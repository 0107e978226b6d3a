"""Paged serving engine: continuous batching + memos-managed KV tiering.

The steady state is a **fused multi-token decode dispatch**: K inner
decode steps run inside one jitted ``jax.lax.scan`` whose carry is
``(tokens, positions, SysmonState, fast_pool, page-write counters)`` —
greedy sampling (argmax) happens on device so the sampled token feeds the
next inner step, SysMon's read/write scatter-adds
(``kernels/hotness_update.touch_update``) and the fast-tier version/write
counters ride in the same dispatch, and the host sees **one dispatch and
one device->host transfer per K tokens** instead of ~4 round-trips per
token (decode + argmax pull + two SysMon records).

Host-side ``step()`` is the slow path that runs only at dispatch
boundaries: admit/resume requests, pre-reserve tail pages for the next K
positions, detect finished sequences from the returned token block, and
run the memos pass (plan + migrate + wear/energy snapshot) **between**
dispatches — monitoring stays at pass granularity exactly as in the
paper's Fig. 10, off the decode critical path.

The dispatch size adapts: K = min(decode_block, min remaining steps over
the batch), snapped to a power of two so recompilation stays bounded.
Every sequence therefore stays live for the whole dispatch (no dead-lane
masking), finished sequences are retired exactly at a boundary, and the
generated tokens are bit-identical to the retained K=1 reference path
(``ServeConfig(reference=True)`` — host argmax + standalone per-step
SysMon records), pinned by tests/test_serving.py.

Tiering dynamics are unchanged from the unfused engine:

  * running sequences touch all their pages every step  -> hot  -> stay;
  * the tail page is written every step                  -> WD   -> stay;
  * preempted / finished-prefix pages go quiet           -> cold -> host;
  * resumed sequences eagerly promote their pages (paper's eager mode).

Supports every ``layout == "attn"`` arch (dense + MoE); MoE expert
hotness is accumulated inside the scan and drained per dispatch.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import ArchConfig
from repro.core import sysmon as sysmon_mod
from repro.core.hierarchy import MemoryHierarchy
from repro.core.memos import MemosConfig, MemosManager
from repro.core.tiers import NO_SLOT, to_device_memory, to_pinned_host
from repro.faults.errors import CapacityError, PageCorruptionError
from repro.faults.injector import get_injector, note_recovered
from repro.kernels.paged_attention import paged_attention, paged_attention_pages
from repro.kernels.wear_update import wear_update
from repro.models import attention as attn_mod
from repro.models import layers as L
from repro.models import transformer as T
from repro.qos import QoSConfig
from repro.serving.kv_cache import SERVE_TIER, PagedKVCache, PagedKVConfig
from repro.serving.prefill import (PackedGroup, PrefillRunner, pack_prompts,
                                   replay_page_counts)
from repro.serving.scheduler import ContinuousBatcher, Request


@dataclass
class ServeConfig:
    page_size: int = 16
    max_batch: int = 4
    fast_slots: int = 48
    slow_slots: int = 512
    # full tier stack (e.g. MemoryHierarchy.three_tier for the
    # HBM -> DRAM-sim -> NVM-sim scenario); None -> two_tier(fast, slow)
    hierarchy: MemoryHierarchy | None = None
    memos_interval: int = 8
    max_pages_per_seq: int = 64
    memos_enabled: bool = True
    # NVM wear feedback horizon (years); None = telemetry only, no feedback
    lifetime_horizon_years: float | None = None
    # K: inner decode steps per fused dispatch (latency vs. dispatch
    # amortization; the effective K shrinks near sequence ends)
    decode_block: int = 8
    # overlap the memos *plan* phase with the next dispatch on a worker
    # thread (snapshot -> plan -> commit; the pass's migrations commit at
    # the following dispatch boundary, degrading to the synchronous pass
    # when pages were dirtied mid-plan)
    overlap_plan: bool = False
    # retained unfused K=1 path — host-side sampling + standalone SysMon
    # records; the parity oracle and the pre-fusion throughput baseline
    reference: bool = False
    # multi-tenant QoS (repro.qos): tenant classes + priorities, page
    # utility weights into memos placement, and the dynamic-power cap.
    # None — or a bare QoSConfig() with no tenants and no budget — keeps
    # every scheduler and placement decision bit-identical to pre-QoS
    # behavior (pinned by tests/test_qos.py).
    qos: QoSConfig | None = None
    # bucketed packed prefill (serving/prefill.py): newly admitted
    # requests ingest their whole prompt in one pow2-bucket dispatch
    # instead of replaying it through the decode scan.  Off by default
    # — the replay path is the bit-parity oracle — and ignored under
    # reference=True (the oracle IS prompt replay).
    prefill: bool = False
    prefill_min_bucket: int = 16
    # largest bucket (pow2-rounded); None -> covers max_pages_per_seq
    prefill_max_bucket: int | None = None
    # pack multiple short prompts into one bucket row (segment-isolated)
    prefill_pack: bool = True
    prefill_max_segments: int = 4


class PagedServingEngine:
    def __init__(self, cfg: ArchConfig, params: dict, scfg: ServeConfig):
        assert cfg.layout == "attn", "paged engine serves attention archs"
        self.cfg = cfg
        self.params = params
        self.scfg = scfg
        # KV pages are stored in the parameters' dtype (bf16 weights ->
        # bf16 pages; float32 smoke configs keep float32 pools)
        self.kv = PagedKVCache(PagedKVConfig(
            n_layers=cfg.n_layers, n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.head_dim, page_size=scfg.page_size,
            fast_slots=scfg.fast_slots, slow_slots=scfg.slow_slots,
            dtype=params["embed"].dtype, hierarchy=scfg.hierarchy))
        store = self.kv.store
        # dual-pool serving: when the deepest tier is a (lossless)
        # pinned-host pool, its pages are served and appended in place by
        # the fused dispatch — no promote-before-attend, and the tier's
        # wear counters ride the scan
        pt = self.kv.pinned_tier
        if pt is not None and store.pools[pt].quantized:
            pt = None     # int8 pools can't absorb token-granular appends
        self.pinned_tier = pt
        # in-dispatch Start-Gap: the fused dual-pool scan advances the
        # pinned tier's gap itself whenever this many pinned writes have
        # accumulated (0 = no leveler / untracked tier -> compiled out)
        lv = (store.leveler_by_tier.get(pt) if pt is not None else None)
        self._gap_interval = (lv.interval if lv is not None
                              and store.wear_by_tier.get(pt) is not None
                              and store.pools[pt].data.shape[0] >= 2
                              else 0)
        self.sysmon = sysmon_mod.init(
            self.kv.n_pages, n_banks=store.cfg.n_banks,
            n_slabs=store.cfg.n_slabs)
        qos = scfg.qos
        self.memos = MemosManager(store, MemosConfig(
            interval=scfg.memos_interval, adaptive_interval=False,
            lifetime_horizon_years=scfg.lifetime_horizon_years,
            async_plan=scfg.overlap_plan,
            power_cap_mw=qos.power_budget_mw if qos is not None else None,
            power_recover_passes=(qos.power_recover_passes
                                  if qos is not None else 2)))
        # priority-aware scheduling engages only when tenants are actually
        # configured: a bare QoSConfig() keeps the literal legacy admission
        # code path, making the bit-identity pin structural
        self.batcher = ContinuousBatcher(
            scfg.max_batch,
            priority_aware=bool(qos is not None and qos.priority_aware
                                and qos.tenants))
        self.step_count = 0
        self.expert_counts = (np.zeros(cfg.n_experts, np.int64)
                              if cfg.is_moe else None)
        self.tokens_out = 0
        self.rid = 0
        self.last_logits = None     # final inner step's logits, on device
        self.last_rows: list[int] = []  # request id of each last_logits row
        self._decode_fn = jax.jit(self._decode_batch, donate_argnums=(5,))
        self._decode_pinned_fn = jax.jit(self._decode_batch_pinned,
                                         donate_argnums=(6,))
        self._fused_fns: dict[int, object] = {}
        self._fused_pinned_fns: dict[int, object] = {}
        self.prefill_runner = (PrefillRunner(self)
                               if scfg.prefill and not scfg.reference
                               else None)
        # prompt tokens ingested by prefill since the last memos tick —
        # the pass's sampling clock advances by them (replay would have
        # spent that many inner decode steps), drained at step 6
        self._prefill_tokens_pending = 0

    # -- request API -----------------------------------------------------------
    def submit(self, prompt: list[int], max_new: int, *,
               tenant: str | None = None) -> Request:
        cap = self.scfg.max_pages_per_seq * self.scfg.page_size
        if len(prompt) + max_new > cap:
            # structured rejection (a bare assert vanishes under -O): the
            # sequence can never fit, so refuse at the door instead of
            # failing mid-serve with a CapacityError nobody can act on
            raise CapacityError(
                f"sequence needs {len(prompt) + max_new} positions but "
                f"max_pages_per_seq*page_size = {cap}")
        if (self.prefill_runner is not None
                and len(prompt) > self.prefill_runner.max_bucket):
            raise CapacityError(
                f"prompt of {len(prompt)} tokens exceeds the largest "
                f"prefill bucket ({self.prefill_runner.max_bucket}); raise "
                f"prefill_max_bucket (or max_pages_per_seq) or split the "
                f"prompt")
        req = Request(self.rid, list(prompt), max_new, arrival=self.step_count)
        req.submit_ts = time.monotonic()
        if tenant is not None:
            req.tenant = tenant
        qos = self.scfg.qos
        if qos is not None:
            spec = qos.spec(tenant)
            req.priority = spec.priority
            if qos.placement_weights:
                req.weight = spec.page_weight
            if spec.deadline_s is not None:
                req.deadline = req.submit_ts + spec.deadline_s
        req.tokens = []          # processed tokens (prompt-consumed + generated)
        req.generated = []       # type: ignore[attr-defined]
        self.rid += 1
        self.batcher.submit(req)
        return req

    # -- page management ---------------------------------------------------------
    def _servable_mask(self, pids):
        """Pages the dispatch can attend to: tier-0 residents, plus the
        pinned deepest tier's residents on the dual-pool path."""
        if self.pinned_tier is not None:
            return self.kv.servable_mask(pids)
        return self.kv.resident_mask(pids)

    def _ensure_pages(self, req: Request, k: int = 1) -> bool:
        """Provision ``req`` for the next ``k`` decode positions: allocate
        the tail pages covering pos .. pos+k-1 and promote every
        non-servable page — the whole span must be addressable by the
        dispatch's block table (HBM, or the pinned-host tier on the
        dual-pool path, where pages are served in place)."""
        need = (req.pos + k - 1) // self.scfg.page_size + 1
        while len(req.pages) < need:
            pid = self.kv.new_page(SERVE_TIER)
            if pid is None:
                return False
            req.pages.append(pid)
            if req.weight != 1.0:
                # tenant utility weight rides onto the page for the memos
                # planner (demotion resistance + ranking multiplier)
                self.memos.set_page_weight([pid], req.weight)
        return self._promote_all([req])

    def _release_pages(self, req: Request) -> None:
        """Free a retired request's pages, first resetting any tenant
        utility weight back to neutral — recycled pages must not inherit
        the previous owner's demotion resistance."""
        if req.weight != 1.0 and req.pages:
            self.memos.set_page_weight(req.pages, 1.0)
        for pid in req.pages:
            self.kv.free_page(pid)
        req.pages = []

    def _promote_all(self, reqs: list[Request]) -> bool:
        """Promote every non-servable page of ``reqs`` in one batched
        migration (single plan->execute bulk move instead of per-request
        per-page copies)."""
        pids = [p for req in reqs for p in req.pages]
        if not pids:
            return True
        mask = self._servable_mask(pids)
        if not mask.all():
            cold = [p for p, m in zip(pids, mask) if not m]
            self.memos.engine.migrate_locked(cold, SERVE_TIER)
            mask = self._servable_mask(pids)
        return bool(mask.all())

    def _make_room(self, max_priority: int | None = None) -> bool:
        victim = self.batcher.preempt_lowest(max_priority)
        if victim is None:
            return False
        obs.get_registry().counter(
            "serving.preemptions",
            "running sequences preempted for capacity").inc()
        # eagerly demote the victim's serving-tier pages: preemption must
        # actually free tier-0 slots, because the lazy memos drain only
        # runs between dispatches and admission can be blocked *now*
        # (livelock otherwise when the pool is smaller than two
        # sequences' demand).  Walk the backing tiers deepest-first so a
        # full deepest tier cascades into any intermediate tier with room.
        store = self.kv.store
        for dst in range(store.n_tiers - 1, 0, -1):
            still = [p for p in victim.pages
                     if int(store.tier[p]) == SERVE_TIER]
            if not still:
                break
            self.memos.engine.migrate_optimistic(still, dst)
        return True

    # -- fault handling (repro.faults) -----------------------------------------
    def _fail_request(self, req: Request, err: Exception) -> None:
        """Terminally fail one request with a structured error: release
        its pages (quarantined pages have no slot left — ``release`` is a
        no-op for them and only the logical id returns) and retire it
        through the scheduler so the batch keeps serving."""
        self._release_pages(req)
        self.batcher.fail(req, self.step_count, err)
        obs.get_registry().counter(
            "serving.failed_requests",
            "requests retired with a structured error").inc()

    def _drain_faults(self) -> None:
        """Fail every sequence owning a page the store quarantined since
        the last drain (scrub, promotion pre-flight, pre-dispatch verify)
        — the page's bits are unrecoverable, so the owner errors cleanly
        instead of ever serving from a corrupt page."""
        store = self.kv.store
        if not store.quarantine_log:
            return
        bad = set(store.quarantine_log)
        store.quarantine_log.clear()
        everyone = (self.batcher.active + list(self.batcher.preempted)
                    + list(self.batcher.waiting))
        for req in everyone:
            hit = sorted(bad.intersection(req.pages))
            if hit:
                self._fail_request(req, PageCorruptionError(
                    f"request {req.rid}: page(s) {hit} lost to media "
                    f"corruption", rid=req.rid, pages=hit))

    def _predispatch_verify(self, active: list[Request]) -> None:
        """Last line of the zero-corrupted-tokens invariant: before the
        block tables are built, re-verify the checksum of every page this
        dispatch would serve out of the pinned-host pool (tier 0 is
        trusted media; host-tier pages verify on promotion pre-flight
        instead).  A mismatch quarantines the slot, and the following
        drain fails the owner before it can attend to the bits."""
        pt = self.pinned_tier
        store = self.kv.store
        if pt is None or not store.integrity.enabled:
            return
        slots = {int(store.slot[p]) for r in active for p in r.pages
                 if int(store.tier[p]) == pt
                 and int(store.slot[p]) != NO_SLOT}
        for s in store.integrity.verify(store, pt, sorted(slots)):
            store.quarantine_slot(pt, s, reason="pre-dispatch")

    # -- jitted model compute ------------------------------------------------------
    def _decode_core(self, params, tokens, positions, block_tables,
                     lengths, fast_pool, live):
        """One decode step for the batch: write the new token's K/V into
        the pool *before* attention (exact self-attention), run the layer
        stack through paged_attention.  tokens [B] i32; positions [B];
        block_tables [B,P] fast-slot ids; lengths [B] (incl. current
        token); live [B] bool, False for the padding rows that fill the
        dispatch to its fixed width (their K/V writes are dropped, they
        attend to nothing and they count no experts).  The kernel reads
        each layer's pages straight out of ``fast_pool``, after that
        layer's append.  Returns (logits [B,Vp], expert_counts|0, new
        fast_pool)."""
        cfg = self.cfg
        page = self.scfg.page_size
        B = tokens.shape[0]
        h = T.embed_in(params, cfg, {"tokens": tokens[:, None]}, None)
        cos, sin = L.rope_angles(positions[:, None], cfg.head_dim,
                                 cfg.rope_theta)
        b_idx = jnp.arange(B)
        slot = jnp.where(live, block_tables[b_idx, positions // page],
                         fast_pool.shape[0])
        off = positions % page
        # padding rows attend to nothing: the kernel fetches no page for
        # them
        lengths = jnp.where(live, lengths, 0)
        counts_acc = (jnp.zeros((cfg.n_experts,), jnp.int32)
                      if cfg.is_moe else jnp.int32(0))
        for l in range(cfg.n_layers):
            lp = T._tree_slice(params["layers"], l)
            with jax.named_scope("attention"):
                x = L.rms_norm(h, lp["ln1"], eps=cfg.norm_eps,
                               gemma_style=cfg.gemma_norm)
                p = T._attn_from_dict(lp["attn"])
                q, k, v = attn_mod.project_qkv(p, x, cos, sin)
            with jax.named_scope("kv_append"):
                dtype = fast_pool.dtype
                fast_pool = fast_pool.at[slot, l, 0, off].set(
                    k[:, 0].astype(dtype), mode="drop")
                fast_pool = fast_pool.at[slot, l, 1, off].set(
                    v[:, 0].astype(dtype), mode="drop")
            with jax.named_scope("attention"):
                out = paged_attention(q[:, 0], fast_pool, l, block_tables,
                                      lengths)
                out = jnp.einsum("bhk,hkd->bd", out.reshape(
                    B, cfg.n_heads, cfg.head_dim), p.wo)[:, None, :]
                h = h + out
            with jax.named_scope("mlp"):
                h, counts, _ = T._ffn_block(lp, cfg, h, None,
                                            valid=live[:, None])
            if cfg.is_moe and counts is not None:
                counts_acc = counts_acc + counts
        with jax.named_scope("head"):
            h = L.rms_norm(h, params["final_norm"], eps=cfg.norm_eps,
                           gemma_style=cfg.gemma_norm)
            logits = T.logits_out(params, cfg, h)[:, 0]
        return logits, counts_acc, fast_pool

    def _decode_batch(self, params, tokens, positions, block_tables,
                      lengths, fast_pool, live):
        """Retained K=1 reference entry point (tokens [B,1]); sampling and
        SysMon charging stay on the host."""
        return self._decode_core(params, tokens[:, 0], positions,
                                 block_tables, lengths, fast_pool, live)

    # -- dual-pool (pinned-host deepest tier) decode -----------------------------
    def _decode_core_pinned(self, params, tokens, positions, block_tables,
                            pool_sel, lengths, fast_pool, pinned_pool,
                            remap, live):
        """One decode step with the KV split across the tier-0 pool and
        the pinned-host pool: pages are attended wherever they live
        (per-page select after a dual gather) and the new token's K/V
        lands in whichever pool holds the tail page — the slow-tier KV
        append joins the dispatch instead of forcing a promotion.

        block_tables [B,P] hold each page's slot *in its own pool* —
        tier-0 pool slot, or the pinned pool's **logical** slot, which is
        translated through ``remap`` (the wear-leveling logical->physical
        permutation, [n_pin] i32) here inside the dispatch: the fused
        path carries the remap in its scan and rotates it as Start-Gap
        advances swap rows mid-dispatch, so translation can't happen on
        the host anymore.  pool_sel [B,P] is 1 for pinned pages.  Rows
        whose tail lives in the other pool write through an out-of-range
        index dropped by the scatter (``mode="drop"``), so a numeric slot
        collision between the two pools can never clobber a real
        write; padding rows (``live`` False) write to neither pool."""
        cfg = self.cfg
        page = self.scfg.page_size
        B = tokens.shape[0]
        h = T.embed_in(params, cfg, {"tokens": tokens[:, None]}, None)
        cos, sin = L.rope_angles(positions[:, None], cfg.head_dim,
                                 cfg.rope_theta)
        b_idx = jnp.arange(B)
        n_fast = fast_pool.shape[0]
        n_pin = pinned_pool.shape[0]
        # pinned entries -> physical rows under the *current* remap (fast
        # entries pass through; the clip keeps the dead gather in range)
        block_tables = jnp.where(
            pool_sel > 0,
            remap[jnp.clip(block_tables, 0, n_pin - 1)], block_tables)
        tailcol = positions // page
        slot = block_tables[b_idx, tailcol]
        sel_tail = pool_sel[b_idx, tailcol] > 0
        off = positions % page
        # OOB (dropped) for pinned tails / fast tails / padding rows
        f_idx = jnp.where(sel_tail | ~live, n_fast, slot)
        p_idx = jnp.where(sel_tail & live, slot, n_pin)
        sel_pages = (pool_sel > 0)[:, :, None, None, None]
        counts_acc = (jnp.zeros((cfg.n_experts,), jnp.int32)
                      if cfg.is_moe else jnp.int32(0))
        for l in range(cfg.n_layers):
            lp = T._tree_slice(params["layers"], l)
            with jax.named_scope("attention"):
                x = L.rms_norm(h, lp["ln1"], eps=cfg.norm_eps,
                               gemma_style=cfg.gemma_norm)
                p = T._attn_from_dict(lp["attn"])
                q, k, v = attn_mod.project_qkv(p, x, cos, sin)
            with jax.named_scope("kv_append"):
                fd, pd = fast_pool.dtype, pinned_pool.dtype
                fast_pool = fast_pool.at[f_idx, l, 0, off].set(
                    k[:, 0].astype(fd), mode="drop")
                fast_pool = fast_pool.at[f_idx, l, 1, off].set(
                    v[:, 0].astype(fd), mode="drop")
                pinned_pool = pinned_pool.at[p_idx, l, 0, off].set(
                    k[:, 0].astype(pd), mode="drop")
                pinned_pool = pinned_pool.at[p_idx, l, 1, off].set(
                    v[:, 0].astype(pd), mode="drop")
            with jax.named_scope("attention"):
                # dual gather + per-page select (out-of-range slots clamp
                # and are discarded by the select)
                k_pages = jnp.where(
                    sel_pages, pinned_pool[block_tables, l, 0].astype(fd),
                    fast_pool[block_tables, l, 0])
                v_pages = jnp.where(
                    sel_pages, pinned_pool[block_tables, l, 1].astype(fd),
                    fast_pool[block_tables, l, 1])
                out = paged_attention_pages(q[:, 0], k_pages, v_pages,
                                            lengths)
                out = jnp.einsum("bhk,hkd->bd", out.reshape(
                    B, cfg.n_heads, cfg.head_dim), p.wo)[:, None, :]
                h = h + out
            with jax.named_scope("mlp"):
                h, counts, _ = T._ffn_block(lp, cfg, h, None,
                                            valid=live[:, None])
            if cfg.is_moe and counts is not None:
                counts_acc = counts_acc + counts
        with jax.named_scope("head"):
            h = L.rms_norm(h, params["final_norm"], eps=cfg.norm_eps,
                           gemma_style=cfg.gemma_norm)
            logits = T.logits_out(params, cfg, h)[:, 0]
        return logits, counts_acc, fast_pool, pinned_pool

    def _decode_batch_pinned(self, params, tokens, positions, block_tables,
                             pool_sel, lengths, fast_pool, pinned_pool,
                             remap, live):
        """Retained K=1 reference entry point for the dual-pool path."""
        logits, counts, fast_pool, pinned_pool = self._decode_core_pinned(
            params, tokens[:, 0], positions, block_tables, pool_sel, lengths,
            fast_pool, to_device_memory(pinned_pool), remap, live)
        return logits, counts, fast_pool, to_pinned_host(pinned_pool)

    @staticmethod
    def _advance_prompt(positions, prompt_buf, prompt_len, sampled, b_idx):
        """Advance one inner decode step: the next position, and the next
        input token — the buffered prompt token while replay is still
        inside the prompt, the freshly sampled token once past it.
        Shared by every fused scan body (single- and dual-pool)."""
        nxt_pos = positions + 1
        prompt_next = prompt_buf[
            b_idx, jnp.clip(nxt_pos, 0, prompt_buf.shape[1] - 1)]
        nxt_tok = jnp.where(nxt_pos < prompt_len, prompt_next, sampled)
        return nxt_tok, nxt_pos

    def _fused_decode(self, params, tokens, positions, prompt_buf,
                      prompt_len, page_tables, block_tables, sm_state,
                      fast_pool, live, *, k_steps: int):
        """K inner decode steps in one dispatch: a ``lax.scan`` carrying
        (tokens, positions, SysmonState, fast_pool, page-write counters).
        Greedy sampling, the SysMon read/write scatter-adds, and the
        fast-tier write counters all stay on device; the host gets back
        one [K, B] token block per dispatch.

        tokens/positions [B]; prompt_buf [B, Lp] padded prompt tokens;
        prompt_len [B]; page_tables [B, P] logical page ids (SysMon's
        id space); block_tables [B, P] fast-pool slots; sm_state and
        fast_pool are donated loop state; live [B] marks real rows (the
        padding rows charge SysMon and the write counters nothing).
        """
        cfg = self.cfg
        page = self.scfg.page_size
        B, P = block_tables.shape
        b_idx = jnp.arange(B)
        col = jnp.arange(P, dtype=jnp.int32)[None, :]
        vp = (params["embed"].shape[0] if cfg.tie_embeddings
              else params["lm_head"].shape[1])
        counts0 = (jnp.zeros((cfg.n_experts,), jnp.int32)
                   if cfg.is_moe else jnp.int32(0))

        def body(carry, _):
            tokens, positions, sm, pool, page_writes, counts_acc, _ = carry
            logits, counts, pool = self._decode_core(
                params, tokens, positions, block_tables, positions + 1, pool,
                live)
            # device-side greedy sampling feeds the next inner step
            with jax.named_scope("head"):
                sampled = jnp.argmax(logits[:, :cfg.vocab],
                                     axis=-1).astype(jnp.int32)
            nxt_tok, nxt_pos = self._advance_prompt(
                positions, prompt_buf, prompt_len, sampled, b_idx)
            with jax.named_scope("sysmon"):
                # SysMon: the exact access stream — one read sampling over
                # the block-table prefix covering the current position,
                # one write sampling on the tail page (same two-sampling
                # cadence as the reference path, so pass counters are
                # bit-comparable)
                tailcol = positions // page
                sm = sysmon_mod.record(
                    sm, page_tables.reshape(-1), is_write=False,
                    valid=((col <= tailcol[:, None])
                           & live[:, None]).reshape(-1))
                tails = page_tables[b_idx, tailcol]
                sm = sysmon_mod.record(sm, tails, is_write=True, valid=live)
                # fast-tier version/write counters (the dirty bits
                # optimistic migration checks) accumulate on device,
                # applied in bulk at the dispatch boundary
                page_writes = page_writes.at[tails].add(
                    live.astype(jnp.int32))
            if cfg.is_moe:
                counts_acc = counts_acc + counts
            return (nxt_tok, nxt_pos, sm, pool, page_writes, counts_acc,
                    logits), sampled

        carry0 = (tokens, positions, sm_state, fast_pool,
                  jnp.zeros((sm_state.n_pages,), jnp.int32), counts0,
                  jnp.zeros((B, vp), jnp.float32))
        (_, _, sm, pool, page_writes, counts, logits), sampled = \
            jax.lax.scan(body, carry0, None, length=k_steps)
        return sampled, logits, sm, pool, page_writes, counts

    def _get_fused(self, k: int):
        fn = self._fused_fns.get(k)
        if fn is None:
            # only the pool is donated: SysmonState fields routinely alias
            # one shared zeros buffer (init/end_pass), which XLA rejects
            # as a double donation — and the state is tiny anyway
            step = partial(self._fused_decode, k_steps=k)
            # the program's name in profiler traces (a partial has none,
            # and would trace as ``jit__unknown``)
            step.__name__ = "_fused_decode"
            fn = jax.jit(step, donate_argnums=(8,))       # fast_pool
            self._fused_fns[k] = fn
        return fn

    def _fused_decode_pinned(self, params, tokens, positions, prompt_buf,
                             prompt_len, page_tables, block_tables, pool_sel,
                             sm_state, fast_pool, pinned_pool, wear, remap,
                             gap, pending, live, *, k_steps: int,
                             gap_interval: int):
        """The dual-pool fused dispatch: K inner steps with KV appends
        landing in either pool and the pinned tier's wear counters riding
        the scan carry — each inner step's slow-tier tail write
        scatter-adds its physical row through the ``wear_update`` kernel.
        Start-Gap leveling runs *inside the dispatch* but **after the
        scan**: the scan accumulates the pinned write count, then a
        single ``while_loop`` performs every advance the dispatch earned
        — swap physical rows (gap, gap+1) of the pinned pool, swap the
        two entries of the remap, charge both rows' wear — the same
        adjacent-row-swap the host leveler performs.  Keeping the loop
        out of the scan body keeps the hot inner step fully fused (an
        in-step ``while_loop`` cost ~35% on CPU even when it never
        fired), while leveling still never serializes the boundary with
        un-jitted whole-pool row swaps; advance *totals* are unchanged
        by the deferred cadence (each advance drains exactly one
        interval), so gap/rotation/remap/pool state stays bit-identical
        to per-token leveling — only the attribution of in-flight app
        writes to pre- vs post-swap physical rows can differ within one
        dispatch.  The boundary adopts (wear, remap, gap, pending,
        #advances) back into the host trackers.  ``gap_interval`` 0
        disables in-dispatch leveling (untracked or unleveled pinned
        tiers); SysMon, sampling, and the page-write counters are
        unchanged from the single-pool path.  The pinned pool enters the
        program in host memory and is moved to device memory for the
        scan; the updated pool is placed back in host memory on exit."""
        cfg = self.cfg
        page = self.scfg.page_size
        B, P = block_tables.shape
        b_idx = jnp.arange(B)
        col = jnp.arange(P, dtype=jnp.int32)[None, :]
        pinned_pool = to_device_memory(pinned_pool)
        n_pin = pinned_pool.shape[0]
        vp = (params["embed"].shape[0] if cfg.tie_embeddings
              else params["lm_head"].shape[1])
        counts0 = (jnp.zeros((cfg.n_experts,), jnp.int32)
                   if cfg.is_moe else jnp.int32(0))

        def advance_gap(state):
            """One Start-Gap move, mirroring StartGapLeveler.advance."""
            ppool, wear, remap, gap, pending, n_adv = state
            nxt = gap + 1
            pair = jnp.stack([gap, nxt])
            ppool = ppool.at[pair].set(ppool[jnp.stack([nxt, gap])])
            remap = jnp.where(remap == gap, nxt,
                              jnp.where(remap == nxt, gap, remap))
            # the swap physically rewrites both rows (leveling overhead)
            wear = wear.at[gap].add(1).at[nxt].add(1)
            gap = jnp.where(nxt >= n_pin - 1, 0, nxt)
            return ppool, wear, remap, gap, pending - gap_interval, n_adv + 1

        def body(carry, _):
            (tokens, positions, sm, fpool, ppool, wear, pin_w,
             page_writes, counts_acc, _) = carry
            logits, counts, fpool, ppool = self._decode_core_pinned(
                params, tokens, positions, block_tables, pool_sel,
                positions + 1, fpool, ppool, remap, live)
            with jax.named_scope("head"):
                sampled = jnp.argmax(logits[:, :cfg.vocab],
                                     axis=-1).astype(jnp.int32)
            nxt_tok, nxt_pos = self._advance_prompt(
                positions, prompt_buf, prompt_len, sampled, b_idx)
            with jax.named_scope("sysmon"):
                tailcol = positions // page
                sm = sysmon_mod.record(
                    sm, page_tables.reshape(-1), is_write=False,
                    valid=((col <= tailcol[:, None])
                           & live[:, None]).reshape(-1))
                tails = page_tables[b_idx, tailcol]
                sm = sysmon_mod.record(sm, tails, is_write=True, valid=live)
                page_writes = page_writes.at[tails].add(
                    live.astype(jnp.int32))
                # pinned-tier wear: tails living in the pinned pool charge
                # their physical row — under the carried remap — on device
                # (amount 0 for fast tails)
                tail_slot = block_tables[b_idx, tailcol]
                tail_pin = pool_sel[b_idx, tailcol] * live.astype(jnp.int32)
                tail_phys = remap[jnp.clip(tail_slot, 0, n_pin - 1)]
                wear = wear_update(wear, tail_phys, amount=tail_pin)
                pin_w = pin_w + tail_pin.sum()
            if cfg.is_moe:
                counts_acc = counts_acc + counts
            return (nxt_tok, nxt_pos, sm, fpool, ppool, wear, pin_w,
                    page_writes, counts_acc, logits), sampled

        carry0 = (tokens, positions, sm_state, fast_pool, pinned_pool, wear,
                  jnp.int32(0), jnp.zeros((sm_state.n_pages,), jnp.int32),
                  counts0, jnp.zeros((B, vp), jnp.float32))
        (_, _, sm, fpool, ppool, wear, pin_w, page_writes, counts,
         logits), sampled = \
            jax.lax.scan(body, carry0, None, length=k_steps)
        n_adv = jnp.int32(0)
        if gap_interval:    # static: compiled out when leveling is off
            pending = pending + pin_w
            ppool, wear, remap, gap, pending, n_adv = jax.lax.while_loop(
                lambda s: s[4] >= gap_interval, advance_gap,
                (ppool, wear, remap, gap, pending, n_adv))
        return (sampled, logits, sm, fpool, to_pinned_host(ppool), wear,
                remap, gap, pending, n_adv, page_writes, counts)

    def _get_fused_pinned(self, k: int):
        fn = self._fused_pinned_fns.get(k)
        if fn is None:
            # the pinned pool is not donated (see core.tiers)
            step = partial(self._fused_decode_pinned, k_steps=k,
                           gap_interval=self._gap_interval)
            step.__name__ = "_fused_decode_pinned"     # as in _get_fused
            fn = jax.jit(step, donate_argnums=(9,))      # fast_pool
            self._fused_pinned_fns[k] = fn
        return fn

    def _page_read_counts(self, positions: np.ndarray,
                          page_tables: np.ndarray, k: int) -> np.ndarray:
        """Per-logical-page read counts for one K-step dispatch: page j of
        a row is read by every inner step whose block-table prefix covers
        it (closed form, no device work)."""
        page = self.scfg.page_size
        P = page_tables.shape[1]
        n_prefix = (positions[:, None] + np.arange(k)[None, :]) // page + 1
        cnt = (n_prefix[:, None, :] > np.arange(P)[None, :, None]).sum(2)
        reads = np.zeros(self.kv.n_pages, np.int64)
        np.add.at(reads, page_tables.reshape(-1), cnt.reshape(-1))
        return reads

    def warmup(self, batch: int | None = None) -> None:
        """Pre-compile every fused dispatch variant this engine can emit
        — each power-of-two K up to ``decode_block``, on the single-pool
        path and (when a pinned tier exists) the dual-pool path — against
        dummy inputs of the given batch width.  A production server does
        this at boot: the dispatch variant actually used at a boundary
        depends on runtime state (tail shrinkage, pinned residency), and
        a mid-stream compile would stall serving for seconds."""
        B = batch or self.scfg.max_batch
        P = self.scfg.max_pages_per_seq
        page = self.scfg.page_size
        store = self.kv.store
        sm = sysmon_mod.init(self.kv.n_pages, n_banks=store.cfg.n_banks,
                             n_slabs=store.cfg.n_slabs)
        zi = lambda *s: jnp.zeros(s, jnp.int32)  # noqa: E731
        ks = []
        k = 1
        while k <= self.scfg.decode_block:
            ks.append(k)
            k *= 2
        live = jnp.ones((B,), bool)
        for k in ks:
            args = (self.params, zi(B), zi(B), zi(B, P * page), zi(B),
                    zi(B, P), zi(B, P))
            # the fast pool is donated by the dispatch: hand each call
            # its own dummy copy, never the live buffer (the pinned pool
            # is only read)
            jax.block_until_ready(
                self._get_fused(k)(*args, sm,
                                   jnp.zeros_like(store.fast_pool), live)[0])
            if self.pinned_tier is not None:
                ppool = store.pools[self.pinned_tier]
                # match the live dispatch's wear-array shape exactly: the
                # real tracker's counters, or the shape-(1,) dummy used
                # when the pinned tier is untracked
                wtr = store.wear_by_tier.get(self.pinned_tier)
                wear = zi(ppool.data.shape[0] if wtr is not None else 1)
                remap = jnp.arange(ppool.data.shape[0], dtype=jnp.int32)
                jax.block_until_ready(
                    self._get_fused_pinned(k)(
                        *args, zi(B, P), sm,
                        jnp.zeros_like(store.fast_pool),
                        ppool.data, wear, remap,
                        jnp.int32(0), jnp.int32(0), live)[0])
        # prefill: AOT-compile every advertised (bucket, pool-variant)
        # dispatch — .lower().compile() against abstract shapes, so no
        # dummy pool copies are needed and serving never recompiles
        if self.prefill_runner is not None:
            self.prefill_runner.warmup()

    # -- main loop (dispatch-boundary slow path) -----------------------------------
    def _publish_dispatch_metrics(self, dt: float, k: int, batch: int,
                                  rows: int) -> None:
        """Per-dispatch latency + throughput metrics (looked up by name
        each dispatch so registry resets between sweep configs take
        effect).  ``batch`` rows are live of the ``rows`` the dispatch
        ran."""
        reg = obs.get_registry()
        reg.histogram("serving.dispatch_latency_s",
                      "wall time of one fused decode dispatch").observe(dt)
        # one dispatch advances every live row by k tokens: per-token
        # latency is dt/k, weighted k so quantiles are over tokens
        reg.histogram("serving.token_latency_s",
                      "per-token decode latency (dispatch wall / K)"
                      ).observe(dt / k, n=k)
        reg.counter("serving.dispatches", "decode dispatches issued").inc()
        reg.counter("serving.tokens_sampled",
                    "tokens sampled across all rows").inc(k * batch)
        reg.counter("serving.decode_row_slots",
                    "row-steps the decode dispatches ran, padding rows "
                    "included").inc(k * rows)
        for qn, qv in self.batcher.depths().items():
            reg.gauge(f"serving.queue_{qn}",
                      f"scheduler {qn} queue depth").set(qv)

    def _publish_page_fetches(self, pages: int, slots: int) -> None:
        """Paged-attention work of one single-pool dispatch: per layer,
        the kernel fetched ``pages`` live pages, of the ``slots`` (padded
        row, table column) pairs a walk of every row's whole table would
        visit; the counters add both for every layer."""
        n = self.cfg.n_layers
        reg = obs.get_registry()
        reg.counter("serving.attn_pages_fetched",
                    "KV pages the paged-attention kernel fetched, all "
                    "layers").inc(pages * n)
        reg.counter("serving.attn_page_slots",
                    "padded rows x table columns of the paged-attention "
                    "calls, all layers").inc(slots * n)

    def _publish_first_token(self, req: Request) -> None:
        """Wall-clock TTFT, aggregate + per-tenant (metric-name label)."""
        if req.ttft_s is None:
            return
        reg = obs.get_registry()
        reg.histogram("serving.ttft_s",
                      "wall-clock time to first token").observe(req.ttft_s)
        reg.histogram(f"qos.ttft_s.{req.tenant}",
                      "per-tenant wall-clock TTFT").observe(req.ttft_s)

    def _publish_finish(self, req: Request) -> None:
        """Wall-clock end-to-end latency + mean inter-token latency for a
        completed request, aggregate + per-tenant."""
        if req.e2e_s is None:
            return
        reg = obs.get_registry()
        reg.histogram("serving.e2e_latency_s",
                      "wall-clock submit-to-finish latency").observe(
                          req.e2e_s)
        reg.histogram(f"qos.e2e_s.{req.tenant}",
                      "per-tenant wall-clock e2e latency").observe(req.e2e_s)
        if req.first_token_ts is not None and len(req.generated) > 1:
            itl = ((req.finish_ts - req.first_token_ts)
                   / (len(req.generated) - 1))
            reg.histogram(f"qos.itl_s.{req.tenant}",
                          "per-tenant mean inter-token latency").observe(
                              itl, n=len(req.generated) - 1)

    # -- bucketed packed prefill (serving/prefill.py) --------------------------
    def _prefill_admitted(self) -> None:
        new = [r for r in self.batcher.active if r.pos == 0]
        if not new:
            return
        pr = self.prefill_runner
        groups = pack_prompts(
            new, min_bucket=pr.min_bucket, max_bucket=pr.max_bucket,
            pack=self.scfg.prefill_pack, max_segments=pr.max_segments)
        for g in groups:
            self._prefill_group(g)

    def _prefill_group(self, group: PackedGroup) -> None:
        """One packed prefill dispatch: provision every segment's prompt
        pages, run the (bucket, pool-variant) executable, then settle the
        boundary accounting — store charges, SysMon streaming record,
        pinned wear/integrity, first-token stamping — with totals exactly
        matching what replaying the prompts through the decode scan would
        have charged (the parity invariant), while SysMon's sampling
        cadence sees ONE sequential burst instead of K decode touches."""
        # provision under pressure: preempt, dropping group members that
        # got evicted themselves (they re-enter at a later boundary with
        # pos still 0), and fail the blocked request when nothing is left
        segs = []
        while True:
            self._drain_faults()
            segs = [r for r in group.requests
                    if not r.preempted and not r.done]
            blocked = None
            for r in segs:
                if not self._ensure_pages(r, k=len(r.prompt)):
                    blocked = r
                    break
            if blocked is None:
                break
            if not self._make_room():
                self._fail_request(blocked, CapacityError(
                    f"request {blocked.rid}: HBM+host pools exhausted "
                    f"during prefill and no preemption victim remains",
                    rid=blocked.rid, occupancy=self.kv.occupancy()))
                note_recovered("backpressure")
        group.requests = segs
        if not segs:
            return

        pr = self.prefill_runner
        store = self.kv.store
        page = self.scfg.page_size
        pt = self.pinned_tier
        Pp = pr.table_pages
        pages_rows = [r.pages for r in segs]
        with obs.span("serve.tables", step=self.step_count):
            if pt is None:
                page_tables, block_tables = self.kv.fill_tables(pages_rows,
                                                                Pp)
                pool_sel = None
                wear_tr = None
            else:
                page_tables, block_tables, pool_sel = \
                    self.kv.fill_tables_mixed(pages_rows, Pp)
                wear_tr = store.wear_by_tier.get(pt)
                if not pool_sel.any():
                    # all prompt pages landed tier-0 resident: single-pool
                    # dispatch (same downgrade the decode boundary applies)
                    pt = None
                    pool_sel = None
                    wear_tr = None
            a = pr.build_args(group, block_tables, pool_sel)
        n_tok = group.total_tokens
        t0 = time.perf_counter()
        with obs.span("serve.prefill", step=self.step_count,
                      bucket=group.bucket, segments=len(segs),
                      tokens=n_tok) as prefill_sp:
            if obs.tracing_enabled():
                prefill_sp.set(rids=[r.rid for r in segs])
            if pt is None:
                fn = pr.get_plain(group.bucket)
                first_d, seg_logits, ecounts, store.fast_pool = fn(
                    self.params, jnp.asarray(a["tokens"]),
                    jnp.asarray(a["local_pos"]),
                    jnp.asarray(a["row_tables"]), jnp.asarray(a["lengths"]),
                    jnp.asarray(a["write_slot"]),
                    jnp.asarray(a["write_off"]),
                    jnp.asarray(a["seg_last"]), store.fast_pool)
            else:
                ppool = store.pools[pt]
                n_pin = ppool.data.shape[0]
                remap_arr = (wear_tr.state.remap if wear_tr is not None
                             else jnp.arange(n_pin, dtype=jnp.int32))
                fn = pr.get_pinned(group.bucket)
                (first_d, seg_logits, ecounts, store.fast_pool,
                 ppool.data) = fn(
                    self.params, jnp.asarray(a["tokens"]),
                    jnp.asarray(a["local_pos"]),
                    jnp.asarray(a["row_tables"]), jnp.asarray(a["row_sel"]),
                    jnp.asarray(a["lengths"]), jnp.asarray(a["write_slot"]),
                    jnp.asarray(a["write_sel"]),
                    jnp.asarray(a["write_off"]),
                    jnp.asarray(a["seg_last"]), store.fast_pool, ppool.data,
                    remap_arr)
            with obs.span("serve.prefill.wait"):
                first = np.asarray(first_d)
        dt = time.perf_counter() - t0
        self.last_logits = seg_logits
        self.last_rows = [r.rid for r in segs]
        with obs.span("serve.account", step=self.step_count):
            reg = obs.get_registry()
            reg.histogram("serving.prefill_latency_s",
                          "wall time of one packed prefill dispatch"
                          ).observe(dt)
            reg.counter("serving.prefill_dispatches",
                        "packed prefill dispatches issued").inc()
            reg.counter("serving.prefill_tokens",
                        "prompt tokens ingested via prefill").inc(n_tok)
            reg.counter("serving.prefill_slots",
                        "bucket positions the prefill dispatches ran, "
                        "padding included").inc(group.bucket)

            if self.expert_counts is not None:
                self.expert_counts += np.asarray(ecounts, np.int64)

            # boundary accounting: closed-form dense totals, bit-identical
            # to the replay stream (reads: page j of an Lp-token segment
            # is covered by Lp - j*page inner-step prefixes; writes: the
            # tail lands on it min(page, Lp - j*page) times)
            prompt_lens = [len(r.prompt) for r in segs]
            d_reads, d_writes = replay_page_counts(
                prompt_lens, page_tables, page, self.kv.n_pages)
            self.sysmon = sysmon_mod.record_dense(
                self.sysmon, jnp.asarray(d_reads, dtype=jnp.int32),
                jnp.asarray(d_writes, dtype=jnp.int32))
            if pt is None:
                store.charge_fast_accesses(d_writes, int(d_reads.sum()))
                lens = np.asarray(a["lengths"])
                self._publish_page_fetches(
                    int(((lens + page - 1) // page).sum()),
                    lens.shape[0] * Pp)
            else:
                store.charge_accesses(d_writes, d_reads)
                # pinned-pool writes charge wear per token write (the
                # decode scan's wear_update totals, host-side) and refresh
                # the written rows' checksums — the in-dispatch scatters
                # bypass the host write paths that normally record both
                wr_slots: list[int] = []
                for si, lp in enumerate(prompt_lens):
                    for j in range((lp - 1) // page + 1):
                        if pool_sel[si, j]:
                            wr_slots.extend([int(block_tables[si, j])]
                                            * min(page, lp - j * page))
                if wear_tr is not None and wr_slots:
                    store._account_host_writes(
                        pt, wear_tr.phys(np.asarray(wr_slots, np.int64)))
                if store.integrity.enabled and wr_slots:
                    store.integrity.record(store, pt, sorted(set(wr_slots)))

        # lifecycle: the prompt is consumed and the first token sampled —
        # the request joins the decode batch at pos == len(prompt), or
        # retires right here when one token was all it asked for
        for req, first_tok in zip(segs, first[:len(segs)]):
            req.tokens = list(req.prompt)
            req.generated = [int(first_tok)]
            self.tokens_out += 1
            req.first_token_step = self.step_count
            req.first_token_ts = time.monotonic()
            self._publish_first_token(req)
            if req.max_new <= 1:
                self.batcher.finish(req, self.step_count)
                self._publish_finish(req)
                self._release_pages(req)
        self._prefill_tokens_pending += n_tok

    def step(self) -> dict:
        # 0) fail owners of pages quarantined since the last boundary
        # (memos-pass scrub, late promotion pre-flights) before admitting
        # against the shrunken pool
        self._drain_faults()
        # 1) admit / resume; make room by preempting if promotion fails.
        # A request that fails provisioning twice in one step is making no
        # progress (its blocker holds the pool) — stop admitting and let
        # the dispatch/memos machinery below free capacity first.
        failed: set[int] = set()
        # power governor (repro.qos): while over the dynamic-power budget
        # the admission width shrinks one slot per throttle level, so the
        # write stream — and with it NVM dynamic power — backs off
        gov = self.memos.governor
        limit = (gov.batch_limit(self.scfg.max_batch)
                 if gov is not None else None)
        admitted_rids = [] if obs.tracing_enabled() else None
        with obs.span("serve.admit", step=self.step_count) as admit_sp:
            while True:
                admitted = self.batcher.admit(limit)
                if not admitted:
                    break
                obs.get_registry().counter(
                    "serving.admissions",
                    "requests admitted into decode slots").inc(len(admitted))
                if admitted_rids is not None:
                    admitted_rids.extend(r.rid for r in admitted)
                ok = True
                stuck = False
                need_room = 0
                for req in admitted:
                    if req.start_step is None:
                        req.start_step = self.step_count
                    if not self._ensure_pages(req):
                        ok = False
                        need_room = max(need_room, req.priority)
                        stuck = stuck or req.rid in failed
                        failed.add(req.rid)
                if stuck:
                    break
                # admission-time preemption is priority-bounded: freeing
                # room for a request may only evict strictly lower
                # priority (unbounded preemption stays reserved for the
                # provision loop, where the dispatch must proceed)
                if not ok and not self._make_room(
                        need_room - 1 if self.batcher.priority_aware
                        else None):
                    break
            if admitted_rids:
                admit_sp.set(rids=admitted_rids)

        # 1b) prefill: every newly admitted request (pos == 0 — nothing
        # processed yet) ingests its whole prompt in one packed bucketed
        # dispatch and joins the running decode batch with its first
        # token already sampled.  Resumed mid-prompt requests (preempted
        # replay) keep the replay path — their pool state is positional.
        if self.prefill_runner is not None:
            self._prefill_admitted()

        active = list(self.batcher.active)
        stats = {"step": self.step_count, "active": len(active)}
        if not active:
            self.step_count += 1
            return stats

        # 2) size the dispatch: K bounded by every sequence's remaining
        # budget (rows stay live for the whole dispatch — finished
        # sequences retire exactly at the boundary), snapped to a power of
        # two so the set of compiled scan lengths stays small
        if self.scfg.reference:
            k = 1
        else:
            k = max(min(self.scfg.decode_block,
                        min(r.remaining_steps for r in active)), 1)
            k = 1 << (k.bit_length() - 1)

        # 3) provision: pre-reserve tail pages for all K positions; under
        # HBM pressure first shrink the dispatch, then preempt (the K=1
        # reference semantics) — preempting to feed a large dispatch
        # would thrash
        with obs.span("serve.provision", step=self.step_count) as prov_sp:
            while True:
                # promotion pre-flights inside _ensure_pages can
                # quarantine a corrupt source page: fail its owner now so
                # the retry below provisions against the survivors
                self._drain_faults()
                active = [r for r in active if not r.done]
                blocked = None
                for req in active:
                    if not req.preempted and not self._ensure_pages(req, k):
                        blocked = req
                        break
                if blocked is None:
                    break
                if k > 1:
                    k //= 2
                elif not self._make_room():
                    # backpressure floor: even at K=1 with nothing left
                    # to preempt the pools cannot host this sequence's
                    # next page — retire it with a structured capacity
                    # error instead of crashing the whole server
                    self._fail_request(blocked, CapacityError(
                        f"request {blocked.rid}: HBM+host pools exhausted "
                        f"and no preemption victim remains",
                        rid=blocked.rid, occupancy=self.kv.occupancy()))
                    note_recovered("backpressure")
            prov_sp.set(k=k)
        active = [r for r in active if not r.preempted and not r.done]
        # pre-dispatch integrity sweep: quarantine any pinned-pool page
        # whose stored bits drifted since its last checksum, and fail its
        # owner, *before* the block tables are built — the dispatch never
        # attends to corrupt bits
        if get_injector().enabled:
            self._predispatch_verify(active)
            self._drain_faults()
            active = [r for r in active if not r.done]
        if not active:
            self.step_count += 1
            return stats

        B = len(active)
        P = self.scfg.max_pages_per_seq
        page = self.scfg.page_size
        store = self.kv.store
        pt = self.pinned_tier
        with obs.span("serve.tables", step=self.step_count):
            positions = np.array([r.pos for r in active], np.int32)
            prompt_lens = np.array([len(r.prompt) for r in active], np.int32)
            tokens = np.array([(r.prompt + r.generated)[r.pos]
                               for r in active], np.int32)
            if pt is None:
                page_tables, block_tables = self.kv.fill_tables(
                    [r.pages for r in active], P)
                pool_sel = None
                wear_tr = None
            else:
                page_tables, block_tables, pool_sel = \
                    self.kv.fill_tables_mixed([r.pages for r in active], P)
                wear_tr = store.wear_by_tier.get(pt)
                if not pool_sel.any():
                    # every page of this dispatch is tier-0 resident: the
                    # block tables are plain fast-pool slots, so take the
                    # single-pool fast path — the dual-pool dispatch
                    # (second gather + select per layer) only pays for
                    # itself when a page actually lives in the pinned tier
                    pt = None
                    pool_sel = None
                    wear_tr = None

            # the dispatch always runs max_batch rows: padding rows (live
            # False) keep one program per K however far the batch drains,
            # so warmup covers every shape serving emits
            Bp = max(self.scfg.max_batch, B)

            def rows(a):
                pad = [(0, Bp - B)] + [(0, 0)] * (a.ndim - 1)
                return jnp.asarray(np.pad(a, pad))

            live = jnp.asarray(np.arange(Bp) < B)
            d_tokens, d_pos = rows(tokens), rows(positions)
            d_pages, d_blocks = rows(page_tables), rows(block_tables)
            d_sel = rows(pool_sel) if pool_sel is not None else None
            prompt_buf = np.zeros((Bp, P * page), np.int32)
            for i, r in enumerate(active):
                prompt_buf[i, :len(r.prompt)] = r.prompt

        dispatch_path = (("reference" if self.scfg.reference else "fused")
                         + ("+pinned" if pt is not None else ""))
        # wall clock measured independently of tracing — the latency
        # histograms must populate with the tracer disabled
        t_disp0 = time.perf_counter()
        with obs.span("serve.dispatch", step=self.step_count, k=k, batch=B,
                      path=dispatch_path):
            if self.scfg.reference and pt is None:
                # -- retained K=1 reference path (parity oracle / baseline)
                logits, ecounts, store.fast_pool = self._decode_fn(
                    self.params, d_tokens[:, None], d_pos, d_blocks,
                    d_pos + 1, store.fast_pool, live)
                logits = logits[:B]
                # host-side argmax sampling: one transfer per token
                sampled_d = jnp.argmax(logits[:, :self.cfg.vocab], axis=-1)
                with obs.span("serve.dispatch.wait"):
                    sampled = np.asarray(sampled_d, np.int32)[None, :]
                # standalone per-step SysMon records — the host round-trips
                # the fused path folds into its scan
                read_valid = (np.arange(P)[None, :]
                              <= (positions // page)[:, None])
                self.sysmon = sysmon_mod.record(
                    self.sysmon, jnp.asarray(page_tables.reshape(-1)),
                    is_write=False, valid=jnp.asarray(read_valid.reshape(-1)))
                tails = page_tables[np.arange(B), positions // page]
                self.sysmon = sysmon_mod.record(
                    self.sysmon, jnp.asarray(tails), is_write=True)
                page_writes = np.zeros(store.cfg.n_pages, np.int64)
                np.add.at(page_writes, tails, 1)
                self.last_logits = logits
            elif self.scfg.reference:
                # -- K=1 reference path over the dual pools (parity oracle)
                ppool = store.pools[pt]
                n_pin = ppool.data.shape[0]
                remap_arr = (wear_tr.state.remap if wear_tr is not None
                             else jnp.arange(n_pin, dtype=jnp.int32))
                logits, ecounts, store.fast_pool, ppool.data = \
                    self._decode_pinned_fn(
                        self.params, d_tokens[:, None], d_pos, d_blocks,
                        d_sel, d_pos + 1, store.fast_pool, ppool.data,
                        remap_arr, live)
                logits = logits[:B]
                sampled_d = jnp.argmax(logits[:, :self.cfg.vocab], axis=-1)
                with obs.span("serve.dispatch.wait"):
                    sampled = np.asarray(sampled_d, np.int32)[None, :]
                read_valid = (np.arange(P)[None, :]
                              <= (positions // page)[:, None])
                self.sysmon = sysmon_mod.record(
                    self.sysmon, jnp.asarray(page_tables.reshape(-1)),
                    is_write=False, valid=jnp.asarray(read_valid.reshape(-1)))
                tails = page_tables[np.arange(B), positions // page]
                self.sysmon = sysmon_mod.record(
                    self.sysmon, jnp.asarray(tails), is_write=True)
                page_writes = np.zeros(store.cfg.n_pages, np.int64)
                np.add.at(page_writes, tails, 1)
                # host-side wear charge for pinned tail writes (the fused
                # path folds this into the scan; totals are bit-identical).
                # The block tables carry *logical* pinned slots now, so
                # translate through the remap before charging the physical
                # rows — this also drives the host leveler, whose advances
                # the next dispatch picks up through ``wear_tr.state.remap``.
                tcol = positions // page
                tslot = block_tables[np.arange(B), tcol]
                tpin = pool_sel[np.arange(B), tcol] > 0
                if wear_tr is not None and tpin.any():
                    store._account_host_writes(pt, wear_tr.phys(tslot[tpin]))
                self.last_logits = logits
            elif pt is None:
                # -- fused K-step dispatch ---------------------------------
                fn = self._get_fused(k)
                (sampled_d, logits, self.sysmon, store.fast_pool,
                 page_writes_d, ecounts) = fn(
                    self.params, d_tokens, d_pos, jnp.asarray(prompt_buf),
                    rows(prompt_lens), d_pages, d_blocks, self.sysmon,
                    store.fast_pool, live)
                # one transfer per K tokens
                with obs.span("serve.dispatch.wait"):
                    sampled = np.asarray(sampled_d)[:, :B]
                    page_writes = np.asarray(page_writes_d)
                self.last_logits = logits[:B]
            else:
                # -- fused K-step dual-pool dispatch: slow-tier KV appends
                # and the wear_update scatter-add ride the same scan -------
                ppool = store.pools[pt]
                n_pin_rows = ppool.data.shape[0]
                wear_arr = (wear_tr.state.wear if wear_tr is not None
                            else jnp.zeros((1,), jnp.int32))
                remap_arr = (wear_tr.state.remap if wear_tr is not None
                             else jnp.arange(n_pin_rows, dtype=jnp.int32))
                lv = (store.leveler_by_tier.get(pt)
                      if self._gap_interval else None)
                gap0 = jnp.int32(lv.stats.gap if lv is not None else 0)
                pending0 = jnp.int32(lv._pending if lv is not None else 0)
                fn = self._get_fused_pinned(k)
                (sampled_d, logits, self.sysmon, store.fast_pool, ppool.data,
                 wear_out, remap_out, gap_out, pending_out, n_adv_out,
                 page_writes_d, ecounts) = fn(
                    self.params, d_tokens, d_pos, jnp.asarray(prompt_buf),
                    rows(prompt_lens), d_pages, d_blocks, d_sel, self.sysmon,
                    store.fast_pool, ppool.data, wear_arr, remap_arr, gap0,
                    pending0, live)
                with obs.span("serve.dispatch.wait"):
                    sampled = np.asarray(sampled_d)[:, :B]
                    page_writes = np.asarray(page_writes_d)
                if wear_tr is not None:
                    n_pin_w = int(page_writes[store.tier == pt].sum())
                    n_adv = int(n_adv_out)
                    # adopt the dispatch's wear counters (app writes + the
                    # two row rewrites each in-dispatch gap advance
                    # charged), its rotated remap, and the leveler's
                    # (gap, pending) bookkeeping — the boundary replays
                    # counter arithmetic only, never pool row swaps
                    with obs.span("serve.startgap_adopt", advances=n_adv):
                        wear_tr.adopt_scan_writes(wear_out, n_pin_w,
                                                  leveling_writes=2 * n_adv)
                        if n_adv:
                            wear_tr.adopt_scan_remap(remap_out)
                        if lv is not None:
                            lv.adopt_scan_advances(n_adv, int(pending_out))
                self.last_logits = logits[:B]
        dispatch_dt = time.perf_counter() - t_disp0
        self.last_rows = [r.rid for r in active]
        with obs.span("serve.account", step=self.step_count):
            self._publish_dispatch_metrics(dispatch_dt, k, B, Bp)

            if self.expert_counts is not None:
                self.expert_counts += np.asarray(ecounts, np.int64)

            # 4) access accounting, vectorized: device-counted page writes
            # bump versions in one add; reads are closed-form.  The
            # dual-pool path splits the charge by each page's tier (the
            # dispatch touched both the fast pool and the pinned tier).
            if pt is None:
                n_reads = int(((positions[:, None] + np.arange(k)[None, :])
                               // page + 1).sum())
                store.charge_fast_accesses(page_writes, n_reads)
                # every inner step's read of a live page is one page the
                # paged-attention kernel fetched, per layer
                self._publish_page_fetches(n_reads, k * Bp * P)
            else:
                page_reads = self._page_read_counts(positions, page_tables,
                                                    k)
                store.charge_accesses(page_writes, page_reads)
            # refresh checksums for pinned-pool rows the dispatch appended
            # to (the in-scan tail writes bypass the host write paths that
            # normally record them)
            if pt is not None and store.integrity.enabled:
                written = np.nonzero(page_writes > 0)[0]
                wmask = (store.tier[written] == pt) & \
                    (store.slot[written] != NO_SLOT)
                if wmask.any():
                    store.integrity.record(
                        store, pt, np.unique(store.slot[written[wmask]]))

        # 5) advance sequences from the returned token block: tokens
        # sampled at inner step s >= emit_from[i] are new generations
        with obs.span("serve.retire", step=self.step_count):
            emit_from = np.maximum(prompt_lens - 1 - positions, 0)
            for i, req in enumerate(active):
                had_gen = bool(req.generated)
                new_gen = [int(t) for t in sampled[emit_from[i]:k, i]]
                req.generated.extend(new_gen)
                self.tokens_out += len(new_gen)
                if new_gen and not had_gen:
                    # first token of this request surfaced in this block:
                    # stamp both clocks (wall for reporting, step for the
                    # deterministic QoS gates — the inner step that
                    # sampled it)
                    req.first_token_step = self.step_count + int(emit_from[i])
                    req.first_token_ts = time.monotonic()
                    self._publish_first_token(req)
                seq = req.prompt + req.generated
                p0 = int(positions[i])
                req.tokens.extend(seq[p0:p0 + k])
                if len(req.generated) >= req.max_new:
                    self.batcher.finish(req, self.step_count + k - 1)
                    self._publish_finish(req)
                    self._release_pages(req)

        # 6) memos loop between dispatches (hot pages stay; cold/preempted
        # pages drain to host) — pass granularity, off the decode hot
        # path.  With overlap_plan the pass's plan phase runs on a worker
        # thread across the *next* dispatch and commits at the following
        # boundary (maybe_step returns that commit's report).
        if self.scfg.memos_enabled:
            # on_commit: re-promote pages an async commit demoted out from
            # under running sequences *before* the next plan snapshots, so
            # the reaction is part of the snapshot instead of a guaranteed
            # mid-plan conflict at the next commit
            # the memos sampling clock also advances by every prompt
            # token prefill ingested since the last tick (replay would
            # have spent that many inner decode steps)
            pending = self._prefill_tokens_pending
            self._prefill_tokens_pending = 0
            self.sysmon, report = self.memos.maybe_step(
                self.sysmon, steps=k + pending,
                on_commit=lambda rep: self._promote_all(
                    list(self.batcher.active)))
            if report is not None:
                stats["memos"] = {
                    "migrated": report.migrations.migrated,
                    "to_fast": report.migrations.to_fast,
                    "to_slow": report.migrations.to_slow,
                    "wear_pressure": report.wear_pressure,
                    "power_pressure": report.power_pressure,
                    "power_throttle": report.power_throttle,
                    "power_mw": report.power_mw,
                    "committed_async": report.committed_async,
                    "plan_conflict": report.plan_conflict,
                    "pages_committed": report.pages_committed,
                    "pages_degraded": report.pages_degraded,
                    "pages_dropped": report.pages_dropped,
                }
                if report.nvm is not None:
                    stats["nvm"] = {
                        "wear_max": report.nvm.wear_max,
                        "slow_writes": report.nvm.slow_writes,
                        "dynamic_power_mw": report.nvm.dynamic_power_mw,
                        "lifetime_years": report.nvm.lifetime_years_actual,
                    }
                # single bulk promotion for every page the memos pass
                # demoted out from under a still-running sequence (async
                # commits already promoted via on_commit above)
                if not self.scfg.overlap_plan:
                    self._promote_all(list(self.batcher.active))

        with obs.span("serve.account", step=self.step_count):
            if not self.scfg.memos_enabled:
                # no memos pass ever rolls the bandwidth-headroom window,
                # so roll it at dispatch boundaries — otherwise cascade
                # targeting would rank tiers by lifetime-cumulative inflow
                store.roll_traffic_window()

            # 7) fault-injection tick, strictly *after* every write path
            # of this boundary has recorded its checksums and *before*
            # the next boundary's pre-dispatch verify — injected
            # corruption always has a detection point ahead of the next
            # serve
            inj = get_injector()
            if inj.enabled:
                inj.tick(store)

        self.step_count += k
        stats["decode_block"] = k
        stats["tokens_out"] = self.tokens_out
        stats.update(self.kv.occupancy())
        return stats

    def run(self, max_steps: int = 10_000) -> list[dict]:
        hist = []
        while not self.batcher.all_done() and self.step_count < max_steps:
            hist.append(self.step())
        # commit any plan still overlapping when the workload drains, so
        # stores/telemetry are consistent for inspection across runs
        if self.scfg.memos_enabled:
            report = self.memos.flush()
            if report is not None and self.batcher.active:
                self._promote_all(list(self.batcher.active))
        return hist

    def close(self) -> None:
        """Release the engine's background resources (the async memos
        plan worker); safe to call multiple times."""
        self.memos.close()
