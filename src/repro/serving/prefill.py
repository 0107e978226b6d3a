"""Bucketed packed prefill: the serving engine's prompt front door.

Before this module, prompts were *replayed* through the fused decode
scan one token per inner step (``prompt_buf`` in ``engine._fused_decode``)
— prompt ingestion cost a full decode dispatch per ``decode_block``
prompt tokens and TTFT was really queueing delay.  Prefill turns prompt
ingestion into **one dispatch per power-of-two length bucket**, modeled
on the JetStream/MaxText offline engine:

  * **pow2 buckets** — a prompt is padded to the smallest covering
    power-of-two bucket (``bucket_for``), so the set of compiled shapes
    is O(log max_len), not O(distinct prompt lengths);
  * **packing** — short prompts are concatenated into one bucket row
    under per-position segment bookkeeping (``pack_prompts``), so a
    bucket never runs mostly-padding.  Segment isolation is structural:
    each packed position's attention runs through a *per-row block
    table* listing only its own segment's KV pages, so a segment can
    never attend across a packing boundary (there is no foreign page to
    address), and the causal mask is the same ``lengths`` mask the
    decode kernel uses;
  * **one dispatch** — every packed position's K/V is written into the
    tier pools positionally (out-of-bucket padding rows scatter to an
    out-of-range slot and are dropped), full-sequence attention reuses
    ``paged_attention`` verbatim (`kernels.paged_attention_prefill`),
    and the first sampled token of every segment comes back with the
    dispatch — TTFT becomes prompt-length-proportional measurement, not
    approximation;
  * **AOT** — every (bucket, pool-variant) dispatch is precompiled by
    ``PagedServingEngine.warmup()`` via ``jit(...).lower().compile()``,
    so first-request latency is serving time, not compile time.
    ``PrefillRunner.n_compiles`` counts compilations; after warmup it
    must not move (pinned by tests/test_prefill.py).

Bit-parity with the prompt-replay oracle is a hard invariant (tokens,
KV pool contents, SysMon read/write/bank/slab counters): the per-layer
op sequence below mirrors ``engine._decode_core`` /
``_decode_core_pinned`` exactly — same pool scatter, same
``paged_attention`` mask math (masked scores are -1e30 regardless of
what garbage sits beyond a row's causal prefix), same row-independent
norm/projection/FFN einsums — so position ``p`` of a packed segment
produces bitwise the decode-step-at-``p`` output.  What *changes* is
the monitoring cadence: the engine reports the burst to SysMon as one
``record_dense`` streaming sampling instead of K fake decode touches,
so the next memos pass sees a sequential write burst (cold, rarely
touched), exactly the access-pattern asymmetry the paper exploits.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.tiers import to_device_memory, to_pinned_host
from repro.kernels.paged_attention import (paged_attention_prefill,
                                           paged_attention_prefill_pages)
from repro.models import attention as attn_mod
from repro.models import layers as L
from repro.models import transformer as T


# =============================================================================
# buckets + packing (pure host-side policy, no jax)
# =============================================================================

def next_pow2(n: int) -> int:
    """Smallest power of two >= n (n >= 1)."""
    return 1 << max(n - 1, 0).bit_length() if n > 1 else 1


def bucket_for(n: int, min_bucket: int, max_bucket: int) -> int:
    """Smallest covering pow2 bucket for a prompt of ``n`` tokens,
    floored at ``min_bucket``.  Raises ValueError past ``max_bucket`` —
    the caller (``submit``) surfaces that as a structured rejection."""
    if n > max_bucket:
        raise ValueError(
            f"prompt of {n} tokens exceeds the largest prefill bucket "
            f"({max_bucket}); raise prefill_max_bucket / max_pages_per_seq "
            f"or shorten the prompt")
    return max(next_pow2(n), min_bucket)


def bucket_list(min_bucket: int, max_bucket: int) -> list[int]:
    """Every bucket warmup advertises: pow2s in [min_bucket, max_bucket]."""
    out = []
    b = next_pow2(min_bucket)
    while b <= max_bucket:
        out.append(b)
        b *= 2
    return out


@dataclass
class PackedGroup:
    """One prefill dispatch: segments packed into a single bucket row."""
    bucket: int
    requests: list = field(default_factory=list)

    @property
    def total_tokens(self) -> int:
        return sum(len(r.prompt) for r in self.requests)


def pack_prompts(reqs: list, *, min_bucket: int, max_bucket: int,
                 pack: bool = True, max_segments: int = 4
                 ) -> list[PackedGroup]:
    """Greedy packing in admission order (order preservation keeps the
    priority-aware batcher's decisions intact): prompts coalesce into
    one group while the packed total still fits ``max_bucket`` and the
    segment budget holds — the group's bucket *escalates* to the
    smallest pow2 covering the packed total, so a burst of short
    prompts becomes one wide dispatch instead of one dispatch each
    (one host round-trip per group is what makes prefill cheaper than
    absorbing prompts into the batched decode scan)."""
    groups: list[PackedGroup] = []
    i = 0
    while i < len(reqs):
        total = len(reqs[i].prompt)
        bucket_for(total, min_bucket, max_bucket)   # raises past the cap
        members = [reqs[i]]
        i += 1
        if pack:
            while (i < len(reqs) and len(members) < max_segments
                   and total + len(reqs[i].prompt) <= max_bucket):
                members.append(reqs[i])
                total += len(reqs[i].prompt)
                i += 1
        groups.append(PackedGroup(
            bucket=max(next_pow2(total), min_bucket), requests=members))
    return groups


def replay_page_counts(prompt_lens: list[int], page_tables: np.ndarray,
                       page: int, n_pages: int
                       ) -> tuple[np.ndarray, np.ndarray]:
    """Closed-form per-logical-page (reads, writes) event totals for a
    packed prefill, *identical in total to the prompt-replay stream*:
    replaying an ``Lp``-token prompt reads segment page ``j`` once per
    inner step whose prefix covers it (``Lp - j*page`` steps) and writes
    it once per step whose tail lands on it (``min(page, Lp - j*page)``).
    These dense totals feed both the store's version/traffic charge and
    SysMon's ``record_dense`` — raw counters stay bit-identical to the
    oracle while the sampling cadence collapses to one streaming touch."""
    reads = np.zeros(n_pages, np.int64)
    writes = np.zeros(n_pages, np.int64)
    for si, lp in enumerate(prompt_lens):
        n_pg = (lp - 1) // page + 1
        for j in range(n_pg):
            pid = int(page_tables[si, j])
            reads[pid] += lp - j * page
            writes[pid] += min(page, lp - j * page)
    return reads, writes


# =============================================================================
# the jitted prefill dispatches
# =============================================================================

class PrefillRunner:
    """Owns the compiled (bucket, pool-variant) prefill executables.

    ``get_plain``/``get_pinned`` return AOT-compiled executables
    (``jit(...).lower(shapes).compile()``), compiling on first use and
    counting every compile in ``n_compiles`` — ``warmup()`` walks the
    advertised bucket list so serving never compiles."""

    def __init__(self, engine):
        self.eng = engine
        scfg = engine.scfg
        cap = scfg.max_pages_per_seq * scfg.page_size
        self.min_bucket = next_pow2(scfg.prefill_min_bucket)
        self.max_bucket = (next_pow2(scfg.prefill_max_bucket)
                           if scfg.prefill_max_bucket is not None
                           else next_pow2(cap))
        self.max_bucket = min(self.max_bucket, next_pow2(cap))
        self.max_segments = scfg.prefill_max_segments
        self._plain: dict[int, object] = {}
        self._pinned: dict[int, object] = {}
        self.n_compiles = 0

    @property
    def buckets(self) -> list[int]:
        return bucket_list(self.min_bucket, self.max_bucket)

    @property
    def table_pages(self) -> int:
        """Per-row block-table width: the decode dispatch's
        ``max_pages_per_seq`` whatever the bucket, so a prefilled
        position's attention reduces over exactly the columns its
        decode-step twin would (a narrower table changes the softmax
        summation order, and with it the low bits)."""
        return self.eng.scfg.max_pages_per_seq

    # -- core compute (mirrors engine._decode_core op-for-op) -----------------
    def _core_plain(self, params, tokens, local_pos, row_tables, lengths,
                    write_slot, write_off, seg_last, fast_pool):
        """One packed prefill over the tier-0 pool.  tokens/local_pos
        [L] i32 (padding rows: pos 0, length 0); row_tables [L, Pp]
        fast-pool slots of the row's own segment; lengths [L] causal
        prefix length (= local_pos+1, 0 for padding); write_slot [L]
        pool slot for this position's K/V (out-of-range for padding —
        dropped); seg_last [S] row index of each segment's last token.
        Returns (first_tokens [S], seg_logits [S, Vp], expert_counts,
        fast_pool)."""
        cfg = self.eng.cfg
        Lb = tokens.shape[0]
        h = T.embed_in(params, cfg, {"tokens": tokens[None, :]}, None)
        cos, sin = L.rope_angles(local_pos[None, :], cfg.head_dim,
                                 cfg.rope_theta)
        valid = (lengths > 0)[None, :]
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
                fast_pool = fast_pool.at[write_slot, l, 0, write_off].set(
                    k[0].astype(dtype), mode="drop")
                fast_pool = fast_pool.at[write_slot, l, 1, write_off].set(
                    v[0].astype(dtype), mode="drop")
            with jax.named_scope("attention"):
                out = paged_attention_prefill(q[0], fast_pool, l,
                                              row_tables, lengths)
                out = jnp.einsum("bhk,hkd->bd", out.reshape(
                    Lb, cfg.n_heads, cfg.head_dim), p.wo)[None, :, :]
                h = h + out
            with jax.named_scope("mlp"):
                h, counts, _ = T._ffn_block(lp, cfg, h, None, valid=valid)
            if cfg.is_moe and counts is not None:
                counts_acc = counts_acc + counts
        with jax.named_scope("head"):
            h = L.rms_norm(h, params["final_norm"], eps=cfg.norm_eps,
                           gemma_style=cfg.gemma_norm)
            logits = T.logits_out(params, cfg, h)[0]          # [L, Vp]
            seg_logits = logits[seg_last]                     # [S, Vp]
            first = jnp.argmax(seg_logits[:, :cfg.vocab],
                               axis=-1).astype(jnp.int32)
        return first, seg_logits, counts_acc, fast_pool

    def _core_pinned(self, params, tokens, local_pos, row_tables, pool_sel,
                     lengths, write_slot, write_sel, write_off, seg_last,
                     fast_pool, pinned_pool, remap):
        """Dual-pool packed prefill (mirrors ``_decode_core_pinned``):
        block tables hold each page's slot in its own pool — pinned
        logical slots translate through ``remap`` in-dispatch — and each
        position's K/V scatters into whichever pool owns its page, with
        the other pool's index driven out of range and dropped.  Wear
        and integrity for the pinned writes are charged at the boundary
        by the engine (host-side, same totals as per-token charging).
        The pinned pool is moved into device memory for the dispatch and
        placed back in host memory on exit."""
        cfg = self.eng.cfg
        Lb = tokens.shape[0]
        pinned_pool = to_device_memory(pinned_pool)
        n_fast = fast_pool.shape[0]
        n_pin = pinned_pool.shape[0]
        row_tables = jnp.where(
            pool_sel > 0,
            remap[jnp.clip(row_tables, 0, n_pin - 1)], row_tables)
        wslot = jnp.where(write_sel > 0,
                          remap[jnp.clip(write_slot, 0, n_pin - 1)],
                          write_slot)
        f_idx = jnp.where(write_sel > 0, n_fast, wslot)
        p_idx = jnp.where(write_sel > 0, wslot, n_pin)
        sel_pages = (pool_sel > 0)[:, :, None, None, None]
        h = T.embed_in(params, cfg, {"tokens": tokens[None, :]}, None)
        cos, sin = L.rope_angles(local_pos[None, :], cfg.head_dim,
                                 cfg.rope_theta)
        valid = (lengths > 0)[None, :]
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
                fast_pool = fast_pool.at[f_idx, l, 0, write_off].set(
                    k[0].astype(fd), mode="drop")
                fast_pool = fast_pool.at[f_idx, l, 1, write_off].set(
                    v[0].astype(fd), mode="drop")
                pinned_pool = pinned_pool.at[p_idx, l, 0, write_off].set(
                    k[0].astype(pd), mode="drop")
                pinned_pool = pinned_pool.at[p_idx, l, 1, write_off].set(
                    v[0].astype(pd), mode="drop")
            with jax.named_scope("attention"):
                k_pages = jnp.where(sel_pages,
                                    pinned_pool[row_tables, l, 0].astype(fd),
                                    fast_pool[row_tables, l, 0])
                v_pages = jnp.where(sel_pages,
                                    pinned_pool[row_tables, l, 1].astype(fd),
                                    fast_pool[row_tables, l, 1])
                out = paged_attention_prefill_pages(q[0], k_pages, v_pages,
                                                    lengths)
                out = jnp.einsum("bhk,hkd->bd", out.reshape(
                    Lb, cfg.n_heads, cfg.head_dim), p.wo)[None, :, :]
                h = h + out
            with jax.named_scope("mlp"):
                h, counts, _ = T._ffn_block(lp, cfg, h, None, valid=valid)
            if cfg.is_moe and counts is not None:
                counts_acc = counts_acc + counts
        with jax.named_scope("head"):
            h = L.rms_norm(h, params["final_norm"], eps=cfg.norm_eps,
                           gemma_style=cfg.gemma_norm)
            logits = T.logits_out(params, cfg, h)[0]
            seg_logits = logits[seg_last]
            first = jnp.argmax(seg_logits[:, :cfg.vocab],
                               axis=-1).astype(jnp.int32)
        return (first, seg_logits, counts_acc, fast_pool,
                to_pinned_host(pinned_pool))

    # -- AOT compilation ------------------------------------------------------
    def _abstract_params(self):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
            self.eng.params)

    def _compile_plain(self, bucket: int):
        store = self.eng.kv.store
        Pp = self.table_pages
        S = self.max_segments
        i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
        fn = jax.jit(self._core_plain, donate_argnums=(8,))
        compiled = fn.lower(
            self._abstract_params(), i32(bucket), i32(bucket),
            i32(bucket, Pp), i32(bucket), i32(bucket), i32(bucket), i32(S),
            jax.ShapeDtypeStruct(store.fast_pool.shape,
                                 store.fast_pool.dtype)).compile()
        self.n_compiles += 1
        self._plain[bucket] = compiled
        return compiled

    def _compile_pinned(self, bucket: int):
        eng = self.eng
        store = eng.kv.store
        ppool = store.pools[eng.pinned_tier]
        n_pin = ppool.data.shape[0]
        Pp = self.table_pages
        S = self.max_segments
        i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
        # the pinned pool is not donated (see core.tiers)
        fn = jax.jit(self._core_pinned, donate_argnums=(10,))
        compiled = fn.lower(
            self._abstract_params(), i32(bucket), i32(bucket),
            i32(bucket, Pp), i32(bucket, Pp), i32(bucket), i32(bucket),
            i32(bucket), i32(bucket), i32(S),
            jax.ShapeDtypeStruct(store.fast_pool.shape,
                                 store.fast_pool.dtype),
            jax.ShapeDtypeStruct(ppool.data.shape, ppool.data.dtype,
                                 sharding=ppool.data.sharding),
            i32(n_pin)).compile()
        self.n_compiles += 1
        self._pinned[bucket] = compiled
        return compiled

    def get_plain(self, bucket: int):
        return self._plain.get(bucket) or self._compile_plain(bucket)

    def get_pinned(self, bucket: int):
        return self._pinned.get(bucket) or self._compile_pinned(bucket)

    def warmup(self) -> None:
        """AOT-compile every advertised (bucket, pool-variant) dispatch."""
        for b in self.buckets:
            self.get_plain(b)
            if self.eng.pinned_tier is not None:
                self.get_pinned(b)

    # -- host-side arg assembly ----------------------------------------------
    def build_args(self, group: PackedGroup, block_tables: np.ndarray,
                   pool_sel: np.ndarray | None) -> dict[str, np.ndarray]:
        """Expand a packed group's per-*segment* tables into the
        per-*position* arrays the dispatch consumes.  ``block_tables``
        (and ``pool_sel`` on the dual-pool path) are [S, Pp] from
        ``fill_tables``/``fill_tables_mixed`` over the group's requests."""
        eng = self.eng
        page = eng.scfg.page_size
        Lb = group.bucket
        Pp = self.table_pages
        S = self.max_segments
        n_fast = eng.kv.store.fast_pool.shape[0]
        tokens = np.zeros(Lb, np.int32)
        local_pos = np.zeros(Lb, np.int32)
        lengths = np.zeros(Lb, np.int32)
        # padding rows scatter out of range in *both* pools: slot n_fast
        # with sel 0 is dropped by the fast pool, and maps to p_idx n_pin
        # on the pinned path
        write_slot = np.full(Lb, n_fast, np.int32)
        write_sel = np.zeros(Lb, np.int32)
        write_off = np.zeros(Lb, np.int32)
        row_tables = np.zeros((Lb, Pp), np.int32)
        row_sel = np.zeros((Lb, Pp), np.int32)
        seg_last = np.zeros(S, np.int32)
        off = 0
        for si, r in enumerate(group.requests):
            lp = len(r.prompt)
            sl = slice(off, off + lp)
            tokens[sl] = r.prompt
            pos = np.arange(lp, dtype=np.int32)
            local_pos[sl] = pos
            lengths[sl] = pos + 1
            row_tables[sl] = block_tables[si]
            if pool_sel is not None:
                row_sel[sl] = pool_sel[si]
                write_sel[sl] = pool_sel[si, pos // page]
            write_slot[sl] = block_tables[si, pos // page]
            write_off[sl] = pos % page
            seg_last[si] = off + lp - 1
            off += lp
        return dict(tokens=tokens, local_pos=local_pos, lengths=lengths,
                    write_slot=write_slot, write_sel=write_sel,
                    write_off=write_off, row_tables=row_tables,
                    row_sel=row_sel, seg_last=seg_last)
