#!/usr/bin/env python3
"""Chip smoke: qwen3_4b at its published widths, served on one TPU through
the memos paged engine, checked against a plain float32 reference.

    python chip_smoke.py [--seed N]

One process, one chip.  The weights are random bf16, drawn from
``--seed`` (no checkpoint is shipped).  Two phases share the model:

  A. the default two-tier hierarchy (HBM pool + host tier): 8 requests
     of 256-512 prompt tokens, ``warmup()`` then ``run()``; the HBM
     tier holds about half the KV working set, so memos must migrate
     pages both ways;
  B. the pinned-host hierarchy (``MemoryHierarchy.two_tier(...,
     pinned_slow=True)``) with a small HBM tier, so prompt pages land in
     pinned host memory and the dual-pool dispatches serve them there.

Every phase is checked against a reference that reads the same bf16
weights, upcasts one layer at a time and computes in float32 at the
highest matmul precision, teacher-forced on prompt + generated tokens:
first-token logits after prefill, the final decode dispatch's logits,
and every generated token whose reference top-1/top-2 margin exceeds
``LOGIT_TOL``.  Any failure raises; the last line of standard output is
the JSON result, printed only when both phases passed.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen3_4b"
PAGE = 16
MAX_NEW = 32
N_REQUESTS = 8
PROMPT_LEN = (256, 512)
MAX_PAGES = (PROMPT_LEN[1] + MAX_NEW) // PAGE       # 34 pages per sequence

# Logit tolerance, in logit units.  The engine keeps bf16 weights and
# bf16 activations between ops: every rounding is up to 2**-9 (~0.2%)
# relative, and 36 layers of such roundings on the residual stream leave
# the final hidden state off by about a percent against the float32
# reference.  The unit-scale random head turns that into logit errors of
# a few hundredths typically and about a tenth at the extremes of a
# 151936-entry vocabulary; 0.25 bounds that with room.  Tokens whose
# reference top-1/top-2 margin is inside the same bound are a coin flip
# for any bf16 engine and are not held to the reference argmax.
LOGIT_TOL = 0.25


def expect(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke: {what}")


def require_tpu():
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found — JAX sees only "
                 f"{devs[0].platform} devices; this smoke runs on a TPU")
    return devs


# -----------------------------------------------------------------------------
# the plain float32 reference (independent of repro.models / repro.serving)
# -----------------------------------------------------------------------------

class Reference:
    """Causal forward of the qwen3 stack over one sequence, float32 at
    HIGHEST precision.  Reads the engine's bf16 params and upcasts one
    layer at a time inside the compiled layer program (a float32 copy
    of the whole model would not fit beside the bf16 one)."""

    def __init__(self, cfg, params, seq_len: int):
        import jax
        import jax.numpy as jnp
        self.jax, self.jnp = jax, jnp
        self.cfg, self.params, self.T = cfg, params, seq_len
        self._layer = jax.jit(self._layer_fn)
        self._head = jax.jit(self._head_fn)

    def _rms(self, x, w, eps):
        jnp = self.jnp
        return x * self.jax.lax.rsqrt(
            jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w

    def _rope(self, x, pos):
        # interleaved-pair rotation, theta from the config
        jnp = self.jnp
        half = x.shape[-1] // 2
        freq = 1.0 / (self.cfg.rope_theta
                      ** (jnp.arange(half, dtype=jnp.float32) / half))
        ang = pos[:, None].astype(jnp.float32) * freq          # [T, half]
        c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        xe, xo = x[..., 0::2], x[..., 1::2]
        return jnp.stack([xe * c - xo * s, xe * s + xo * c],
                         axis=-1).reshape(x.shape)

    def _layer_fn(self, layers, l, h):
        jax, jnp, cfg = self.jax, self.jnp, self.cfg
        f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
        lp = jax.tree.map(lambda a: f32(jax.lax.dynamic_index_in_dim(
            a, l, keepdims=False)), layers)
        hp = jax.lax.Precision.HIGHEST
        a = lp["attn"]
        T = h.shape[0]
        pos = jnp.arange(T)
        x = self._rms(h, lp["ln1"], cfg.norm_eps)
        q = jnp.einsum("td,dhk->thk", x, a["wq"], precision=hp)
        k = jnp.einsum("td,dhk->thk", x, a["wk"], precision=hp)
        v = jnp.einsum("td,dhk->thk", x, a["wv"], precision=hp)
        q = self._rope(self._rms(q, a["q_norm"], 1e-6), pos)
        k = self._rope(self._rms(k, a["k_norm"], 1e-6), pos)
        g = cfg.n_heads // cfg.n_kv_heads
        k = jnp.repeat(k, g, axis=1)
        v = jnp.repeat(v, g, axis=1)
        s = jnp.einsum("qhk,shk->hqs", q, k, precision=hp) \
            * cfg.head_dim ** -0.5
        s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
        o = jnp.einsum("hqs,shk->qhk", jax.nn.softmax(s, axis=-1), v,
                       precision=hp)
        h = h + jnp.einsum("thk,hkd->td", o, a["wo"], precision=hp)
        x = self._rms(h, lp["ln2"], cfg.norm_eps)
        m = lp["mlp"]
        gate = jnp.einsum("td,df->tf", x, m["w_gate"], precision=hp)
        up = jnp.einsum("td,df->tf", x, m["w_up"], precision=hp)
        return h + jnp.einsum("tf,fd->td", jax.nn.silu(gate) * up,
                              m["w_down"], precision=hp)

    def _head_fn(self, final_norm, lm_head, h):
        jnp = self.jnp
        x = self._rms(h, final_norm.astype(jnp.float32), self.cfg.norm_eps)
        logits = jnp.einsum("td,dv->tv", x, lm_head.astype(jnp.float32),
                            precision=self.jax.lax.Precision.HIGHEST)
        return logits[:, :self.cfg.vocab]

    def logits(self, tokens: list[int], at: np.ndarray) -> np.ndarray:
        """float32 logits [len(at), vocab] predicting the token after each
        position in ``at`` (causal, so the padding past the sequence never
        reaches them)."""
        jnp, p = self.jnp, self.params
        toks = np.zeros(self.T, np.int32)
        toks[:len(tokens)] = tokens
        h = p["embed"][jnp.asarray(toks)].astype(jnp.float32)
        for l in range(self.cfg.n_layers):
            h = self._layer(p["layers"], l, h)
        out = self._head(p["final_norm"], p["lm_head"],
                         h[jnp.asarray(at, jnp.int32)])
        return np.asarray(out)


# -----------------------------------------------------------------------------
# one serving phase
# -----------------------------------------------------------------------------

class CompileMeter:
    """Counts XLA compilations (and persistent-cache hits) process-wide;
    phases read the difference of two snapshots."""

    def __init__(self):
        from jax import monitoring
        self.n = self.hits = 0
        self.secs = 0.0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.secs += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self):
        return self.n, self.secs, self.hits


def serve(name, cfg, params, scfg, prompts, meter, *, warmup: bool):
    """Serve ``prompts`` through a fresh engine; returns (engine, requests,
    phase stats).  The engine keeps each request's first-token logits."""
    import jax
    from repro.serving import PagedServingEngine

    class Engine(PagedServingEngine):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.first_logits: dict[int, np.ndarray] = {}

        def _prefill_group(self, group):
            super()._prefill_group(group)
            for row, rid in enumerate(self.last_rows):
                self.first_logits[rid] = np.asarray(
                    self.last_logits[row, :cfg.vocab], np.float32)

    before = meter.snapshot()
    eng = Engine(cfg, params, scfg)
    t0 = time.perf_counter()
    if warmup:
        eng.warmup()
    t_warm = time.perf_counter() - t0
    reqs = [eng.submit(p, max_new=MAX_NEW) for p in prompts]
    t1 = time.perf_counter()
    eng.run(max_steps=100_000)
    jax.block_until_ready(eng.last_logits)
    t_run = time.perf_counter() - t1
    n, secs, hits = (a - b for a, b in zip(meter.snapshot(), before))
    st = eng.kv.store
    mig = sum(r.migrations.migrated for r in eng.memos.reports)
    up = sum(b for (s, d), b in st.traffic.items() if s > d)
    down = sum(b for (s, d), b in st.traffic.items() if s < d)
    stats = dict(
        phase=name, warmup_s=t_warm, run_s=t_run, xla_compiles=n,
        xla_compile_s=secs, cache_hits=hits,
        prefill_compiles=(eng.prefill_runner.n_compiles
                          if eng.prefill_runner else 0),
        requests_done=sum(r.done for r in reqs), requests=len(reqs),
        tokens_out=eng.tokens_out, memos_passes=len(eng.memos.reports),
        migrations=mig, bytes_to_host=down, bytes_to_hbm=up)
    print(f"[{name}] compile: {n} XLA compiles, {hits} persistent-cache "
          f"hits, {stats['prefill_compiles']} prefill executables; smoke "
          f"timings: compile {secs:.1f} s, warmup {t_warm:.1f} s")
    print(f"[{name}] served {stats['requests_done']}/{len(reqs)} requests, "
          f"{eng.tokens_out} tokens out, {eng.step_count} decode steps; "
          f"smoke timing (not a benchmark): run {t_run:.1f} s")
    print(f"[{name}] memos: {stats['memos_passes']} passes, {mig} pages "
          f"migrated; traffic HBM->host {down} B, host->HBM {up} B")
    return eng, reqs, stats


def check(name, ref, eng, reqs):
    """Teacher-forced comparison against the float32 reference."""
    first_err = last_err = 0.0
    checked = agreed = skipped = 0
    last_rows = {rid: row for row, rid in enumerate(eng.last_rows)}
    last = np.asarray(eng.last_logits, np.float32)
    for r in reqs:
        seq = r.prompt + r.generated
        lp = len(r.prompt)
        at = np.arange(lp - 1, len(seq) - 1)       # positions that sampled
        ref_logits = ref.logits(seq[:-1], at)        # [len(generated), V]
        first_err = max(first_err, float(np.max(np.abs(
            eng.first_logits[r.rid] - ref_logits[0]))))
        if r.rid in last_rows:
            got = last[last_rows[r.rid], :ref_logits.shape[1]]
            last_err = max(last_err, float(np.max(np.abs(
                got - ref_logits[-1]))))
        top2 = np.sort(ref_logits, axis=-1)[:, -2:]
        margin = top2[:, 1] - top2[:, 0]
        want = np.argmax(ref_logits, axis=-1)
        held = margin > LOGIT_TOL
        checked += int(held.sum())
        skipped += int((~held).sum())
        agreed += int((want[held] == np.asarray(r.generated)[held]).sum())
    print(f"[{name}] vs float32 reference: max |logit err| first-token "
          f"{first_err:.4f}, last dispatch {last_err:.4f} (tol {LOGIT_TOL}); "
          f"tokens agreeing {agreed}/{checked} with margin > tol "
          f"({skipped} inside the margin not held)")
    expect(bool(eng.last_rows), f"{name}: no decode dispatch ran")
    expect(first_err <= LOGIT_TOL, f"{name}: first-token logits off")
    expect(last_err <= LOGIT_TOL, f"{name}: last-dispatch logits off")
    expect(checked > 0 and agreed == checked, f"{name}: tokens disagree")
    return dict(first_err=first_err, last_err=last_err, checked=checked,
                agreed=agreed)


def make_prompts(seed: int, vocab: int) -> list[list[int]]:
    rng = np.random.RandomState(seed)
    lens = rng.randint(PROMPT_LEN[0], PROMPT_LEN[1] + 1, N_REQUESTS)
    return [rng.randint(0, vocab, n).tolist() for n in lens]


def run(cfg, params, prompts, meter) -> list[dict]:
    """Both phases over one model; raises on any failed check."""
    from repro.core.hierarchy import MemoryHierarchy
    from repro.serving import ServeConfig

    ref = Reference(cfg, params, MAX_PAGES * PAGE)
    common = dict(page_size=PAGE, max_batch=4, max_pages_per_seq=MAX_PAGES,
                  decode_block=8, prefill=True,
                  prefill_min_bucket=PROMPT_LEN[1],
                  prefill_max_bucket=PROMPT_LEN[1])
    out = []
    # A: HBM holds about half of the 8 requests' KV working set
    eng, reqs, st = serve(
        "A two-tier", cfg, params,
        ServeConfig(fast_slots=128, slow_slots=512, **common),
        prompts, meter, warmup=True)
    expect(st["requests_done"] == len(reqs), "A: requests left unserved")
    expect(st["migrations"] > 0, "A: memos migrated nothing")
    expect(st["bytes_to_host"] > 0 and st["bytes_to_hbm"] > 0,
           "A: tier traffic missing a direction")
    st.update(check("A two-tier", ref, eng, reqs))
    out.append(st)
    eng.close()
    del eng, reqs
    gc.collect()

    # B: the pinned-host tier serves what the small HBM tier cannot hold
    hier = MemoryHierarchy.two_tier(32, 128, pinned_slow=True)
    eng, reqs, st = serve("B pinned-host", cfg, params,
                          ServeConfig(hierarchy=hier, **common),
                          prompts[:3], meter, warmup=False)
    expect(st["requests_done"] == len(reqs), "B: requests left unserved")
    pinned = eng.kv.store.pools[eng.pinned_tier]
    expect(pinned.data.sharding.memory_kind == "pinned_host",
           "B: the pinned tier's pool left pinned host memory")
    expect(eng.kv.store.writes_to[eng.pinned_tier] > 0,
           "B: no KV write landed in the pinned-host tier")
    st.update(check("B pinned-host", ref, eng, reqs))
    out.append(st)
    eng.close()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devs = require_tpu()
    import jax
    import jax.numpy as jnp
    from repro.configs import get_arch
    from repro.launch.compile_cache import use_compile_cache
    from repro.models import transformer as T

    cache = use_compile_cache()
    meter = CompileMeter()
    dev = devs[0]
    cfg = get_arch(ARCH)
    print(f"device: {dev.platform} {dev.device_kind} x{len(devs)}; "
          f"jax {jax.__version__}; compile cache {cache}")
    print(f"model: {cfg.name} d_model {cfg.d_model}, {cfg.n_layers} layers, "
          f"{cfg.n_heads}/{cfg.n_kv_heads} heads x {cfg.head_dim}, d_ff "
          f"{cfg.d_ff}, vocab {cfg.vocab}; random bf16 weights, seed "
          f"{args.seed}")
    t0 = time.perf_counter()
    params = T.init_params(cfg, jax.random.PRNGKey(args.seed), jnp.bfloat16)
    jax.block_until_ready(params)
    n_params = sum(a.size for a in jax.tree.leaves(params))
    print(f"init: {n_params / 1e9:.3f} B params; smoke timing "
          f"{time.perf_counter() - t0:.1f} s")
    prompts = make_prompts(args.seed, cfg.vocab)
    print(f"traffic: {len(prompts)} requests, prompt lengths "
          f"{[len(p) for p in prompts]}, max_new {MAX_NEW}")
    run(cfg, params, prompts, meter)
    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
