"""Serving driver: batched requests through the paged tiering engine.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3_4b \
      --requests 8 --max-new 16                 # reduced smoke config
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3_4b --no-smoke \
      --prefill --page-size 16 --fast-slots 128  # published widths, bf16

The smoke config keeps float32 weights; ``--no-smoke`` serves the
published widths with random bf16 weights (no checkpoint is shipped).
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch, smoke
from repro.launch.compile_cache import use_compile_cache
from repro.models import transformer as T
from repro.serving import PagedServingEngine, ServeConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3_4b")
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="reduced same-family config (--no-smoke: the "
                         "published widths)")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--max-batch", type=int, default=3)
    ap.add_argument("--fast-slots", type=int, default=24)
    ap.add_argument("--tiers", type=int, choices=(2, 3), default=2,
                    help="2 = HBM->NVM; 3 = HBM->DRAM-sim->NVM demo")
    ap.add_argument("--dram-slots", type=int, default=16,
                    help="middle-tier capacity for --tiers 3")
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--no-memos", action="store_true")
    ap.add_argument("--prefill", action="store_true",
                    help="ingest prompts through bucketed packed prefill")
    args = ap.parse_args()

    use_compile_cache()
    cfg = get_arch(args.arch)
    if args.smoke:
        cfg = smoke(cfg)
    if cfg.layout != "attn":
        raise SystemExit(f"{args.arch}: paged serving engine supports "
                         "attention-layout archs (dense/MoE)")
    params = T.init_params(cfg, jax.random.PRNGKey(0),
                           jnp.float32 if args.smoke else jnp.bfloat16)
    hier = None
    if args.tiers == 3:
        from repro.core.hierarchy import MemoryHierarchy
        hier = MemoryHierarchy.three_tier(args.fast_slots, args.dram_slots,
                                          1024)
    eng = PagedServingEngine(cfg, params, ServeConfig(
        page_size=args.page_size, max_batch=args.max_batch,
        fast_slots=args.fast_slots, slow_slots=1024, hierarchy=hier,
        memos_enabled=not args.no_memos, prefill=args.prefill))

    rng = np.random.RandomState(0)
    reqs = [eng.submit(rng.randint(0, cfg.vocab,
                                   size=rng.randint(3, 14)).tolist(),
                       max_new=args.max_new)
            for _ in range(args.requests)]
    eng.run(max_steps=5000)

    print(f"served {len(reqs)} requests in {eng.step_count} steps; "
          f"{eng.tokens_out} tokens generated")
    lats = [(r.finish_step or 0) - r.arrival for r in reqs]
    print(f"latency steps: mean {np.mean(lats):.1f} max {max(lats)}")
    st = eng.kv.store
    print(f"tier traffic: ->host {st.traffic[(0, 1)]}B  ->HBM "
          f"{st.traffic[(1, 0)]}B  migrations "
          f"{sum(r.migrations.migrated for r in eng.memos.reports)}")
    if eng.expert_counts is not None:
        c = eng.expert_counts
        print(f"expert hotness: top {np.argsort(-c)[:4].tolist()} "
              f"(counts {np.sort(c)[::-1][:4].tolist()}), "
              f"cold experts: {int((c == 0).sum())}/{len(c)}")


if __name__ == "__main__":
    main()
