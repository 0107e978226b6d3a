#!/usr/bin/env python3
"""Compile a cell's serving programs for a described TPU v5e, without the
chip, and print what each would hold in device memory.

    JAX_PLATFORMS=cpu python bench/aot.py --workload qwen3_4b.chat \
        [--hbm-pages N] [--ks 8,1]

It compiles the fused decode dispatch for each K and the prefill
dispatch of the cell's bucket at the cell's shapes (``max_batch`` rows,
``max_pages_per_seq`` columns, the HBM tier of the cell file or
``--hbm-pages``), and prints ``memory_analysis()`` of each with the
weights' bytes.  What the compiler refuses here would fail on the chip.
The HBM tier a cell can afford is the chip's ``bytes_limit`` less the
weights, the largest program's temporaries and a margin, in pages.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from functools import partial
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(Path(__file__).resolve().parent))
sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--hbm-pages", type=int, default=None)
    ap.add_argument("--ks", default="8,1")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchlib import spec, weights
    from benchlib.serve import engine_config

    jax.config.update("jax_enable_compilation_cache", False)
    cell = spec.load_cell(args.workload)
    cfg = spec.arch_config(cell.config)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    dev = SingleDeviceSharding(topo.devices[0])
    # the kernels pick their TPU path from the default backend
    jax.default_backend = lambda: "tpu"

    from repro.core import sysmon as sysmon_mod
    from repro.serving import PagedServingEngine

    hbm = args.hbm_pages or cell.cell["hbm_pages"]
    scfg = engine_config(cell, hbm_pages=1, host_pages=1)
    absp = cell.model.abstract_params(cfg)
    shard = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,  # noqa: E731
                                           sharding=dev)
    params = jax.tree.map(shard, absp)
    eng = PagedServingEngine(cfg, params, scfg)
    page_shape = eng.kv.store.cfg.page_shape
    pool = jax.ShapeDtypeStruct((hbm, *page_shape), jnp.bfloat16,
                                sharding=dev)
    n_pages = 2 * hbm
    sm = jax.eval_shape(lambda: sysmon_mod.init(
        n_pages, n_banks=eng.kv.store.cfg.n_banks,
        n_slabs=eng.kv.store.cfg.n_slabs))
    sm = jax.tree.map(shard, sm)
    B, P, page = scfg.max_batch, scfg.max_pages_per_seq, scfg.page_size
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32,  # noqa: E731
                                          sharding=dev)
    out = {"workload": cell.name, "hbm_pages": hbm,
           "page_bytes": int(jnp.dtype(jnp.bfloat16).itemsize
                             * int(jnp.prod(jnp.array(page_shape)))),
           "weight_bytes": weights.param_bytes(absp), "programs": {}}

    def record(name, compiled):
        ma = compiled.memory_analysis()
        out["programs"][name] = {
            k: int(getattr(ma, k)) for k in (
                "argument_size_in_bytes", "output_size_in_bytes",
                "alias_size_in_bytes", "temp_size_in_bytes",
                "generated_code_size_in_bytes")}
        print(name, json.dumps(out["programs"][name]), flush=True)

    for k in [int(x) for x in args.ks.split(",")]:
        fn = jax.jit(partial(eng._fused_decode, k_steps=k),
                     donate_argnums=(8,))
        record(f"decode_k{k}", fn.lower(
            params, i32(B), i32(B), i32(B, P * page), i32(B), i32(B, P),
            i32(B, P), sm, pool,
            jax.ShapeDtypeStruct((B,), jnp.bool_, sharding=dev)).compile())
    bucket = cell.config["engine"]["prefill_bucket"]
    S = scfg.prefill_max_segments
    fn = jax.jit(eng.prefill_runner._core_plain, donate_argnums=(8,))
    record(f"prefill_{bucket}", fn.lower(
        params, i32(bucket), i32(bucket), i32(bucket, P), i32(bucket),
        i32(bucket), i32(bucket), i32(S), pool).compile())
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
