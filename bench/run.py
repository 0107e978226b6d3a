#!/usr/bin/env python3
"""The benchmark: one cell of ``BENCHMARK.json`` on one chip.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

It serves the cell's traffic mix through the memos paged engine at the
configuration's published widths, with random bf16 weights drawn from
``--seed`` on the device, for ``--seconds`` of open-loop arrivals, then
compares what the window served with the plain float32 reference.  With
``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` its per-layer metrics, read from the program's spans and
counters and from a profiler trace of the window.

What the run did is printed on standard error, the compared numbers with
their limits last; the last line of standard output is the result.  It
exits non-zero, printing no result, where JAX finds no TPU.  Readings
at other rates, tier sizes or with the controls: ``bench/calibrate.py``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))


def _compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (or where ``JAX_COMPILATION_CACHE_DIR`` says), for every
    program whatever its compile time."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT
                                                              / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def main(argv=None, *, allow_cpu: bool = False, breaker=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # libtpu writes its logs under /tmp unless told otherwise; a run
    # writes only inside its checkout and the directories it is given
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    from benchlib import spec
    cell = spec.load_cell(args.workload, ROOT)

    import jax
    devs = jax.devices()
    want = int(cell.workload["chips"])
    if not allow_cpu and (devs[0].platform != "tpu" or len(devs) < want):
        print(f"bench: needs {want} TPU chip(s); JAX sees "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        return 3
    cache = _compile_cache()

    from benchlib.serve import run_cell
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   profile_dir=ROOT / "bench_out" / "profile"
                   / f"{cell.name}.{args.seed}", breaker=breaker)
    lines = out.pop("lines")
    print(f"compile cache: {cache}", file=sys.stderr)
    for line in lines:
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
