#!/usr/bin/env python3
"""Readings for setting a cell's limit and rate: several seeds of one cell
in one process, each a whole run (set-up, window, comparison), optionally
with the controls read on the same served tokens.

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        --seconds <s> [--control fp8,int8] [--rate R] [--hbm-pages N]

Prints one JSON line per seed: the compared number, each control's
reading, and the run's end-to-end numbers.  Never run by the check.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import re
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def main(argv=None, *, allow_cpu: bool = False) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", default="")
    ap.add_argument("--rate", type=float, default=None)
    ap.add_argument("--hbm-pages", type=int, default=None)
    args = ap.parse_args(argv)
    # libtpu writes its logs under /tmp unless told otherwise; a run
    # writes only inside its checkout and the directories it is given
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax
    if not allow_cpu and jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 3
    import run
    run._compile_cache()
    from benchlib import spec
    from benchlib.serve import run_cell
    cell = spec.load_cell(args.workload, BENCH.parent)
    hbm = args.hbm_pages
    for seed in [int(s) for s in args.seeds.split(",")]:
        out = run_cell(cell, seed, args.seconds, False, rate=args.rate,
                       hbm_pages=hbm, host_pages=(2 * hbm if hbm else None),
                       control=tuple(q for q in args.control.split(",")
                                     if q))
        lines = out.pop("lines")
        for line in lines:
            print(f"[{seed}] {line}", file=sys.stderr)
        row = {"seed": seed, "correct": out["correct"],
               "checks": out["checks"],
               "metrics": {k: v["value"] for k, v in out["metrics"].items()}}
        for line in lines:
            m = re.match(r"control (\w+): max_gap ([0-9.e+-]+)", line)
            if m:
                row[f"control_{m[1]}"] = float(m[2])
        print(json.dumps(row), flush=True)
        del out
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
