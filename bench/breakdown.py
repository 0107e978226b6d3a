#!/usr/bin/env python3
"""One traced run of a cell, broken down below what its metrics read:
device time by program and by ``jax.named_scope``, and TTFT by part.

    python3 bench/breakdown.py --workload <cell> --seed <n> \
        [--seconds 51] [--out bench_out/breakdown.json]

It serves the cell through ``benchlib.serve.run_cell`` with tracing on,
as ``bench/run.py --trace 1`` does, and keeps what the harness leaves
out: each request's due time, the compiled HLO of the serving programs
(read from the warm engine before the window) and the profiler trace of
the window's last 10 s.  Then it prints, besides the run's own result:

* for each program (``jit__fused_decode``, ``jit__core_plain``, ...)
  its executions and device time, by the named scope its ops were traced
  under (``attention``, ``kv_append``, ``mlp``, ``sysmon``, ``head``;
  ``unscoped`` outside them) and by top op.  The ops of a TPU trace carry
  no metadata: an op's scope is the ``op_name`` metadata of the
  instruction of the same name in its program's compiled HLO, its
  program the enclosing event of the device's ``XLA Modules`` line (on
  the CPU, the op's ``hlo_module`` stat).  Where a program has several
  compiled variants (one per decode K), an execution takes the variant
  that holds the most of its ops' names; ``unmapped`` counts the device
  time whose instruction no variant has.
* TTFT of the requests prefilled in the window, by part: due -> submit
  (the harness submits between steps, so a request waits for the step in
  flight), submit -> start of the ``serve.prefill`` span that carries
  its id (``rids``), that span, and due -> first token; p50 and p95.

Neither is a benchmark metric.  It exits 3 where JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import json
import re
import shutil
import sys
import types
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

from benchlib.profile import op_name  # noqa: E402

SCOPES = ("attention", "kv_append", "mlp", "sysmon", "head")
# control-flow ops whose events enclose the ops they run (profile.py's)
CONTAINERS = re.compile(r"^(while|conditional|call)$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*)$", re.M)
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')


def scope_of(path: str) -> str:
    """The innermost of ``SCOPES`` in an ``op_name`` path (its first
    part, where fusion joined several with ``;``), else ``unscoped``."""
    parts = path.split(";", 1)[0].split("/")
    for p in reversed(parts):
        if p in SCOPES:
            return p
    return "unscoped"


def hlo_scopes(text: str) -> dict[str, str]:
    """Instruction name -> scope, for every instruction of a compiled
    module's HLO text."""
    out = {}
    for m in _INSTR.finditer(text):
        on = _OP_NAME.search(m.group(2))
        out[m.group(1)] = scope_of(on.group(1)) if on else "unscoped"
    return out


def instr_name(event_name: str) -> str:
    """``%fusion.115 = bf16[...] fusion(...)`` -> ``fusion.115``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def device_ops(xplane) -> list[tuple[str, int, str, float]]:
    """(program, execution, instruction, ns) of every device op of a
    profiler trace, control-flow containers left out.  A TPU plane's
    ops are its ``XLA Ops`` line, each in the ``XLA Modules`` event that
    encloses its start; elsewhere an op is an event with ``hlo_op`` and
    ``hlo_module`` stats (one execution per program)."""
    from jax.profiler import ProfileData
    out = []
    runs: dict[str, int] = {}
    for plane in ProfileData.from_file(str(xplane)).planes:
        lines = {ln.name: ln for ln in plane.lines}
        if "XLA Modules" in lines and "XLA Ops" in lines:
            mods = sorted((e.start_ns, e.start_ns + e.duration_ns,
                           re.sub(r"\(\d+\)$", "", e.name))
                          for e in lines["XLA Modules"].events)
            j = 0
            for e in sorted(lines["XLA Ops"].events,
                            key=lambda e: e.start_ns):
                while j < len(mods) and mods[j][1] <= e.start_ns:
                    j += 1
                if j == len(mods) or mods[j][0] > e.start_ns:
                    continue
                n = instr_name(e.name)
                if not CONTAINERS.match(re.sub(r"\.\d+$", "", n)):
                    out.append((mods[j][2], runs.setdefault(
                        f"{plane.name}/{j}", len(runs)), n, e.duration_ns))
            continue
        for ln in plane.lines:
            for e in ln.events:
                st = dict(e.stats)
                if "hlo_op" in st and "hlo_module" in st:
                    n = instr_name(e.name)
                    if not CONTAINERS.match(re.sub(r"\.\d+$", "", n)):
                        mod = str(st["hlo_module"])
                        out.append((mod, runs.setdefault(mod, len(runs)),
                                    n, e.duration_ns))
    return out


def by_scope(ops, hlo: dict[str, list[dict[str, str]]], top: int = 12
             ) -> dict:
    """Per program: executions, device seconds, seconds by scope and the
    ``top`` op names (instance numbers dropped, as profile.op_name does)
    by seconds, each with its scope.  ``hlo``: program -> the scope maps
    of its compiled variants."""
    execs: dict[tuple, list] = {}
    for prog, run, instr, ns in ops:
        execs.setdefault((prog, run), []).append((instr, ns))
    res: dict[str, dict] = {}
    for (prog, _), items in execs.items():
        cands = hlo.get(prog) or [{}]
        best = max(cands, key=lambda m: sum(i in m for i, _ in items))
        r = res.setdefault(prog, {"executions": 0, "device_s": 0.0,
                                  "scopes": {}, "ops": {}})
        r["executions"] += 1
        for instr, ns in items:
            sc = best.get(instr, "unmapped")
            key = f"{op_name(instr)} [{sc}]"
            r["device_s"] += ns / 1e9
            r["scopes"][sc] = r["scopes"].get(sc, 0.0) + ns / 1e9
            r["ops"][key] = r["ops"].get(key, 0.0) + ns / 1e9
    for r in res.values():
        r["scopes"] = dict(sorted(r["scopes"].items(), key=lambda kv: -kv[1]))
        r["ops"] = dict(sorted(r["ops"].items(),
                               key=lambda kv: -kv[1])[:top])
    return dict(sorted(res.items(), key=lambda kv: -kv[1]["device_s"]))


def ttft_parts(served, spans) -> dict | None:
    """Seconds of each part of TTFT, p50 and p95 over the requests whose
    ``serve.prefill`` span (matched by ``rids``) is in ``spans`` and that
    have a first token: due -> submit, submit -> prefill start, the
    prefill span, due -> first token."""
    pre = {}
    for e in spans:
        if e.name == "serve.prefill" and e.attrs and "rids" in e.attrs:
            for rid in e.attrs["rids"]:
                pre[rid] = (e.ts_ns / 1e9, (e.ts_ns + e.dur_ns) / 1e9)
    rows = []
    for s in served:
        if s.rid in pre and s.req.first_token_ts is not None:
            a, b = pre[s.rid]
            rows.append((s.req.submit_ts - s.due, a - s.req.submit_ts, b - a,
                         s.req.first_token_ts - s.due))
    if not rows:
        return None
    cols = np.array(rows)
    names = ("due_to_submit", "submit_to_prefill", "prefill", "ttft")
    out = {"requests": len(rows)}
    for i, n in enumerate(names):
        out[n] = {"p50": float(np.percentile(cols[:, i], 50)),
                  "p95": float(np.percentile(cols[:, i], 95))}
    # the scheduler's share: due -> prefill start
    wait = cols[:, 0] + cols[:, 1]
    out["due_to_prefill"] = {"p50": float(np.percentile(wait, 50)),
                             "p95": float(np.percentile(wait, 95))}
    return out


def compiled_hlo(eng) -> dict[str, list[dict[str, str]]]:
    """Program name -> scope maps of the engine's compiled serving
    programs: each fused decode K and each prefill bucket (plain and
    pinned), as the warm engine holds them."""
    import jax
    import jax.numpy as jnp
    sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
    B, P = eng.scfg.max_batch, eng.scfg.max_pages_per_seq
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    params = jax.tree.map(sds, eng.params)
    sm = jax.tree.map(sds, eng.sysmon)
    pool = sds(eng.kv.store.fast_pool)
    out: dict[str, list] = {}
    for k, fn in eng._fused_fns.items():
        # the warm-up compiled these shapes: lowering again hits the cache
        c = fn.lower(params, i32(B), i32(B), i32(B, P * eng.scfg.page_size),
                     i32(B), i32(B, P), i32(B, P), sm, pool,
                     jax.ShapeDtypeStruct((B,), jnp.bool_)).compile()
        out.setdefault("jit__fused_decode", []).append(hlo_scopes(
            c.as_text()))
    runner = eng.prefill_runner
    if runner is not None:
        for name, table in (("jit__core_plain", runner._plain),
                            ("jit__core_pinned", runner._pinned)):
            for c in table.values():
                out.setdefault(name, []).append(hlo_scopes(c.as_text()))
    return out


def _lines(split: dict, parts: dict | None, known) -> list[str]:
    """One line of scopes and one of top ops per program in ``known``
    (those with compiled HLO), one line for all the other programs."""
    out = []
    rest = [r for p, r in split.items() if p not in known]
    for prog, r in split.items():
        if prog not in known:
            continue
        tot = r["device_s"] or 1.0
        sc = "; ".join(f"{k} {v:.6f} s ({100 * v / tot:.1f}%)"
                       for k, v in r["scopes"].items())
        ops = "; ".join(f"{k} {v:.6f} s" for k, v in r["ops"].items())
        out.append(f"program {prog}: {r['executions']} executions, "
                   f"{r['device_s']:.6f} s on the device; by scope: {sc}")
        out.append(f"program {prog}: top ops: {ops}")
    if rest:
        out.append(f"other programs: {len(rest)}, "
                   f"{sum(r['executions'] for r in rest)} executions, "
                   f"{sum(r['device_s'] for r in rest):.6f} s on the device")
    if parts:
        out.append(f"ttft over {parts['requests']} requests prefilled in "
                   "the window: " + "; ".join(
                       f"{n} p50 {v['p50']:.6f} s p95 {v['p95']:.6f} s"
                       for n, v in parts.items() if n != "requests"))
    return out


def main(argv=None, *, allow_cpu: bool = False) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--out", default=None,
                    help="also write the breakdown here as JSON")
    args = ap.parse_args(argv)
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    from benchlib import serve as S
    from benchlib import spec
    cell = spec.load_cell(args.workload, ROOT)

    import jax
    devs = jax.devices()
    if not allow_cpu and (devs[0].platform != "tpu"
                          or len(devs) < int(cell.workload["chips"])):
        print(f"breakdown: needs a TPU; JAX sees {len(devs)} "
              f"{devs[0].platform} device(s)", file=sys.stderr)
        return 3
    import run as bench_run
    bench_run._compile_cache()

    # what run_cell builds and throws away, kept through its own names
    served, kept = [], {}
    served_cls, per_layer = S.Served, S._per_layer

    def keep_served(*a, **k):
        s = served_cls(*a, **k)
        served.append(s)
        return s

    def keep_rec(cell_, rec):
        kept["spans"] = rec.spans
        return per_layer(cell_, rec)

    def keep_trace(path, **kw):
        try:
            kept["ops"] = device_ops(S.profile.find_xplane(path))
        except FileNotFoundError:
            kept["ops"] = []
        shutil.rmtree(path, **kw)

    S.Served, S._per_layer = keep_served, keep_rec
    S.shutil = types.SimpleNamespace(rmtree=keep_trace)
    try:
        res = S.run_cell(
            cell, args.seed, args.seconds, True,
            profile_dir=ROOT / "bench_out" / "profile"
            / f"{cell.name}.{args.seed}.breakdown",
            breaker=lambda eng: kept.update(hlo=compiled_hlo(eng)))
    finally:
        S.Served, S._per_layer, S.shutil = served_cls, per_layer, shutil
    for line in res.pop("lines"):
        print(line, file=sys.stderr)
    split = by_scope(kept.get("ops", []), kept.get("hlo", {}))
    parts = ttft_parts(served, kept.get("spans", []))
    for line in _lines(split, parts, set(kept.get("hlo", {}))):
        print(line)
    print(json.dumps(res))
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(
            {"workload": cell.name, "seed": args.seed, "programs": split,
             "ttft": parts, "result": res}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
