"""One run of one cell: weights from the seed, the paged engine built from
the cell's files, a warm prelude, an open-loop window, the readings, and
the comparison with the plain reference.

The window drives the program's own path: ``PagedServingEngine.submit``
and ``.step()`` with prefill on, the fused K-step decode, memos as the
cell's engine settings say, and the default two-tier hierarchy (an HBM
tier and a numpy host tier).
Before each ``step()`` every request whose due time has passed is
submitted; each request is timed from its due time.
"""
from __future__ import annotations

import gc
import math
import shutil
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from . import costs, profile, traffic, weights
from .meter import CompileMeter
from .peaks import peaks_for
from .spec import Cell, arch_config, load_module

# longest device trace taken in a traced run, in seconds of the window
PROFILE_S = 10.0


def engine_config(cell: Cell, hbm_pages: int | None = None,
                  host_pages: int | None = None):
    """The program's ``ServeConfig`` for a cell: the configuration's
    engine settings (which the cell file's ``engine`` may override), the
    mix's table width, the cell's tier sizes."""
    from repro.serving import ServeConfig
    e = {**cell.config["engine"], **cell.cell.get("engine", {})}
    return ServeConfig(
        page_size=e["page_size"], max_batch=e["max_batch"],
        fast_slots=hbm_pages or cell.cell["hbm_pages"],
        slow_slots=host_pages or cell.cell["host_pages"],
        memos_interval=e["memos_interval"],
        memos_enabled=e.get("memos_enabled", True),
        max_pages_per_seq=cell.traffic["max_pages_per_seq"],
        decode_block=e["decode_block"], prefill=True,
        prefill_min_bucket=e["prefill_bucket"],
        prefill_max_bucket=e["prefill_bucket"], prefill_pack=True,
        prefill_max_segments=e["prefill_max_segments"])


@dataclass
class Served:
    """What the harness keeps of one request."""
    rid: int
    due: float                    # monotonic due time
    prompt: list[int]
    req: object                   # the engine's Request
    moved: bool = False           # preempted, so its pages left HBM


@dataclass
class RunRecord:
    """Everything a per-layer metric reader may read (see bench/metrics)."""
    cfg: object
    model: object                       # the configuration's model module
    platform: str
    peaks: dict | None
    steps: list[costs.StepWork]         # engine steps in the window
    spans: list                         # repro.obs span events, window
    dispatches: int                     # decode dispatches in the window
    counters: dict = field(default_factory=dict)  # the window's counters
    trace: object = None                # profile.Trace, traced TPU runs
    trace_lo: float = 0.0               # traced window, trace clock (ns)
    trace_hi: float = 0.0
    traced_steps: list = field(default_factory=list)


def _warm(eng, cfg, cell, seed) -> None:
    """Compile every shape the window can use, by serving: the prefill
    bucket and each K of the fused decode (two requests whose budget runs
    down through K = 8, 4, 2, 1); with memos on, also the page
    gather/scatter of every power-of-two group size the migration engine
    stages, both ways."""
    import jax
    import jax.numpy as jnp
    e = cell.config["engine"]
    cap = eng.scfg.max_pages_per_seq * eng.scfg.page_size
    for a in traffic.warm_requests(seed, cfg.vocab,
                                   min(e["prefill_bucket"], cap) // 2,
                                   e["decode_block"]):
        eng.submit(a.prompt, max_new=a.max_new)
    eng.run(max_steps=10_000)
    if eng.scfg.memos_enabled:
        pool = eng.kv.store.pools[0]
        n = 1
        while n <= eng.memos.engine.chunk_pages:
            slots = np.arange(n) % pool.data.shape[0]
            staged = pool.gather(slots)
            pool.scatter(slots, staged)
            host = np.asarray(staged, np.float32)[:n]
            pool.scatter(slots, jax.device_put(host))
            n *= 2
        jax.block_until_ready(pool.data)
    # the decode dispatch hands back ``logits[:B]`` for the live batch B:
    # one slice program per B
    logits = jnp.zeros((eng.scfg.max_batch, cell.model.padded_vocab(cfg)),
                       jnp.float32)
    for b in range(1, eng.scfg.max_batch + 1):
        logits[:b].block_until_ready()


def _step_work(inflight: dict, pos0: dict) -> costs.StepWork:
    w = costs.StepWork()
    for rid, s in inflight.items():
        p0, p1 = pos0[rid], s.req.pos
        if p1 <= p0:
            continue
        lp = len(s.prompt)
        if p0 == 0:
            w.prefill.append(lp)
            p0 = lp
        if p1 > p0:
            w.decode.append((p0, p1 - p0))
    return w


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             rate: float | None = None, hbm_pages: int | None = None,
             host_pages: int | None = None, control: tuple = (),
             profile_dir: Path | None = None, breaker=None) -> dict:
    """One run.  Returns the result object the command prints last, plus
    ``lines``: what the run prints before it, on standard error.

    ``breaker`` (tests only) is called with the engine before the window,
    to break the timed path and see the comparison fail."""
    import jax
    from repro import obs
    from repro.serving import PagedServingEngine

    t_setup0 = time.perf_counter()
    obs.reset()
    obs.configure(trace=False)
    meter = CompileMeter()
    dev = jax.devices()[0]
    n_dev = len(jax.devices())
    cfg = arch_config(cell.config)
    scfg = engine_config(cell, hbm_pages, host_pages)
    rate = rate if rate is not None else cell.cell["rate_rps"]
    mix = cell.traffic
    lines: list[str] = []

    params = cell.model.make_params(cfg, seed)
    jax.block_until_ready(params)
    t_weights = time.perf_counter() - t_setup0
    eng = PagedServingEngine(cfg, params, scfg)
    _warm(eng, cfg, cell, seed)
    t_warm = time.perf_counter() - t_setup0 - t_weights
    mem = dev.memory_stats() or {}
    lines.append(f"memory after warm-up: bytes_in_use "
                 f"{mem.get('bytes_in_use')} of bytes_limit "
                 f"{mem.get('bytes_limit')}; weights "
                 f"{weights.param_bytes(params)}")

    n_backlog = int(round(mix.get("backlog_batches", 0) * scfg.max_batch))
    backlog, arrivals = traffic.schedule(mix, rate, seconds, seed, cfg.vocab,
                                         n_backlog)
    served: dict[int, Served] = {}
    inflight: dict[int, Served] = {}
    batcher = eng.batcher

    def submit(a, due):
        r = eng.submit(a.prompt, max_new=a.max_new)
        s = Served(r.rid, due, a.prompt, r)
        served[r.rid] = s
        inflight[r.rid] = s
        return s

    def step():
        pos0 = {rid: s.req.pos for rid, s in inflight.items()}
        eng.step()
        w = _step_work(inflight, pos0)
        for r in batcher.preempted:
            served[r.rid].moved = True
        for rid in [rid for rid, s in inflight.items() if s.req.done]:
            del inflight[rid]
        return w

    # prelude: the backlog, served until the cell's condition holds
    t_pre = time.monotonic()
    for a in backlog:
        submit(a, t_pre)
    until = mix.get("prelude_until", "none")
    reg = obs.get_registry()
    if until == "first_preemption":
        cap = float(mix.get("prelude_max_s", 90))
        while (reg.counter("serving.preemptions").value == 0
               and time.monotonic() - t_pre < cap
               and not batcher.all_done()):
            step()
    elif until != "none":
        raise ValueError(f"unknown prelude_until {until!r}")
    prelude_s = time.monotonic() - t_pre
    prelude_preempt = reg.counter("serving.preemptions").value

    if breaker is not None:
        breaker(eng)
    obs.reset()
    obs.configure(trace=trace)
    store = eng.kv.store
    traffic0 = dict(store.traffic)
    n_reports0 = len(eng.memos.reports)
    tok0 = eng.tokens_out
    c0 = meter.snapshot()
    setup_s = time.perf_counter() - t_setup0

    # -- the window ---------------------------------------------------------
    steps: list[costs.StepWork] = []
    step_t: list[tuple[float, float]] = []
    lags: list[float] = []
    nxt = 0
    prof_lo = prof_hi = None
    prof_steps: list[int] = []
    if trace:
        # the device trace covers the window's last PROFILE_S seconds and
        # is written out after the window has closed
        from jax.profiler import TraceAnnotation
        prof_at = seconds - min(PROFILE_S, seconds)
        profile_dir = Path(profile_dir)
    t0 = time.monotonic()
    t_end = t0 + seconds
    while True:
        now = time.monotonic()
        if now >= t_end:
            break
        while nxt < len(arrivals) and t0 + arrivals[nxt].t <= now:
            submit(arrivals[nxt], t0 + arrivals[nxt].t)
            lags.append(now - (t0 + arrivals[nxt].t))
            nxt += 1
        if trace and prof_lo is None and now - t0 >= prof_at:
            jax.profiler.start_trace(str(profile_dir))
            prof_lo = time.monotonic()
        if batcher.all_done():
            due = t0 + arrivals[nxt].t if nxt < len(arrivals) else t_end
            time.sleep(max(0.0, min(due, t_end) - time.monotonic()))
            continue
        ts = time.monotonic()
        if prof_lo is not None:
            prof_steps.append(len(steps))
            with TraceAnnotation(f"bench.step.{len(steps)}"):
                w = step()
        else:
            w = step()
        steps.append(w)
        step_t.append((ts, time.monotonic()))
    t_close = time.monotonic()
    if prof_lo is not None:
        prof_hi = t_close
        jax.profiler.stop_trace()
    window_s = t_close - t0
    backlog_close = (len(batcher.waiting), len(batcher.preempted),
                     len(batcher.running))
    c1 = meter.snapshot()
    compiles_in_window = c1[0] - c0[0]
    tokens = eng.tokens_out - tok0

    # -- readings -----------------------------------------------------------
    stats = dev.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    due_in = [s for s in served.values() if t0 <= s.due < t0 + seconds]
    ttft = [((s.req.first_token_ts if s.req.first_token_ts is not None
              else t_close) - s.due) for s in due_in]
    done_in = [s for s in served.values()
               if s.req.done and s.req.error is None
               and s.req.finish_ts is not None and s.req.finish_ts >= t0]
    tpot = [(s.req.finish_ts - s.req.first_token_ts)
            / (len(s.req.generated) - 1)
            for s in done_in if len(s.req.generated) > 1]
    failed = [s for s in served.values() if s.req.error is not None]
    hbm_bytes = sum(b - traffic0[k] for k, b in store.traffic.items()
                    if 0 in k)
    reports = eng.memos.reports[n_reports0:]
    migrated = sum(r.migrations.migrated for r in reports)
    counters = {n: m.value for n, m in obs.get_registry().collect().items()
                if isinstance(m, obs.Counter)}
    spans = obs.get_tracer().events() if trace else []
    preempted = int(counters.get("serving.preemptions", 0))
    admitted = int(counters.get("serving.admissions", 0))
    dispatches = int(counters.get("serving.dispatches", 0))

    lines.append(f"device: {dev.platform} {dev.device_kind} x{n_dev}; jax "
                 f"{jax.__version__}")
    lines.append(
        f"set-up: {setup_s:.3f} s (weights {t_weights:.3f} s, warm "
        f"{t_warm:.3f} s, prelude {prelude_s:.3f} s with "
        f"{prelude_preempt} preemptions); {c0[0]} XLA compiles "
        f"({c0[1]:.3f} s), {c0[2]} persistent-cache hits")
    lines.append(
        f"window: {window_s:.3f} s at {rate} req/s offered; due "
        f"{len(due_in)}, submitted {nxt}, admitted {admitted}, finished "
        f"{len(done_in)}, preempted {preempted}, failed {len(failed)}; "
        f"{tokens} output tokens; {len(steps)} steps, {dispatches} decode "
        f"dispatches; {compiles_in_window} compiles inside the window "
        f"({c1[1] - c0[1]:.3f} s); at close {backlog_close[0]} waiting, "
        f"{backlog_close[1]} preempted, {backlog_close[2]} running")
    lines.append(
        f"memos: {len(reports)} passes, {migrated} pages migrated; bytes "
        f"across the HBM boundary {hbm_bytes} (to host "
        f"{sum(b - traffic0[k] for k, b in store.traffic.items() if k[0] == 0)}"
        f", to HBM "
        f"{sum(b - traffic0[k] for k, b in store.traffic.items() if k[1] == 0)}"
        f"); peak bytes_in_use {peak}")
    lines.append(
        f"generator lag: mean "
        f"{(sum(lags) / len(lags) if lags else 0.0):.6f} s, max "
        f"{(max(lags) if lags else 0.0):.6f} s over {len(lags)} submits")
    if ttft:
        lines.append(
            f"tails (all cells; judged only where BENCHMARK.json lists them): "
            f"ttft p50 {np.percentile(ttft, 50):.4f} s p95 "
            f"{np.percentile(ttft, 95):.4f} s over {len(ttft)}; tpot p50 "
            f"{(np.percentile(tpot, 50) * 1e3 if tpot else float('nan')):.3f}"
            f" ms p95 "
            f"{(np.percentile(tpot, 95) * 1e3 if tpot else float('nan')):.3f}"
            f" ms over {len(tpot)}")

    e2e = {"tokens_per_s": tokens / window_s, "setup_s": setup_s}
    if ttft:
        e2e["ttft_p95_s"] = float(np.percentile(ttft, 95))
    if tpot:
        e2e["tpot_p95_ms"] = float(np.percentile(tpot, 95)) * 1e3
    rec = RunRecord(cfg=cfg, model=cell.model, platform=dev.platform,
                    peaks=(peaks_for(dev.device_kind)
                           if dev.platform == "tpu" else None),
                    steps=steps, spans=spans, dispatches=dispatches,
                    counters=counters)
    n_due, n_failed = len(due_in), len(failed)
    keep = [(s.rid, list(s.prompt), list(s.req.generated), s.moved)
            for s in done_in]

    # -- release the program's state, then compare with the reference -------
    eng.close()
    del eng, store, batcher, served, inflight, due_in, done_in, failed
    gc.collect()
    from .check import compare
    checks, info = compare(cell, params, keep, seed, control=control)
    lines.extend(info)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": n_dev, "memory_peak_bytes": peak}
    breakdown = None
    if trace and dev.platform == "tpu" and prof_lo is not None:
        breakdown = _reduce_trace(rec, profile_dir, step_t, prof_steps,
                                  prof_lo, prof_hi, device, lines)
    if prof_lo is not None:
        shutil.rmtree(profile_dir, ignore_errors=True)
    if trace:
        metrics = _per_layer(cell, rec)
    else:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end() if m["name"] in e2e}
    correct = (bool(checks) and n_failed == 0
               and all(c["value"] <= c["limit"] for c in checks.values()))
    result = {"correct": correct, "attempted": n_due, "failed": n_failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    for name, c in checks.items():
        lines.append(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    result["lines"] = lines
    return result


def _reduce_trace(rec: RunRecord, profile_dir: Path, step_t, prof_steps,
                  prof_lo, prof_hi, device: dict, lines: list) -> dict:
    """Device busy time, window and breakdown from the profiler trace;
    puts the trace on ``rec`` for the per-layer readers."""
    tr = profile.load(profile.find_xplane(profile_dir))
    marks = {int(h.name.rsplit(".", 1)[1]): h.start_ns for h in tr.host
             if h.name.startswith("bench.step.")}
    offs = [marks[i] - step_t[i][0] * 1e9 for i in prof_steps if i in marks]
    if not offs or not tr.ops:
        lines.append(f"trace: no device ops or step markers read "
                     f"(planes {tr.planes})")
        return None
    off = float(np.median(offs))
    lo, hi = prof_lo * 1e9 + off, prof_hi * 1e9 + off
    busy = profile.busy_ns(tr, lo, hi)
    rec.trace, rec.trace_lo, rec.trace_hi = tr, lo, hi
    rec.traced_steps = [rec.steps[i] for i in prof_steps]
    device["busy_s"] = busy / 1e9
    device["window_s"] = (hi - lo) / 1e9
    lines.append(f"trace: {len(tr.ops)} device ops, busy "
                 f"{busy / 1e9:.6f} s of {(hi - lo) / 1e9:.6f} s, "
                 f"{len(prof_steps)} steps traced")
    spans = [(e.ts_ns + off, e.ts_ns + e.dur_ns + off, e.name)
             for e in rec.spans if e.ph == "X"]
    gaps = []
    for a, b in profile.idle_gaps(tr, lo, hi)[:10]:
        best, label = 0.0, "host outside engine spans"
        for s0, s1, name in spans:
            ov = min(b, s1) - max(a, s0)
            if ov > best and name != "serve.dispatch":
                best, label = ov, name
        gaps.append([label, (b - a) / 1e9])
    return {"device_ops": [[n, t / 1e9] for n, t in
                           profile.top_ops(tr, lo, hi)],
            "idle_gaps": gaps}


def _per_layer(cell: Cell, rec: RunRecord) -> dict:
    """Each per-layer metric of the cell, read by its own file under
    ``metrics/``; a reader that finds nothing to read returns None and
    the metric is left out."""
    out = {}
    for m in cell.per_layer():
        mod = load_module(cell.bench_dir / "metrics" / f"{m['name']}.py")
        v = mod.read(rec)
        if v is not None and math.isfinite(v):
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out
