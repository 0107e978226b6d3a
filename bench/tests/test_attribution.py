"""The readers that split device time by program and idle time by wait
(``benchlib.attribution``): on a hand-built run, on a profiler trace
recorded here on the CPU (whose ops carry their program's module, the
independent answer the span attribution must give), and on a served
smoke engine, where the six readers the benchmark had before read the
same numbers with or without the spans the program added since."""
from __future__ import annotations

import time
import types

import jax
import jax.numpy as jnp
import pytest

from benchlib import attribution as A
from benchlib import costs, profile, readers
from benchlib.peaks import PEAKS
from benchlib.profile import OpEvent, Trace
from benchlib.serve import RunRecord, _step_work
from benchlib.spec import load_module
from conftest import BENCH

from repro import obs
from repro.obs.trace import SpanEvent

V5E = PEAKS["TPU v5 lite"]
OFF = 5_000_000_000          # trace clock minus span clock, hand-built run
CFG = types.SimpleNamespace(n_heads=32, n_kv_heads=8, head_dim=128,
                            n_layers=36, d_model=2560, d_ff=9728)
DENSE = load_module(BENCH / "models" / "dense.py")
# the padded-work counters of _hand_run's window
WINDOW = {"serving.dispatches": 2, "serving.tokens_sampled": 30,
          "serving.decode_row_slots": 48, "serving.prefill_tokens": 140,
          "serving.prefill_slots": 256}


@pytest.fixture(autouse=True)
def obs_isolation():
    obs.configure(trace=False)
    obs.reset()
    yield
    obs.configure(trace=False)
    obs.reset()


def _span(name, a, b, **attrs):
    return SpanEvent(name, "X", a, b - a, 1, attrs or None)


def _hand_run(spans=None, platform="tpu", counters=None):
    """Two engine steps on the span clock, their device ops on the trace
    clock.  Step 0 prefills 140 prompt tokens and decodes K=8; step 1
    decodes K=4."""
    if spans is None:
        spans = [
            _span("serve.admit", 0, 10),
            _span("serve.prefill", 100, 1100, tokens=140, bucket=256),
            _span("serve.prefill.wait", 300, 1100),
            _span("serve.dispatch", 1200, 3200, k=8, batch=3),
            _span("serve.dispatch.wait", 1300, 3200),
            _span("serve.account", 3200, 3300),
            _span("serve.admit", 4000, 4010),
            _span("serve.dispatch", 4100, 6100, k=4, batch=3),
            _span("serve.dispatch.wait", 4200, 6100),
        ]
    dev = "/device:TPU:0"
    ops = [OpEvent(n, OFF + a, OFF + b, dev) for n, a, b in [
        ("fusion", 150, 400), ("paged_attention", 400, 900),      # prefill
        ("copy", 1110, 1150),                                     # other
        ("paged_attention", 1250, 2250), ("fusion", 2250, 3000),  # decode
        ("paged_attention", 4150, 5150), ("fusion", 5150, 6000)]]
    marks = [OpEvent("bench.step.0", OFF, OFF + 3300, "/host:CPU"),
             OpEvent("bench.step.1", OFF + 4000, OFF + 6200, "/host:CPU")]
    steps = [costs.StepWork(prefill=[140], decode=[(140, 8)]),
             costs.StepWork(decode=[(148, 4)])]
    return RunRecord(cfg=CFG, model=DENSE, platform=platform, peaks=V5E,
                     steps=steps, spans=spans, dispatches=2,
                     counters=counters or {}, trace=Trace(ops, marks),
                     trace_lo=OFF - 100, trace_hi=OFF + 7000,
                     traced_steps=steps)


def test_hand_built_run_split_and_readers():
    rec = _hand_run()
    assert A.trace_offset(rec) == OFF
    assert A.split_ns(rec) == {"prefill": 750, "decode": 3600, "other": 40,
                               "busy": 4390}
    assert A.prefill_device_ms_per_ktok(rec) == pytest.approx(
        750 / 1e6 / 140 * 1e3)
    assert A.decode_device_ms_per_step(rec) == pytest.approx(3600 / 1e6 / 12)
    decode_only = [costs.StepWork(decode=[(140, 8)]),
                   costs.StepWork(decode=[(148, 4)])]
    assert A.paged_attention_decode_roofline(rec) == pytest.approx(
        100 * costs.min_seconds(DENSE.attention_work(rec, decode_only), V5E)
        / 2000e-9)
    # idle 2710 ns of the 7100 ns window; 500 ns of it inside the waits
    assert A.host_idle_share(rec) == pytest.approx(100 * 2210 / 7100)
    assert readers.device_idle_share(rec) == pytest.approx(100 * 2710 / 7100)


def test_spans_started_outside_the_window_are_not_counted():
    rec = _hand_run()
    rec.trace_lo = OFF + 1150        # the prefill started before the window
    assert A.prefill_device_ms_per_ktok(rec) is None
    assert A.decode_device_ms_per_step(rec) == pytest.approx(3600 / 1e6 / 12)


def test_readers_find_nothing_without_what_they_read():
    # a CPU run, and a program whose spans have no waits
    assert A.split_ns(_hand_run(platform="cpu")) is None
    assert A.decode_device_ms_per_step(_hand_run(platform="cpu")) is None
    no_waits = [e for e in _hand_run().spans if not e.name.endswith(".wait")]
    assert A.host_idle_share(_hand_run(spans=no_waits)) is None
    # a span ring that dropped a step's spans cannot be aligned
    dropped = [e for e in _hand_run().spans[1:]]
    assert A.trace_offset(_hand_run(spans=dropped)) is None
    assert A.decode_device_ms_per_step(_hand_run(spans=dropped)) is None
    # the window's dispatches, but no counters of padded work
    rec = _hand_run(counters={"serving.dispatches": 2})
    assert A.decode_live_row_share(rec) is None
    assert A.prefill_fill_share(rec) is None


def test_padded_work_shares_read_the_program_counters():
    rec = _hand_run(counters=WINDOW)
    assert A.decode_live_row_share(rec) == pytest.approx(62.5)
    assert A.prefill_fill_share(rec) == pytest.approx(100 * 140 / 256)


def test_padded_work_shares_refuse_a_registry_past_the_window():
    """The shares read the counters the record took as the window closed,
    not the program's live registry, which may have counted past it."""
    reg = obs.get_registry()
    for name, v in WINDOW.items():
        reg.counter(name).inc(v)
    # a dispatch published after the window closed
    reg.counter("serving.dispatches").inc()
    reg.counter("serving.tokens_sampled").inc(8)
    reg.counter("serving.decode_row_slots").inc(8)
    rec = _hand_run(counters=WINDOW)
    assert A.decode_live_row_share(rec) == pytest.approx(62.5)
    assert A.prefill_fill_share(rec) == pytest.approx(100 * 140 / 256)
    assert A.decode_live_row_share(_hand_run()) is None


def _modules(xplane) -> dict:
    """(op name, start) -> the op's ``hlo_module`` stat, read straight
    from the recorded file."""
    from jax.profiler import ProfileData
    out = {}
    for p in ProfileData.from_file(str(xplane)).planes:
        for ln in p.lines:
            for e in ln.events:
                st = dict(e.stats)
                if "hlo_module" in st:
                    out[(profile.op_name(e.name), e.start_ns)] = \
                        st["hlo_module"]
    return out


def test_span_attribution_matches_the_ops_modules(tmp_path):
    """Two programs run inside the program's two kinds of span; the ops
    the spans attribute to each are exactly the ops of its module."""
    def prefill_prog(x):
        return jnp.tanh(x @ x) + 1.0

    def decode_prog(x):
        return jnp.sin(x @ x.T) * 2.0

    pf, dc = jax.jit(prefill_prog), jax.jit(decode_prog)
    x = jnp.ones((256, 256), jnp.float32)
    pf(x).block_until_ready()
    dc(x).block_until_ready()
    obs.configure(trace=True)
    n_steps = 4
    with jax.profiler.trace(str(tmp_path)):
        t0 = time.monotonic_ns()
        for i in range(n_steps):
            with jax.profiler.TraceAnnotation(f"bench.step.{i}"):
                with obs.span("serve.admit"):
                    pass
                with obs.span("serve.prefill", tokens=100):
                    with obs.span("serve.prefill.wait"):
                        pf(x).block_until_ready()
                with obs.span("serve.dispatch", k=2):
                    with obs.span("serve.dispatch.wait"):
                        dc(x).block_until_ready()
            time.sleep(0.005)
        t1 = time.monotonic_ns()
    xplane = profile.find_xplane(tmp_path)
    tr = profile.load(xplane, plane_prefix="/host:CPU")
    spans = obs.get_tracer().events()
    rec = RunRecord(cfg=CFG, model=DENSE, platform="tpu", peaks=V5E,
                    steps=[costs.StepWork() for _ in range(n_steps)],
                    spans=spans, dispatches=n_steps, trace=tr)
    off = A.trace_offset(rec)
    rec.trace_lo, rec.trace_hi = t0 + off, t1 + off
    modules = _modules(xplane)
    for name, prog in ((A.PREFILL, "jit_prefill_prog"),
                       (A.DECODE, "jit_decode_prog")):
        got = {(o.name, o.start_ns) for o in
               A.ops_in(tr, A.executions(rec, name))}
        want = {k for k, m in modules.items() if m == prog}
        assert got and got == want, name
    split = A.split_ns(rec)
    assert split["prefill"] + split["decode"] + split["other"] \
        == pytest.approx(split["busy"], rel=0.02)
    assert A.decode_device_ms_per_step(rec) > 0
    assert 0 <= A.host_idle_share(rec) <= readers.device_idle_share(rec)


def test_offset_matches_the_spans_in_the_trace(tmp_path):
    """The program's spans, entered as profiler annotations, sit in the
    trace where the marker-based offset puts them."""
    obs.configure(trace=True)
    with jax.profiler.trace(str(tmp_path)):
        for i in range(3):
            with jax.profiler.TraceAnnotation(f"bench.step.{i}"):
                with obs.span("serve.admit"):
                    time.sleep(0.001)
    from jax.profiler import ProfileData
    admits = sorted(e.start_ns for p in ProfileData.from_file(
        str(profile.find_xplane(tmp_path))).planes for ln in p.lines
        for e in ln.events if e.name == "serve.admit")
    tr = profile.load(profile.find_xplane(tmp_path), plane_prefix="/host:CPU")
    spans = obs.get_tracer().events()
    rec = RunRecord(cfg=CFG, model=DENSE, platform="tpu", peaks=V5E,
                    steps=[costs.StepWork()] * 3, spans=spans, dispatches=0,
                    trace=tr)
    off = A.trace_offset(rec)
    mine = sorted(e.ts_ns for e in spans if e.name == "serve.admit")
    assert len(admits) == len(mine) == 3
    for t_trace, t_span in zip(admits, mine):
        assert abs(t_span + off - t_trace) < 1e6


EXISTING = ("decode_ms_per_step", "prefill_ms_per_ktok",
            "engine_host_ms_per_dispatch", "device_idle_share",
            "paged_attention_roofline", "step_mfu")
# spans the program added to the serving step; the parent had none
NEW_SPANS = ("serve.tables", "serve.account", "serve.dispatch.wait",
             "serve.prefill.wait")


def test_existing_readers_read_the_same_with_the_new_spans(tmp_path):
    """A smoke engine served under the profiler: the six readers the
    benchmark had give the same numbers from the spans as they are and
    from the spans the program had before (the new ones taken out)."""
    from repro.configs import registry, smoke
    from repro.models import transformer as T
    from repro.serving import PagedServingEngine, ServeConfig
    cfg = smoke(registry()["qwen3_4b"])
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    eng = PagedServingEngine(cfg, params, ServeConfig(
        page_size=8, max_batch=4, fast_slots=64, slow_slots=128,
        max_pages_per_seq=8, memos_enabled=False, decode_block=4,
        prefill=True, prefill_max_bucket=64))
    reqs = [eng.submit(list(range(1, n)), max_new=6) for n in (9, 21, 14)]
    eng.run(max_steps=100)                       # compiles every shape
    obs.configure(trace=True, capacity=65536)
    obs.reset()
    reqs = [eng.submit(list(range(2, n)), max_new=5 + n % 4)
            for n in (12, 30, 7, 25, 18, 40)]
    steps = []
    with jax.profiler.trace(str(tmp_path)):
        t0 = time.monotonic_ns()
        while not eng.batcher.all_done():
            pos0 = {r.rid: r.pos for r in reqs}
            with jax.profiler.TraceAnnotation(f"bench.step.{len(steps)}"):
                eng.step()
            inflight = {r.rid: types.SimpleNamespace(req=r, prompt=r.prompt)
                        for r in reqs}
            steps.append(_step_work(inflight, pos0))
        t1 = time.monotonic_ns()
    eng.close()
    spans = obs.get_tracer().events()
    assert {e.name for e in spans} >= set(NEW_SPANS)
    tr = profile.load(profile.find_xplane(tmp_path), plane_prefix="/host:CPU")

    def record(sp):
        return RunRecord(cfg=cfg, model=DENSE, platform="tpu", peaks=V5E,
                         steps=steps, spans=sp, dispatches=sum(
                             e.name == "serve.dispatch" for e in sp),
                         trace=tr, traced_steps=steps)

    now = record(spans)
    before = record([e for e in spans if e.name not in NEW_SPANS])
    off = A.trace_offset(now)
    for rec in (now, before):
        rec.trace_lo, rec.trace_hi = t0 + off, t1 + off
    got = {n: getattr(readers, n)(now) for n in EXISTING}
    assert got == {n: getattr(readers, n)(before) for n in EXISTING}
    # the span readers and the trace readers all read something here
    assert all(got[n] is not None for n in EXISTING[:4]), got
    assert got["step_mfu"] is not None
    # and the new numbers agree with the old where they overlap
    split = A.split_ns(now)
    assert split["prefill"] + split["decode"] + split["other"] \
        == pytest.approx(split["busy"], rel=0.02)
    assert A.decode_device_ms_per_step(now) <= got["decode_ms_per_step"]
    assert A.host_idle_share(now) <= got["device_idle_share"]
