"""Unified tracing + metrics subsystem (repro.obs).

Pins the observability contracts the serving/memos instrumentation
relies on:

  * span nesting + thread attribution — a forced async memos pass puts
    ``memos.plan`` on the worker thread, time-overlapping the main
    thread's dispatch span (the overlap the Chrome-trace export exists
    to make visible);
  * ring-buffer wraparound — a full ring drops oldest events, never
    stalls or grows;
  * disabled-mode zero cost — disabled tracing records zero events,
    retains zero attributes, enters no profiler annotation, and hands
    out one shared no-op span;
  * one clock — an enabled span is also a ``jax.profiler``
    annotation, so a recorded profiler trace holds it as a host event
    with its attributes as stats;
  * the serving step's spans (tables, dispatch and prefill waits,
    accounting, request ids) and its padded-work counters;
  * log-bucketed histogram quantiles, the exporters' formats, and the
    MemosReport to_dict/from_dict/flat_metrics serialization contract.
"""
import glob
import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import sysmon
from repro.core.memos import (MemosConfig, MemosManager, MemosReport,
                              aggregate_reports)
from repro.core.migration import MigrationStats
from repro.core.tiers import TierConfig, TierStore
from repro.obs import trace as obs_trace
from repro.obs.trace import NULL_SPAN, Tracer


@pytest.fixture(autouse=True)
def obs_isolation():
    """Every test starts and ends with tracing off and empty sinks."""
    obs.configure(trace=False)
    obs.reset()
    yield
    obs.configure(trace=False)
    obs.reset()


# =============================================================================
# tracer
# =============================================================================

def test_span_nesting_and_attrs():
    tr = Tracer(enabled=True)
    with tr.span("parent", step=3) as p:
        with tr.span("child"):
            pass
        p.set(k=16)
    ev = tr.events()
    # spans record at exit: child lands first, both on this thread
    assert [e.name for e in ev] == ["child", "parent"]
    child, parent = ev
    assert child.tid == parent.tid == threading.get_ident()
    assert parent.attrs == {"step": 3, "k": 16}
    # context-manager discipline: the child interval nests inside the
    # parent's [start, start + dur)
    assert parent.ts_ns <= child.ts_ns
    assert child.ts_ns + child.dur_ns <= parent.ts_ns + parent.dur_ns
    assert tr.thread_names[child.tid] == threading.current_thread().name


def test_ring_wraparound_keeps_newest():
    tr = Tracer(capacity=8, enabled=True)
    for i in range(20):
        with tr.span(f"e{i}"):
            pass
    ev = tr.events()
    assert [e.name for e in ev] == [f"e{i}" for i in range(12, 20)]
    assert tr.n_recorded == 20
    assert tr.n_dropped == 12
    tr.clear()
    assert tr.events() == [] and tr.n_recorded == 0


def test_disabled_mode_records_nothing():
    tr = Tracer(enabled=False)
    s = tr.span("x", big_attr=list(range(1000)))
    assert s is NULL_SPAN                       # one shared no-op object
    assert tr.span("y") is NULL_SPAN
    with s:
        s.set(more="attrs")
    assert tr.events() == [] and tr.n_recorded == 0
    # the module-level API takes the same fast path
    assert not obs.tracing_enabled()
    with obs.span("serve.dispatch", k=16) as sp:
        assert sp is NULL_SPAN
    assert obs.get_tracer().n_recorded == 0


def test_configure_flip_and_capacity():
    obs.configure(trace=True)
    with obs.span("a"):
        pass
    assert obs.get_tracer().n_recorded == 1
    obs.configure(capacity=16)                  # resize drops events
    assert obs.get_tracer().capacity == 16
    assert obs.get_tracer().n_recorded == 0
    assert obs.tracing_enabled()                # flag survives the resize


# =============================================================================
# one clock: enabled spans are profiler annotations
# =============================================================================

def _profiler_host_events(log_dir) -> dict:
    """Host events of a recorded profiler trace by name: (start, end,
    stats) of the last event of each name."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(f"{log_dir}/plugins/profile/*/*.xplane.pb"))[-1]
    return {e.name: (e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
            for p in ProfileData.from_file(path).planes
            if p.name.startswith("/host:")
            for ln in p.lines for e in ln.events}


def test_enabled_span_is_a_profiler_host_event(tmp_path):
    obs.configure(trace=True)
    with jax.profiler.trace(str(tmp_path)):
        with obs.span("serve.prefill", step=7, rids=[3, 4], tokens=5) as sp:
            sp.set(bucket=256, path="fused")
            with obs.span("serve.prefill.wait"):
                time.sleep(0.002)
    host = _profiler_host_events(tmp_path)
    a, b, stats = host["serve.prefill"]
    # a list travels joined by ";" (the profiler splits metadata at ",")
    assert stats == {"step": 7, "rids": "3;4", "tokens": 5, "bucket": 256,
                     "path": "fused"}
    wa, wb, _ = host["serve.prefill.wait"]
    assert a <= wa < wb <= b and wb - wa >= 2e6
    # the ring keeps the attributes as they were given
    (ev,) = [e for e in obs.get_tracer().events()
             if e.name == "serve.prefill"]
    assert ev.attrs["rids"] == [3, 4] and ev.attrs["bucket"] == 256


def test_disabled_span_enters_no_annotation(monkeypatch):
    entered = []

    class CountingAnnotation:
        def __init__(self, name, **meta):
            self.name = name

        @staticmethod
        def is_enabled():                   # a profiler trace is running
            return True

        def __enter__(self):
            entered.append(self.name)

        def __exit__(self, *exc):
            return False

        def set_metadata(self, **meta):
            pass

    # the class the tracer enters (it imports jax.profiler's once)
    monkeypatch.setattr(obs_trace, "_annotation_cls", CountingAnnotation)
    with obs.span("serve.dispatch", k=8) as sp:
        sp.set(batch=3)
        assert sp is NULL_SPAN
    assert entered == []
    obs.configure(trace=True)               # the patch is what spans enter
    with obs.span("serve.dispatch", k=8):
        with obs.span("serve.dispatch.wait"):
            pass
    assert entered == ["serve.dispatch", "serve.dispatch.wait"]


def test_span_outside_a_profiler_trace_enters_no_annotation(monkeypatch):
    """Enabled, but with no profiler trace running: the span is recorded
    and makes no annotation (the profiler would drop it anyway)."""
    made = []

    class NoProfile:
        def __init__(self, name, **meta):
            made.append(name)

        is_enabled = staticmethod(jax.profiler.TraceAnnotation.is_enabled)

    assert not NoProfile.is_enabled()
    obs.configure(trace=True)
    monkeypatch.setattr(obs_trace, "_annotation_cls", NoProfile)
    with obs.span("serve.dispatch", k=8) as sp:
        sp.set(batch=3)
    assert made == []
    (ev,) = obs.get_tracer().events()
    assert ev.name == "serve.dispatch" and ev.attrs == {"k": 8, "batch": 3}


# =============================================================================
# metrics
# =============================================================================

def test_histogram_quantiles_exact_for_equal_stream():
    h = obs.get_registry().histogram("lat_s")
    for _ in range(100):
        h.observe(0.25)
    assert h.quantile(0.5) == pytest.approx(0.25)
    assert h.quantile(0.99) == pytest.approx(0.25)
    assert h.count == 100 and h.mean == pytest.approx(0.25)


def test_histogram_weighted_and_ordered():
    h = obs.get_registry().histogram("tok_s")
    h.observe(0.001, n=90)                      # 90 fast tokens
    h.observe(0.1, n=10)                        # 10 slow tokens
    assert h.count == 100
    assert h.quantile(0.5) == pytest.approx(0.001, rel=0.25)
    assert h.quantile(0.99) == pytest.approx(0.1, rel=0.25)
    assert h.min == 0.001 and h.max == 0.1
    d = h.to_dict()
    assert d["p50"] <= d["p90"] <= d["p99"] <= d["max"]


def test_registry_kind_mismatch_and_flat():
    reg = obs.get_registry()
    reg.counter("a").inc(3)
    reg.gauge("b").set(1.5)
    reg.histogram("c").observe(2.0)
    with pytest.raises(TypeError):
        reg.gauge("a")
    flat = reg.flat()
    assert flat["a"] == 3 and flat["b"] == 1.5
    assert flat["c.count"] == 1 and flat["c.p50"] == pytest.approx(2.0)
    reg.reset()
    assert reg.flat() == {}


# =============================================================================
# exporters
# =============================================================================

def test_chrome_trace_export(tmp_path):
    obs.configure(trace=True)
    with obs.span("outer", step=1):
        with obs.span("inner", rids=[3, 4]):
            pass
    p = obs.export.write_chrome_trace(tmp_path / "t.json", obs.get_tracer())
    doc = json.loads(p.read_text())
    evs = doc["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    spans = {e["name"]: e for e in evs if e["ph"] == "X"}
    assert meta and meta[0]["name"] == "thread_name"
    assert len(spans) == 2 == len(evs) - len(meta)
    outer, inner = spans["outer"], spans["inner"]
    assert outer["args"] == {"step": 1} and outer["dur"] >= inner["dur"]
    assert inner["args"] == {"rids": "[3, 4]"}
    # timestamps rebase to the earliest event
    assert outer["ts"] == 0 and inner["ts"] >= 0
    assert doc["otherData"]["dropped_events"] == 0


def test_prometheus_text():
    reg = obs.get_registry()
    reg.counter("memos.passes", "passes").inc(2)
    reg.gauge("store.t0_used").set(7)
    reg.histogram("serving.dispatch_latency_s").observe(0.01, n=4)
    text = obs.export.prometheus_text(reg)
    assert "# TYPE repro_memos_passes counter" in text
    assert "repro_memos_passes 2" in text
    assert "repro_store_t0_used 7" in text
    assert 'repro_serving_dispatch_latency_s_bucket{le="+Inf"} 4' in text
    assert "repro_serving_dispatch_latency_s_count 4" in text


# =============================================================================
# MemosReport serialization
# =============================================================================

def make_store(seed=0):
    store = TierStore(TierConfig(
        n_pages=32, fast_slots=8, slow_slots=32, page_shape=(4,),
        dtype=jnp.float32, n_banks=2, n_slabs=4, gap_write_interval=5))
    rng = np.random.RandomState(seed)
    for p in range(32):
        assert store.allocate(p, int(store.tier[p]))
        store.write_page(p, rng.standard_normal(4).astype(np.float32))
    return store


def drive(mgr, n_steps=24):
    sm = sysmon.init(32, mgr.store.cfg.n_banks, mgr.store.cfg.n_slabs)
    rng = np.random.RandomState(7)
    for step in range(n_steps):
        phase = step // 8
        hot = np.arange(phase * 6, phase * 6 + 6)
        warm = rng.randint(20, 32, size=3)
        sm = sysmon.record(sm, jnp.asarray(hot, jnp.int32), is_write=True)
        sm = sysmon.record(sm, jnp.asarray(warm, jnp.int32), is_write=False)
        sm, _ = mgr.maybe_step(sm)
    mgr.flush()
    return sm


def test_memos_report_roundtrip():
    store = make_store()
    mgr = MemosManager(store, MemosConfig(interval=4,
                                          adaptive_interval=False))
    drive(mgr)
    assert mgr.reports and any(r.migrations.migrated for r in mgr.reports)
    for rep in mgr.reports:
        d = rep.to_dict()
        blob = json.dumps(d)                    # must be JSON-safe
        back = MemosReport.from_dict(json.loads(blob))
        assert back == rep
        assert back.to_dict() == d
        flat = rep.flat_metrics()
        assert flat["migrated"] == rep.migrations.migrated
        assert flat["tier0_pages"] == rep.tier_pages[0]
        for t in rep.nvm_by_tier:
            assert f"nvm.t{t}.wear_max" in flat


def test_migration_stats_roundtrip():
    st = MigrationStats(migrated=5, bytes_moved=1280, to_fast=2, to_slow=3)
    st.note_move(0, 1, 3)
    st.note_move(1, 0, 2)
    back = MigrationStats.from_dict(json.loads(json.dumps(st.to_dict())))
    assert back == st


def test_aggregate_reports():
    store = make_store()
    mgr = MemosManager(store, MemosConfig(interval=4,
                                          adaptive_interval=False))
    drive(mgr)
    agg = aggregate_reports(mgr.reports)
    assert agg["passes"] == len(mgr.reports)
    assert agg["migrated"] == sum(r.migrations.migrated
                                  for r in mgr.reports)
    assert agg["tier_pages"] == list(mgr.reports[-1].tier_pages)
    assert aggregate_reports([])["passes"] == 0


# =============================================================================
# instrumentation: spans + metrics out of a real memos pass
# =============================================================================

def test_sync_pass_spans_and_metrics():
    obs.configure(trace=True)
    store = make_store()
    mgr = MemosManager(store, MemosConfig(interval=4,
                                          adaptive_interval=False))
    drive(mgr)
    names = {e.name for e in obs.get_tracer().events()}
    assert "memos.pass_sync" in names
    assert "migrate.move_group" in names        # batched per-(src,dst) moves
    flat = obs.get_registry().flat()
    assert flat["memos.passes"] == len(mgr.reports)
    assert flat["memos.pages_migrated"] == sum(
        r.migrations.migrated for r in mgr.reports)
    assert "store.t0_used" in flat and "store.t0_slots" in flat
    assert "sysmon.hot_pages" in flat


def test_forced_async_pass_thread_attribution(monkeypatch):
    """Force a real plan/dispatch overlap: the worker's ``memos.plan``
    span must carry the worker tid and time-overlap the main thread's
    dispatch span recorded while the plan slept."""
    import repro.core.memos as memos_mod
    obs.configure(trace=True)
    store = make_store()
    mgr = MemosManager(store, MemosConfig(interval=4,
                                          adaptive_interval=False,
                                          async_plan=True))
    # slow the placement step itself so the sleep lands inside the
    # worker's timed plan window (plan_t0 .. plan_t1)
    orig_plan = memos_mod.plan
    monkeypatch.setattr(
        memos_mod, "plan",
        lambda *a, **k: (time.sleep(0.05), orig_plan(*a, **k))[1])

    sm = sysmon.init(32, store.cfg.n_banks, store.cfg.n_slabs)
    sm = sysmon.record(sm, jnp.asarray(np.arange(6), jnp.int32),
                       is_write=True)
    sm = mgr.begin_pass(sm)
    with obs.span("serve.dispatch", k=16):      # the overlapped dispatch
        time.sleep(0.08)
    rep = mgr.commit_pending()
    mgr.close()

    by_name = {e.name: e for e in obs.get_tracer().events()}
    plan, disp = by_name["memos.plan"], by_name["serve.dispatch"]
    commit = by_name["memos.commit"]
    main_tid = threading.get_ident()
    assert disp.tid == commit.tid == main_tid
    assert plan.tid != main_tid                 # worker thread
    assert obs.get_tracer().thread_names[plan.tid].startswith("memos-plan")
    # the plan interval overlaps the dispatch interval in time
    assert plan.ts_ns < disp.ts_ns + disp.dur_ns
    assert plan.ts_ns + plan.dur_ns > disp.ts_ns
    # and the slept plan was (mostly) hidden under the longer dispatch
    assert rep.committed_async
    assert rep.overlap_efficiency is not None
    assert rep.overlap_efficiency > 0.5
    assert rep.plan_ms >= 50.0
    assert mgr.overlap_efficiency == pytest.approx(rep.overlap_efficiency)


def test_disabled_tracing_still_publishes_metrics():
    """Metrics are always-on; tracing off must not suppress them (the
    overhead gate compares tracing on/off at identical metric output)."""
    store = make_store()
    mgr = MemosManager(store, MemosConfig(interval=4,
                                          adaptive_interval=False))
    drive(mgr)
    assert obs.get_tracer().n_recorded == 0
    flat = obs.get_registry().flat()
    assert flat["memos.passes"] == len(mgr.reports) > 0


# =============================================================================
# the serving step: spans where the host works, request ids, padded work
# =============================================================================

MAX_BATCH = 4
PROMPTS = [list(range(1, 1 + n)) for n in (9, 20, 5, 30, 12, 17)]


@pytest.fixture(scope="module")
def served():
    """Six prompts through a smoke-size engine with prefill on and
    tracing enabled: more requests than decode rows, so admission and
    prefill happen at several steps and decode runs padding rows."""
    from repro.configs import registry, smoke
    from repro.models import transformer as T
    from repro.serving import PagedServingEngine, ServeConfig
    cfg = smoke(registry()["qwen3_4b"])
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    eng = PagedServingEngine(cfg, params, ServeConfig(
        page_size=8, max_batch=MAX_BATCH, fast_slots=64, slow_slots=128,
        max_pages_per_seq=8, memos_enabled=False, decode_block=4,
        prefill=True, prefill_max_bucket=64))
    obs.configure(trace=True, capacity=65536)   # room for every span
    obs.reset()
    try:
        reqs = [eng.submit(p, max_new=5 + i) for i, p in enumerate(PROMPTS)]
        eng.run(max_steps=500)
        events = obs.get_tracer().events()
        counters = {n: m.value for n, m in obs.get_registry().collect().items()
                    if isinstance(m, obs.Counter)}
    finally:
        obs.configure(trace=False)
        obs.reset()
        eng.close()
    assert all(r.done and r.error is None for r in reqs)
    return reqs, events, counters


def _spans(events, name):
    return [e for e in events if e.name == name]


def _inside(child, parent) -> bool:
    return (parent.ts_ns <= child.ts_ns and child.ts_ns + child.dur_ns
            <= parent.ts_ns + parent.dur_ns)


@pytest.mark.parametrize("outer,inner", [
    ("serve.dispatch", "serve.dispatch.wait"),
    ("serve.prefill", "serve.prefill.wait"),
])
def test_wait_spans_nest_in_their_dispatch(served, outer, inner):
    _, events, _ = served
    outers, inners = _spans(events, outer), _spans(events, inner)
    assert outers and len(inners) == len(outers)
    for w in inners:
        assert sum(_inside(w, o) for o in outers) == 1


def test_host_work_between_dispatches_is_spanned(served):
    _, events, _ = served
    tables, account = (_spans(events, "serve.tables"),
                       _spans(events, "serve.account"))
    dispatches = _spans(events, "serve.dispatch")
    prefills = _spans(events, "serve.prefill")
    # one table build before every dispatch of either kind; accounting
    # after every prefill, and twice per decode step
    assert len(tables) == len(dispatches) + len(prefills)
    assert len(account) == 2 * len(dispatches) + len(prefills)
    # the new spans never overlap the spans the benchmark already reads
    old = [e for e in events if e.name in (
        "serve.admit", "serve.provision", "serve.retire", "serve.dispatch",
        "serve.prefill")]
    for e in tables + account:
        for o in old:
            assert (e.ts_ns >= o.ts_ns + o.dur_ns
                    or e.ts_ns + e.dur_ns <= o.ts_ns), (e.name, o.name)


def test_request_ids_on_prefill_and_admit(served):
    reqs, events, _ = served
    prefilled = [rid for e in _spans(events, "serve.prefill")
                 for rid in e.attrs["rids"]]
    admitted = [rid for e in _spans(events, "serve.admit")
                if e.attrs and "rids" in e.attrs for rid in e.attrs["rids"]]
    ids = [r.rid for r in reqs]
    assert sorted(prefilled) == ids == sorted(admitted)
    for e in _spans(events, "serve.prefill"):
        assert len(e.attrs["rids"]) == e.attrs["segments"]
        assert e.attrs["tokens"] == sum(len(PROMPTS[ids.index(rid)])
                                        for rid in e.attrs["rids"])


def test_padded_work_counters(served):
    _, events, counters = served
    ks = [e.attrs["k"] for e in _spans(events, "serve.dispatch")]
    buckets = [e.attrs["bucket"] for e in _spans(events, "serve.prefill")]
    assert counters["serving.decode_row_slots"] == sum(ks) * MAX_BATCH
    assert counters["serving.prefill_slots"] == sum(buckets)
    # live work is a share of the padded work, and the prompts are
    # what prefill ingested
    assert 0 < counters["serving.tokens_sampled"] \
        < counters["serving.decode_row_slots"]
    assert counters["serving.prefill_tokens"] == sum(map(len, PROMPTS)) \
        <= counters["serving.prefill_slots"]


def test_page_fetch_counters(served):
    """The paged-attention kernel fetches, per layer, each row's live
    pages: ceil(length / page) for every prefilled position and every
    decode step; the slots are every padded row's whole table."""
    from repro.configs import registry, smoke
    reqs, events, counters = served
    page, n_pages = 8, 8                       # the served engine's
    n_layers = smoke(registry()["qwen3_4b"]).n_layers
    ks = [e.attrs["k"] for e in _spans(events, "serve.dispatch")]
    buckets = [e.attrs["bucket"] for e in _spans(events, "serve.prefill")]
    live = lambda n: -(-n // page)             # noqa: E731
    prefill = sum(live(n) for p in PROMPTS for n in range(1, len(p) + 1))
    # decode step at position pos attends to pos + 1 tokens; a request's
    # first token comes from prefill, the other max_new - 1 from decode
    decode = sum(live(pos + 1) for r in reqs for pos in
                 range(len(r.prompt), len(r.prompt) + r.max_new - 1))
    assert counters["serving.attn_pages_fetched"] == \
        n_layers * (prefill + decode)
    assert counters["serving.attn_page_slots"] == \
        n_layers * n_pages * (sum(ks) * MAX_BATCH + sum(buckets))
