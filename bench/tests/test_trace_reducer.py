"""The trace reduction (``benchlib.profile``) on hand-made intervals and on
a profiler trace recorded here on the CPU, whose XLA ops stand in for a
device plane's."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import pytest

from benchlib import profile
from benchlib.profile import OpEvent, Trace


def _trace(iv, plane="/device:TPU:0", name="fusion"):
    return Trace([OpEvent(name, a, b, plane) for a, b in iv])


def test_union_clip_gaps_by_hand():
    tr = _trace([(0, 10), (5, 20), (30, 40), (38, 45), (60, 70)])
    assert profile.union([(a.start_ns, a.end_ns) for a in tr.ops]) == [
        (0, 20), (30, 45), (60, 70)]
    # window [10, 65]: busy 10..20, 30..45, 60..65
    assert profile.busy_ns(tr, 10, 65) == 10 + 15 + 5
    assert profile.idle_gaps(tr, 10, 65) == [(45, 60), (20, 30)]
    assert profile.kernel_ns(tr, "fus", 0, 100) == 10 + 15 + 10 + 7 + 10


def test_busy_is_averaged_over_device_planes():
    a = _trace([(0, 50)], plane="/device:TPU:0").ops
    b = _trace([(0, 10)], plane="/device:TPU:1").ops
    tr = Trace(a + b)
    assert profile.busy_ns(tr, 0, 100) == (50 + 10) / 2
    assert profile.kernel_ns(tr, "fusion", 0, 100) == (50 + 10) / 2


def test_top_ops_orders_by_time():
    tr = Trace([OpEvent("a", 0, 5, "p"), OpEvent("b", 5, 25, "p"),
                OpEvent("a", 30, 40, "p")])
    assert profile.top_ops(tr, 0, 100) == [("b", 20), ("a", 15)]


@pytest.fixture(scope="module")
def cpu_trace(tmp_path_factory):
    d = tmp_path_factory.mktemp("trace")
    f = jax.jit(lambda x: jnp.tanh(x @ x) + 1.0)
    x = jnp.ones((384, 384), jnp.float32)
    f(x).block_until_ready()
    with jax.profiler.trace(str(d)):
        for _ in range(4):
            with jax.profiler.TraceAnnotation("bench.step.0"):
                f(x).block_until_ready()
            time.sleep(0.02)
    return profile.find_xplane(d)


def test_recorded_trace(cpu_trace):
    from jax.profiler import ProfileData
    tr = profile.load(cpu_trace, plane_prefix="/host:CPU")
    assert tr.ops, "no XLA op events read from the recorded trace"
    assert any(h.name == "bench.step.0" for h in tr.host)
    # independent sums straight from the file
    pd = ProfileData.from_file(str(cpu_trace))
    dots = [(e.start_ns, e.start_ns + e.duration_ns)
            for p in pd.planes if p.name.startswith("/host:CPU")
            for ln in p.lines for e in ln.events
            if e.name.startswith("dot") and any(k == "hlo_op"
                                                for k, _ in e.stats)]
    assert dots
    lo = min(o.start_ns for o in tr.ops)
    hi = max(o.end_ns for o in tr.ops)
    assert profile.kernel_ns(tr, r"^dot", lo, hi) == pytest.approx(
        sum(b - a for a, b in dots))
    busy = profile.busy_ns(tr, lo, hi)
    durs = [o.end_ns - o.start_ns for o in tr.ops]
    assert max(durs) <= busy <= min(sum(durs), hi - lo)
    # the idle gaps and the busy union tile the window exactly, and the
    # four sleeps between steps are among the idle gaps
    gaps = profile.idle_gaps(tr, lo, hi)
    assert busy + sum(b - a for a, b in gaps) == pytest.approx(hi - lo)
    assert len([g for g in gaps if g[1] - g[0] >= 15e6]) >= 3
    idle_share = 1 - busy / (hi - lo)
    assert 0 < idle_share < 1
