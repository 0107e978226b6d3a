"""Reduction of a JAX profiler trace (``.xplane.pb``) to device metrics.

Device operations are the events of the device planes (``/device:TPU:N``)
on their ``XLA Ops`` line, or, on a plane without one, any event that
carries an ``hlo_op`` stat (the CPU backend puts its ops on host threads;
the tests record one).  Op names are kept without their HLO text and
instance number, so the 36 per-layer instances of a kernel share one.
Busy time is the union of their intervals inside the traced window; the
idle share is 1 minus busy over the window; a kernel's time is the sum of
the durations of the events whose name contains the kernel's name.
"""
from __future__ import annotations

import glob
import re
from dataclasses import dataclass, field
from pathlib import Path


# control-flow ops whose events enclose the ops they run
_CONTAINERS = re.compile(r"^(while|conditional|call)$")


def op_name(event_name: str) -> str:
    """An op's name without its HLO text or instance numbers:
    ``%paged_attention.68 = bf16[...] custom-call(...)`` becomes
    ``paged_attention``, ``%fusion.115.remat`` becomes ``fusion.remat``."""
    n = event_name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+(?=\.|$)", "", n)


@dataclass
class OpEvent:
    name: str
    start_ns: float
    end_ns: float
    plane: str


@dataclass
class Trace:
    ops: list[OpEvent]                 # device operations, by start
    host: list[OpEvent] = field(default_factory=list)  # bench.* markers
    planes: list[str] = field(default_factory=list)


def find_xplane(log_dir: str | Path) -> Path:
    found = sorted(glob.glob(str(Path(log_dir) / "plugins" / "profile" / "*"
                                / "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return Path(found[-1])


def load(path: str | Path, plane_prefix: str = "/device:TPU:",
         marker_prefix: str = "bench.") -> Trace:
    """Read the device operations of every plane whose name starts with
    ``plane_prefix``, and the host events whose name starts with
    ``marker_prefix`` (the harness's ``TraceAnnotation`` markers)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    ops: list[OpEvent] = []
    host: list[OpEvent] = []
    planes = []
    for plane in pd.planes:
        planes.append(plane.name)
        dev = plane.name.startswith(plane_prefix)
        # a TPU plane's compute is its "XLA Ops" line (its async copies
        # are not compute); a plane without one marks ops by their stat
        op_line = any(ln.name == "XLA Ops" for ln in plane.lines)
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(marker_prefix):
                    host.append(OpEvent(e.name, e.start_ns,
                                        e.start_ns + e.duration_ns,
                                        plane.name))
                    continue
                if not dev or (op_line and line.name != "XLA Ops"):
                    continue
                if op_line or any(k == "hlo_op" for k, _ in e.stats):
                    ops.append(OpEvent(op_name(e.name), e.start_ns,
                                       e.start_ns + e.duration_ns,
                                       plane.name))
    ops.sort(key=lambda o: o.start_ns)
    host.sort(key=lambda o: o.start_ns)
    return Trace(ops, host, planes)


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    out = []
    for a, b in intervals:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            out.append((a, b))
    return out


def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted, disjoint cover of ``intervals``."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_ns(trace: Trace, lo: float, hi: float) -> float:
    """Nanoseconds of [lo, hi] in which some device op ran, averaged over
    the device planes that ran any."""
    by_plane: dict[str, list] = {}
    for o in trace.ops:
        by_plane.setdefault(o.plane, []).append((o.start_ns, o.end_ns))
    if not by_plane:
        return 0.0
    tot = sum(sum(b - a for a, b in union(clip(iv, lo, hi)))
              for iv in by_plane.values())
    return tot / len(by_plane)


def idle_gaps(trace: Trace, lo: float, hi: float) -> list[tuple[float, float]]:
    """Intervals of [lo, hi] with no device op, longest first."""
    busy = union(clip([(o.start_ns, o.end_ns) for o in trace.ops], lo, hi))
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return sorted(gaps, key=lambda g: g[0] - g[1])


def kernel_ns(trace: Trace, pattern: str, lo: float, hi: float) -> float:
    """Summed device time of the ops whose name matches ``pattern`` (a
    regular expression), clipped to [lo, hi], per device plane."""
    rx = re.compile(pattern)
    iv = [(o.start_ns, o.end_ns) for o in trace.ops if rx.search(o.name)]
    n_planes = len({o.plane for o in trace.ops}) or 1
    return sum(b - a for a, b in clip(iv, lo, hi)) / n_planes


def top_ops(trace: Trace, lo: float, hi: float, n: int = 10
            ) -> list[tuple[str, float]]:
    """The ``n`` op names with the most device time in [lo, hi], leaving
    out the control-flow ops that enclose others."""
    tot: dict[str, float] = {}
    for o in trace.ops:
        if _CONTAINERS.match(o.name):
            continue
        a, b = max(o.start_ns, lo), min(o.end_ns, hi)
        if b > a:
            tot[o.name] = tot.get(o.name, 0.0) + (b - a)
    n_planes = len({o.plane for o in trace.ops}) or 1
    return sorted(((k, v / n_planes) for k, v in tot.items()),
                  key=lambda kv: -kv[1])[:n]
