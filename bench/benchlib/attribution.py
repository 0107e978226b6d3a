"""Readers that split the served work by program and by wait.

Device time is attributed to a program by the program's own spans: the
engine's ``serve.prefill`` span encloses one prefill execution and
``serve.dispatch`` one fused decode execution, each from its launch to
the blocking read of its output (``serve.prefill.wait`` and
``serve.dispatch.wait``).  A device op belongs to the execution whose
span was open when the op started.  Work and device time are counted
over the same executions: those whose span starts inside the traced
window.

The spans come from ``RunRecord.spans`` on the program's clock.  They are
put on the trace's clock by the harness's ``bench.step.<i>`` markers:
the ``i``-th step of the window opens with its ``serve.admit`` span, so
the median over the traced steps of (marker start - that span's start)
is the offset.  The marker starts a few microseconds before the span, so
the spans land that much early on the trace: negligible against
executions of milliseconds.

The padded-work shares read the program's counters as the window
closed (``RunRecord.counters``; the harness resets the registry as the
window opens).

Each reader returns None where the run has nothing to read: no device
trace or not a TPU, or a program without the spans or counters read.
"""
from __future__ import annotations

import re
import statistics

from . import costs, profile
from .readers import PAGED_ATTENTION, _traced

PREFILL = "serve.prefill"
DECODE = "serve.dispatch"
WAIT_SUFFIX = ".wait"


def trace_offset(rec) -> float | None:
    """Trace clock minus the program's span clock, in nanoseconds."""
    admits = sorted(e.ts_ns for e in rec.spans if e.name == "serve.admit")
    if len(admits) != len(rec.steps):
        return None          # the span ring dropped events, or no spans
    marks = {int(h.name.rsplit(".", 1)[1]): h.start_ns
             for h in rec.trace.host if h.name.startswith("bench.step.")}
    offs = [m - admits[i] for i, m in marks.items() if i < len(admits)]
    return statistics.median(offs) if offs else None


def executions(rec, name: str) -> list[tuple[float, float, dict]]:
    """(start, end, attrs) on the trace's clock of the spans called
    ``name`` that start inside the traced window."""
    off = trace_offset(rec)
    if off is None:
        return []
    out = []
    for e in rec.spans:
        if e.name == name:
            a = e.ts_ns + off
            if rec.trace_lo <= a < rec.trace_hi:
                out.append((a, a + e.dur_ns, e.attrs or {}))
    return sorted(out, key=lambda x: x[0])


def ops_in(trace, spans, pattern: str | None = None) -> list:
    """The device ops that started while one of ``spans`` was open,
    optionally only those whose name matches ``pattern``."""
    rx = re.compile(pattern) if pattern else None
    starts = [a for a, _, _ in spans]
    out, j = [], 0
    for o in trace.ops:                       # both sorted by start
        while j < len(spans) and spans[j][1] <= o.start_ns:
            j += 1
        if (j < len(spans) and starts[j] <= o.start_ns
                and (rx is None or rx.search(o.name))):
            out.append(o)
    return out


def device_ns(ops) -> float:
    """Union of the ops' intervals, averaged over the device planes that
    ran any (as ``profile.busy_ns``)."""
    by_plane: dict[str, list] = {}
    for o in ops:
        by_plane.setdefault(o.plane, []).append((o.start_ns, o.end_ns))
    if not by_plane:
        return 0.0
    return sum(sum(b - a for a, b in profile.union(iv))
               for iv in by_plane.values()) / len(by_plane)


def split_ns(rec) -> dict | None:
    """Device time of the traced window's prefill and decode executions
    and of every other op that ran in the window, with the busy time, in
    nanoseconds."""
    if not _traced(rec) or trace_offset(rec) is None:
        return None
    pre = ops_in(rec.trace, executions(rec, PREFILL))
    dec = ops_in(rec.trace, executions(rec, DECODE))
    mine = {id(o) for o in pre + dec}
    rest = [o for o in rec.trace.ops if id(o) not in mine
            and o.end_ns > rec.trace_lo and o.start_ns < rec.trace_hi]
    return {"prefill": device_ns(pre), "decode": device_ns(dec),
            "other": profile.busy_ns(profile.Trace(rest), rec.trace_lo,
                                     rec.trace_hi),
            "busy": profile.busy_ns(rec.trace, rec.trace_lo, rec.trace_hi)}


def prefill_device_ms_per_ktok(rec):
    """Device time of the prefill executions per 1000 prompt tokens."""
    if not _traced(rec):
        return None
    spans = executions(rec, PREFILL)
    toks = sum(s[2].get("tokens", 0) for s in spans)
    t = device_ns(ops_in(rec.trace, spans))
    return t / 1e6 / toks * 1e3 if toks and t > 0 else None


def decode_device_ms_per_step(rec):
    """Device time of the fused decode executions per inner step."""
    if not _traced(rec):
        return None
    spans = executions(rec, DECODE)
    ks = sum(s[2].get("k", 0) for s in spans)
    t = device_ns(ops_in(rec.trace, spans))
    return t / 1e6 / ks if ks and t > 0 else None


def decode_kernel_roofline(rec, pattern: str, work):
    """Least time of ``work(rec, steps)`` for the traced steps' decode
    rows alone (``costs.min_seconds``) over the device time of the
    kernels matching ``pattern`` inside the decode executions, in
    percent."""
    if not _traced(rec) or not rec.traced_steps:
        return None
    t = device_ns(ops_in(rec.trace, executions(rec, DECODE), pattern)) / 1e9
    if t <= 0:
        return None
    steps = [costs.StepWork(decode=s.decode) for s in rec.traced_steps]
    return 100.0 * costs.min_seconds(work(rec, steps), rec.peaks) / t


def paged_attention_decode_roofline(rec):
    """Least time of the traced steps' live-context decode attention over
    the kernel's device time inside the decode executions, in percent."""
    return decode_kernel_roofline(rec, PAGED_ATTENTION,
                                  rec.model.attention_work)


def host_idle_share(rec):
    """Share of the traced window in which no device op ran while the
    main thread was in no ``*.wait`` span: the chip waiting on host work,
    not on the host reading a result back."""
    if not _traced(rec):
        return None
    off = trace_offset(rec)
    waits = [(e.ts_ns + off, e.ts_ns + e.dur_ns + off) for e in rec.spans
             if e.name.endswith(WAIT_SUFFIX)] if off is not None else []
    lo, hi = rec.trace_lo, rec.trace_hi
    if not waits or hi <= lo:
        return None
    ops = [(o.start_ns, o.end_ns) for o in rec.trace.ops]
    covered = profile.union(profile.clip(ops + waits, lo, hi))
    return 100.0 * (1.0 - sum(b - a for a, b in covered) / (hi - lo))


def counter_share(rec, num: str, den: str):
    """100 x counter ``num`` / counter ``den``, as the window closed."""
    n, d = rec.counters.get(num), rec.counters.get(den)
    return 100.0 * n / d if n is not None and d else None


def decode_live_row_share(rec):
    """Live rows of the decode dispatches' padded row-steps, in percent."""
    return counter_share(rec, "serving.tokens_sampled",
                         "serving.decode_row_slots")


def prefill_fill_share(rec):
    """Prompt tokens of the prefill dispatches' bucket positions, in
    percent."""
    return counter_share(rec, "serving.prefill_tokens",
                         "serving.prefill_slots")
