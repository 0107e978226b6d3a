"""Readers shared by the per-layer metric files under ``bench/metrics``.

Each takes the run's ``RunRecord`` and returns a number, or None where
the run has nothing to read (no such span, no device trace, not a TPU).
Spans and counters are the program's own (``repro.obs``); device numbers
come from the profiler trace of the traced window only."""
from __future__ import annotations

from . import costs, profile


def _span_ms(rec, prefix: str) -> float:
    """Summed milliseconds of the union of the main-thread spans whose
    name starts with ``prefix`` (a nested span is not counted twice)."""
    iv = [(e.ts_ns, e.ts_ns + e.dur_ns) for e in rec.spans
          if e.ph == "X" and e.name.startswith(prefix)]
    return sum(b - a for a, b in profile.union(iv)) / 1e6


def decode_ms_per_step(rec):
    """Host-clock time of the fused decode dispatches (the span ends on
    the device->host transfer of the sampled tokens) per inner step."""
    ks = sum(e.attrs.get("k", 0) for e in rec.spans
             if e.name == "serve.dispatch" and e.attrs)
    return _span_ms(rec, "serve.dispatch") / ks if ks else None


def prefill_ms_per_ktok(rec):
    toks = sum(e.attrs.get("tokens", 0) for e in rec.spans
               if e.name == "serve.prefill" and e.attrs)
    return _span_ms(rec, "serve.prefill") / toks * 1e3 if toks else None


def engine_host_ms_per_dispatch(rec):
    if not rec.dispatches:
        return None
    ms = sum(_span_ms(rec, n) for n in
             ("serve.admit", "serve.provision", "serve.retire"))
    return ms / rec.dispatches


def _traced(rec) -> bool:
    return (rec.trace is not None and rec.peaks is not None
            and rec.platform == "tpu")


def device_idle_share(rec):
    if not _traced(rec):
        return None
    window = rec.trace_hi - rec.trace_lo
    busy = profile.busy_ns(rec.trace, rec.trace_lo, rec.trace_hi)
    return 100.0 * (1.0 - busy / window) if window > 0 and busy > 0 else None


# the Pallas paged-attention kernel's name as the TPU trace shows it
PAGED_ATTENTION = r"^paged_attention$"


def kernel_roofline(rec, pattern: str, work):
    """Least time of ``work(rec, steps)`` for the traced steps
    (``costs.min_seconds``) over the device time of the kernels whose
    trace name matches ``pattern``, in percent."""
    if not _traced(rec) or not rec.traced_steps:
        return None
    t = profile.kernel_ns(rec.trace, pattern, rec.trace_lo,
                          rec.trace_hi) / 1e9
    if t <= 0:
        return None
    return 100.0 * costs.min_seconds(work(rec, rec.traced_steps),
                                     rec.peaks) / t


def paged_attention_roofline(rec):
    """Least time of the live-context attention work of the traced steps
    over the kernel's device time in the trace, in percent."""
    return kernel_roofline(rec, PAGED_ATTENTION, rec.model.attention_work)


def step_mfu(rec):
    """Model FLOPs of the traced steps over the traced window at the
    chip's bf16 peak, in percent."""
    if not _traced(rec) or not rec.traced_steps:
        return None
    window = (rec.trace_hi - rec.trace_lo) / 1e9
    f = rec.model.model_flops(rec, rec.traced_steps)
    return 100.0 * f / (window * rec.peaks["bf16_flops"]) if f else None
