"""The least time the chip could take for a kernel's calls, from the
operations and bytes each call needs.

The harness records, for every engine step, which prompts were prefilled
and which rows decoded from which position for how many inner steps
(``StepWork``).  The configuration's model module turns that into the
FLOPs and bytes of each kernel call (``attention_work``) and into the
model's FLOPs (``model_flops``); any implementation is judged on the
same work."""
from __future__ import annotations

from dataclasses import dataclass, field


@dataclass
class StepWork:
    prefill: list[int] = field(default_factory=list)   # prompt lengths
    decode: list[tuple[int, int]] = field(default_factory=list)  # (pos, k)


def min_seconds(work: tuple, peaks: dict) -> float:
    """Least time of ``work``, ``(layers, [(flops, bytes), ...])`` (each
    of ``layers`` layers makes every call of the list): per call, the
    larger of FLOPs over the bf16 peak and bytes over HBM bandwidth,
    summed, times the layers."""
    layers, calls = work
    pf, bw = peaks["bf16_flops"], peaks["hbm_bytes_per_s"]
    total = 0.0
    for f, b in calls:
        total += max(f / pf, b / bw)
    return total * layers
