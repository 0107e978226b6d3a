"""Operations and bytes the served work needs, from its shapes.

The harness records, for every engine step, which prompts were prefilled
and which rows decoded from which position for how many inner steps
(``StepWork``).  The functions here turn that into the least time the
chip could take for the paged-attention kernel (live context only, not
the width of the block table or the grid) and into the model's FLOPs.
Any implementation is judged on the same work."""
from __future__ import annotations

from dataclasses import dataclass, field


@dataclass
class StepWork:
    prefill: list[int] = field(default_factory=list)   # prompt lengths
    decode: list[tuple[int, int]] = field(default_factory=list)  # (pos, k)


def _attn_dims(cfg):
    return cfg.n_heads, cfg.n_kv_heads, cfg.head_dim


def attention_min_seconds(cfg, steps: list[StepWork], peaks: dict,
                          kv_bytes: int = 2) -> float:
    """Least time of every paged-attention call in ``steps``: per layer,
    per decode inner step (one call over the batch) and per prefill
    (one call over the step's prompts), the larger of FLOPs over the
    bf16 peak and bytes over HBM bandwidth.  FLOPs are QK and PV over
    each query's causal prefix; bytes are that prefix's K and V read
    once per call, plus the queries read and the outputs written."""
    hq, hkv, d = _attn_dims(cfg)
    pf, bw = peaks["bf16_flops"], peaks["hbm_bytes_per_s"]
    total = 0.0
    for s in steps:
        if s.prefill:
            f = sum(4 * hq * d * lp * (lp + 1) // 2 for lp in s.prefill)
            b = sum(lp * (2 * hkv + 2 * hq) * d * kv_bytes
                    for lp in s.prefill)
            total += max(f / pf, b / bw)
        if s.decode:
            kmax = max(k for _, k in s.decode)
            for j in range(kmax):
                lens = [p + j + 1 for p, k in s.decode if j < k]
                f = sum(4 * hq * d * n for n in lens)
                b = sum(n * 2 * hkv * d * kv_bytes for n in lens) \
                    + len(lens) * 2 * hq * d * kv_bytes
                total += max(f / pf, b / bw)
    return total * cfg.n_layers


def non_embedding_params(cfg) -> int:
    hq, hkv, d = _attn_dims(cfg)
    dm = cfg.d_model
    per_layer = dm * d * (hq + 2 * hkv) + hq * d * dm + 3 * dm * cfg.d_ff \
        + 2 * dm
    return cfg.n_layers * per_layer


def model_flops(cfg, steps: list[StepWork]) -> float:
    """2 x non-embedding params per token processed, plus attention over
    the live context (QK and PV, every layer), prefill and decode."""
    hq, _, d = _attn_dims(cfg)
    n_par = non_embedding_params(cfg)
    attn = 4 * hq * d * cfg.n_layers        # per token of context
    total = 0.0
    for s in steps:
        for lp in s.prefill:
            total += 2 * n_par * lp + attn * lp * (lp + 1) / 2
        for p, k in s.decode:
            total += 2 * n_par * k + attn * (k * (p + 1) + k * (k - 1) / 2)
    return total
