"""What the harness knows of a dense decoder (GQA or MHA attention, an
optional qk-norm, one SwiGLU MLP in every layer, a tied or untied head):
its weights and the work a served step costs.  A configuration whose
``reference`` is ``dense`` is served through these functions; the harness's
side of them is set out in ``benchlib.spec.Cell.model``.

Weights: the parameter tree the paged engine reads, with a stacked layer
axis; ``embed`` [Vp, d]; ``lm_head`` [d, Vp] when the head is untied,
its vocab padded to a multiple of 256.  Matrices are N(0, 1/fan_in),
norm gains 1 + 0.1 N(0, 1) per layer, per norm and per channel
(``benchlib.weights``)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from benchlib.weights import gain, normal, seed_key


def padded_vocab(cfg) -> int:
    return cfg.vocab if cfg.tie_embeddings else -(-cfg.vocab // 256) * 256


def _layer(cfg, dtype, key):
    d, hq, hkv, dh, f = (cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                         cfg.head_dim, cfg.d_ff)
    k = jax.random.split(key, 11)
    attn = {"wq": normal(k[0], (d, hq, dh), d, dtype),
            "wk": normal(k[1], (d, hkv, dh), d, dtype),
            "wv": normal(k[2], (d, hkv, dh), d, dtype),
            "wo": normal(k[3], (hq, dh, d), hq * dh, dtype)}
    if cfg.qk_norm:
        attn["q_norm"] = gain(k[7], dh, dtype)
        attn["k_norm"] = gain(k[8], dh, dtype)
    return {"ln1": gain(k[9], d, dtype), "ln2": gain(k[10], d, dtype),
            "attn": attn,
            "mlp": {"w_gate": normal(k[4], (d, f), d, dtype),
                    "w_up": normal(k[5], (d, f), d, dtype),
                    "w_down": normal(k[6], (f, d), f, dtype)}}


@partial(jax.jit, static_argnums=(0, 1))
def _make(cfg, dtype, key):
    kl, ke, kh, kn = jax.random.split(key, 4)
    layers = jax.lax.map(partial(_layer, cfg, dtype),
                         jax.random.split(kl, cfg.n_layers))
    vp = padded_vocab(cfg)
    params = {"layers": layers,
              "final_norm": gain(kn, cfg.d_model, dtype),
              "embed": normal(ke, (vp, cfg.d_model), cfg.d_model, dtype)}
    if not cfg.tie_embeddings:
        params["lm_head"] = normal(kh, (cfg.d_model, vp), cfg.d_model,
                                   dtype)
    return params


def make_params(cfg, seed: int, dtype=jnp.bfloat16) -> dict:
    """The whole parameter tree, drawn on the default device."""
    return _make(cfg, jnp.dtype(dtype), seed_key(seed))


def abstract_params(cfg, dtype=jnp.bfloat16) -> dict:
    """Shapes and dtypes of ``make_params`` without drawing anything."""
    return jax.eval_shape(lambda k: _make(cfg, jnp.dtype(dtype), k),
                          seed_key(0))


def attention_work(rec, steps, kv_bytes: int = 2) -> list:
    """Every paged-attention call of ``steps``, in each of the layers: per
    decode inner step one call over the batch, per prefill one call over
    the step's prompts.  FLOPs are QK and PV over each query's causal
    prefix; bytes are that prefix's K and V read once per call, plus the
    queries read and the outputs written."""
    cfg = rec.cfg
    hq, hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    calls = []
    for s in steps:
        if s.prefill:
            f = sum(4 * hq * d * lp * (lp + 1) // 2 for lp in s.prefill)
            b = sum(lp * (2 * hkv + 2 * hq) * d * kv_bytes
                    for lp in s.prefill)
            calls.append((f, b))
        if s.decode:
            kmax = max(k for _, k in s.decode)
            for j in range(kmax):
                lens = [p + j + 1 for p, k in s.decode if j < k]
                f = sum(4 * hq * d * n for n in lens)
                b = sum(n * 2 * hkv * d * kv_bytes for n in lens) \
                    + len(lens) * 2 * hq * d * kv_bytes
                calls.append((f, b))
    return cfg.n_layers, calls


def non_embedding_params(cfg) -> int:
    hq, hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dm = cfg.d_model
    per_layer = dm * d * (hq + 2 * hkv) + hq * d * dm + 3 * dm * cfg.d_ff \
        + 2 * dm
    return cfg.n_layers * per_layer


def model_flops(rec, steps) -> float:
    """2 x non-embedding params per token processed, plus attention over
    the live context (QK and PV, every layer), prefill and decode."""
    cfg = rec.cfg
    n_par = non_embedding_params(cfg)
    attn = 4 * cfg.n_heads * cfg.head_dim * cfg.n_layers  # per context token
    total = 0.0
    for s in steps:
        for lp in s.prefill:
            total += 2 * n_par * lp + attn * lp * (lp + 1) / 2
        for p, k in s.decode:
            total += 2 * n_par * k + attn * (k * (p + 1) + k * (k - 1) / 2)
    return total
