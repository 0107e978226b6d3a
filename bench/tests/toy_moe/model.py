"""A toy routed decoder, the model module of the configuration that
``test_data_driven.py`` adds by files alone: the dense module's
attention, norms and head, and in every layer a routed SwiGLU expert
layer in place of the MLP, drawn as the program's
``repro.models.transformer._ffn`` reads it (``lp["moe"]``: ``w_router``
[d, E], ``w_gate`` and ``w_up`` [E, d, f], ``w_down`` [E, f, d])."""
from __future__ import annotations

from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp

from benchlib.spec import load_module
from benchlib.weights import gain, normal, seed_key

_dense = load_module(Path(__file__).with_name("dense.py"))
padded_vocab = _dense.padded_vocab
attention_work = _dense.attention_work


def _layer(cfg, dtype, key):
    d, hq, hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    e, f = cfg.n_experts, cfg.expert_d_ff
    k = jax.random.split(key, 12)
    attn = {"wq": normal(k[0], (d, hq, dh), d, dtype),
            "wk": normal(k[1], (d, hkv, dh), d, dtype),
            "wv": normal(k[2], (d, hkv, dh), d, dtype),
            "wo": normal(k[3], (hq, dh, d), hq * dh, dtype)}
    if cfg.qk_norm:
        attn["q_norm"] = gain(k[7], dh, dtype)
        attn["k_norm"] = gain(k[8], dh, dtype)
    return {"ln1": gain(k[9], d, dtype), "ln2": gain(k[10], d, dtype),
            "attn": attn,
            "moe": {"w_router": normal(k[11], (d, e), d, dtype),
                    "w_gate": normal(k[4], (e, d, f), d, dtype),
                    "w_up": normal(k[5], (e, d, f), d, dtype),
                    "w_down": normal(k[6], (e, f, d), f, dtype)}}


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
    return _make(cfg, jnp.dtype(dtype), seed_key(seed))


def abstract_params(cfg, dtype=jnp.bfloat16) -> dict:
    return jax.eval_shape(lambda k: _make(cfg, jnp.dtype(dtype), k),
                          seed_key(0))


def model_flops(rec, steps) -> float:
    """2 x the parameters a token passes through (attention, the router
    and its ``top_k`` experts, the norms) per token processed, plus
    attention over the live context."""
    cfg = rec.cfg
    hq, hkv, dh, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_model
    per_layer = (d * dh * (hq + 2 * hkv) + hq * dh * d + d * cfg.n_experts
                 + cfg.top_k * 3 * d * cfg.expert_d_ff + 2 * d)
    n_par = cfg.n_layers * per_layer
    attn = 4 * hq * dh * cfg.n_layers
    total = 0.0
    for s in steps:
        for lp in s.prefill:
            total += 2 * n_par * lp + attn * lp * (lp + 1) / 2
        for p, k in s.decode:
            total += 2 * n_par * k + attn * (k * (p + 1) + k * (k - 1) / 2)
    return total
