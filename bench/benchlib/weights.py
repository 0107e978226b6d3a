"""Random weights from the seed, made on the device by one jitted program,
in the dtype they are served in and in the parameter tree the paged
engine reads (a stacked layer axis; ``embed`` [V, d]; ``lm_head`` [d, Vp]
when the head is untied, its vocab padded to a multiple of 256).

Matrices are N(0, 1/fan_in), the scale the program's own initialiser
uses, so logits come out with unit spread.  Every norm gain is drawn too,
1 + 0.1 N(0, 1) per layer, per norm and per channel, so the program is
compared with the reference on gains that are not all one."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any non-negative seed, including seeds past 32 bits."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def padded_vocab(cfg) -> int:
    return cfg.vocab if cfg.tie_embeddings else -(-cfg.vocab // 256) * 256


def _normal(key, shape, fan_in, dtype):
    return (jax.random.normal(key, shape, jnp.float32)
            * fan_in ** -0.5).astype(dtype)


def _gain(key, n, dtype):
    return (1.0 + 0.1 * jax.random.normal(key, (n,), jnp.float32)
            ).astype(dtype)


def _layer(cfg, dtype, key):
    d, hq, hkv, dh, f = (cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                         cfg.head_dim, cfg.d_ff)
    k = jax.random.split(key, 11)
    attn = {"wq": _normal(k[0], (d, hq, dh), d, dtype),
            "wk": _normal(k[1], (d, hkv, dh), d, dtype),
            "wv": _normal(k[2], (d, hkv, dh), d, dtype),
            "wo": _normal(k[3], (hq, dh, d), hq * dh, dtype)}
    if cfg.qk_norm:
        attn["q_norm"] = _gain(k[7], dh, dtype)
        attn["k_norm"] = _gain(k[8], dh, dtype)
    return {"ln1": _gain(k[9], d, dtype), "ln2": _gain(k[10], d, dtype),
            "attn": attn,
            "mlp": {"w_gate": _normal(k[4], (d, f), d, dtype),
                    "w_up": _normal(k[5], (d, f), d, dtype),
                    "w_down": _normal(k[6], (f, d), f, dtype)}}


@partial(jax.jit, static_argnums=(0, 1))
def _make(cfg, dtype, key):
    kl, ke, kh, kn = jax.random.split(key, 4)
    layers = jax.lax.map(partial(_layer, cfg, dtype),
                         jax.random.split(kl, cfg.n_layers))
    vp = padded_vocab(cfg)
    params = {"layers": layers,
              "final_norm": _gain(kn, cfg.d_model, dtype),
              "embed": _normal(ke, (vp, cfg.d_model), cfg.d_model, dtype)}
    if not cfg.tie_embeddings:
        params["lm_head"] = _normal(kh, (cfg.d_model, vp), cfg.d_model,
                                    dtype)
    return params


def make_params(cfg, seed: int, dtype=jnp.bfloat16) -> dict:
    """The whole parameter tree, drawn on the default device."""
    return _make(cfg, jnp.dtype(dtype), seed_key(seed))


def abstract_params(cfg, dtype=jnp.bfloat16) -> dict:
    """Shapes and dtypes of ``make_params`` without drawing anything."""
    return jax.eval_shape(lambda k: _make(cfg, jnp.dtype(dtype), k),
                          seed_key(0))


def param_bytes(params) -> int:
    return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
