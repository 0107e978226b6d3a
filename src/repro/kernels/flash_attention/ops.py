"""jit'd public wrapper for the flash attention kernel.

Handles: GQA layout flattening, qk scaling, head_dim padding to a 128
multiple (MXU lane width), and seq padding to block multiples.  The
kernel is compiled for the TPU; ``interpret=True`` (kernel-parity tests)
runs it in the Pallas interpreter.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .flash_attention import flash_attention_bhsd


def _pad_to(x: jnp.ndarray, axis: int, mult: int) -> jnp.ndarray:
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@partial(jax.jit, static_argnames=("causal", "window", "bq", "bk", "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: int = 0,
                    bq: int = 128, bk: int = 256,
                    interpret: bool = False) -> jnp.ndarray:
    """q: [B, Sq, Hq, D]; k/v: [B, Sk, Hkv, D] -> [B, Sq, Hq, D]."""
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    scale = D ** -0.5

    qf = (q.transpose(0, 2, 1, 3).reshape(B * Hq, Sq, D) * scale)
    kf = k.transpose(0, 2, 1, 3).reshape(B * Hkv, Sk, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Hkv, Sk, D)

    bq_eff = min(bq, max(8, Sq))
    bk_eff = min(bk, max(8, Sk))
    qf = _pad_to(_pad_to(qf, 1, bq_eff), 2, 128)
    kf = _pad_to(_pad_to(kf, 1, bk_eff), 2, 128)
    vf = _pad_to(_pad_to(vf, 1, bk_eff), 2, 128)

    out = flash_attention_bhsd(qf, kf, vf, causal=causal, window=window,
                               bq=bq_eff, bk=bk_eff, seq_len=Sk,
                               interpret=interpret)
    out = out[:, :Sq, :D].reshape(B, Hq, Sq, D).transpose(0, 2, 1, 3)
    return out
