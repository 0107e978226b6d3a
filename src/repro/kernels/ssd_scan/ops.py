"""jit'd wrapper for the SSD scan kernel."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .ssd_scan import ssd_scan_pallas


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, A, Bm, Cm, *, chunk: int = 128,
             interpret: bool = False):
    """x: [B, L, H, P]; dt: [B, L, H]; A: [H]; Bm/Cm: [B, L, N].
    Pads L to a chunk multiple (identity steps: dt=0).  Compiled for the
    TPU; ``interpret=True`` (kernel-parity tests) runs the interpreter."""
    L0 = x.shape[1]
    pad = (-L0) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
    y, h = ssd_scan_pallas(x, dt, A, Bm, Cm, chunk, interpret=interpret)
    return y[:, :L0], h
