"""jit'd wrappers for the fused SysMon kernels.

``sysmon_pass`` and ``touch_update`` follow the ``kernels/wear_update``
dispatch discipline:

  * TPU            — the blocked Pallas histogram kernel, compiled;
  * explicit       — ``interpret=True`` runs the Pallas kernel in
                     interpreter mode (kernel-parity tests);
  * other backends — jitted XLA scatter-adds with identical integer
                     semantics (bit-exact: integer adds are associative).

Both paths are traceable, so the serving engine can call ``touch_update``
from inside its ``lax.scan``-fused decode dispatch.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .hotness_update import sysmon_pass_pallas, touch_update_pallas
from .ref import sysmon_pass_ref


@partial(jax.jit, static_argnames=("window_len", "k_len", "hi", "lo",
                                   "block", "interpret"))
def sysmon_pass(reads, writes, hist, *, window_len: int = 8, k_len: int = 3,
                hi: int = 6, lo: int = 2, block: int = 1024,
                interpret: bool | None = None):
    if interpret is None:
        if jax.default_backend() != "tpu":
            return sysmon_pass_ref(reads, writes, hist, window_len=window_len,
                                   k_len=k_len, hi=hi, lo=lo)
        interpret = False
    return sysmon_pass_pallas(reads, writes, hist, window_len=window_len,
                              k_len=k_len, hi=hi, lo=lo, block=block,
                              interpret=interpret)


@partial(jax.jit, static_argnums=(0,))
def _touch_xla(n_pages: int, ids, r, w):
    d_reads = jnp.zeros((n_pages,), jnp.int32).at[ids].add(r)
    d_writes = jnp.zeros((n_pages,), jnp.int32).at[ids].add(w)
    touched = jnp.zeros((n_pages,), jnp.int32).at[ids].max(
        jnp.minimum(r + w, 1))
    return d_reads, d_writes, touched


def touch_update(n_pages: int, page_ids, is_write, valid=None, *,
                 block: int = 512, interpret: bool | None = None):
    """Dense per-page increments for one SysMon sampling.

    page_ids: int [k] touched pages (may repeat; clipped in-bounds);
    is_write: bool or bool [k]; valid: optional bool [k] mask for padded
    id lists.  Returns int32 [n_pages] (d_reads, d_writes, touched) —
    counts accumulate duplicates, touched dedupes to {0, 1}.
    """
    ids = jnp.clip(jnp.asarray(page_ids, jnp.int32).reshape(-1), 0,
                   n_pages - 1)
    k = ids.shape[0]
    if isinstance(is_write, bool):
        is_write = jnp.full((k,), is_write)
    is_write = jnp.broadcast_to(jnp.asarray(is_write).reshape(-1), (k,))
    if valid is None:
        valid = jnp.ones((k,), bool)
    valid = jnp.broadcast_to(jnp.asarray(valid).reshape(-1), (k,))
    r = (valid & ~is_write).astype(jnp.int32)
    w = (valid & is_write).astype(jnp.int32)
    if interpret is None:
        if jax.default_backend() != "tpu":
            return _touch_xla(n_pages, ids, r, w)
        interpret = False
    block = min(block, -(-n_pages // 128) * 128)
    return touch_update_pallas(n_pages, ids, r, w, block=block,
                               interpret=interpret)
