"""SysMon Pallas TPU kernels — the paper's "page shadow array ... raw
byte and bit manipulation" (Sec. 4.2), fused.

Two kernels share this module:

``sysmon_pass_pallas`` — the pass-boundary sweep.  One elementwise pass
over the page-counter arrays computes, per page:
  * WD/RD/COLD classification (weight-2 writes, Sec. 3.1),
  * history-byte shift  hist' = (hist << 1 | wd) & 0xFF,
  * SWAR popcount of the window,
  * the WD_FREQ_H / WD_FREQ_L / UN_WD prediction with the K_Len Reverse
    override (Sec. 3.2, Fig. 4).

``touch_update_pallas`` — the per-sampling scatter-add behind
``core.sysmon.record``.  A decode step hands SysMon a padded list of
touched page ids (block-table prefix reads + the tail-page write); this
kernel turns the event list into dense per-page increment vectors
(d_reads, d_writes, touched) in one blocked sweep, same ownership
discipline as ``kernels/wear_update``: each grid step owns one [block]
span of the page axis and reduces the full event list against it, so the
scatter is race-free across grid steps and bit-exact vs. the numpy
oracle.  This is the piece the serving engine's fused multi-token decode
carries inside ``lax.scan`` — monitoring without leaving the device.

Blocked [bp] pages per grid step; everything stays in int32 vregs (VPU
lanes), zero HBM re-reads — the fused version reads each counter array
once vs. 4 passes for the unfused jnp composition in core/sysmon.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import patterns, predictor


def _pass_kernel(reads_ref, writes_ref, hist_ref,
                 wd_ref, newhist_ref, future_ref, *,
                 window_len: int, k_len: int, hi: int, lo: int):
    r = reads_ref[...].astype(jnp.int32)
    w = writes_ref[...].astype(jnp.int32)
    hist = hist_ref[...].astype(jnp.int32)

    touched = (r + w) > 0
    is_wd = (patterns.WRITE_WEIGHT * w) >= r
    wd_code = jnp.where(touched,
                        jnp.where(is_wd, patterns.WD, patterns.RD),
                        patterns.COLD).astype(jnp.int32)
    wd_bit = (wd_code == patterns.WD).astype(jnp.int32)

    mask = (1 << window_len) - 1
    hist = ((hist << 1) | wd_bit) & mask

    # SWAR popcount (8-bit window inside an int32 lane)
    x = hist
    x = x - ((x >> 1) & 0x55)
    x = (x & 0x33) + ((x >> 2) & 0x33)
    ones = (x + (x >> 4)) & 0x0F

    base = jnp.where(ones >= hi, predictor.WD_FREQ_H,
                     jnp.where(ones >= lo, predictor.WD_FREQ_L,
                               predictor.UN_WD))
    kmask = (1 << k_len) - 1
    suffix = hist & kmask
    fut = jnp.where(suffix == kmask, predictor.WD_FREQ_H, base)
    fut = jnp.where(suffix == 0, predictor.UN_WD, fut)

    wd_ref[...] = wd_code
    newhist_ref[...] = hist
    future_ref[...] = fut


def sysmon_pass_pallas(reads: jnp.ndarray, writes: jnp.ndarray,
                       hist: jnp.ndarray, *, window_len: int = 8,
                       k_len: int = 3, hi: int = 6, lo: int = 2,
                       block: int = 1024, interpret: bool = False):
    """reads/writes: int32 [n]; hist: int32 [n] (low window_len bits).
    Returns (wd_code, new_hist, future) int32 [n]."""
    n = reads.shape[0]
    pad = (-n) % block
    if pad:
        reads = jnp.pad(reads, (0, pad))
        writes = jnp.pad(writes, (0, pad))
        hist = jnp.pad(hist, (0, pad))
    np_ = reads.shape[0] // block
    kernel = functools.partial(_pass_kernel, window_len=window_len,
                               k_len=k_len, hi=hi, lo=lo)
    spec = pl.BlockSpec((block,), lambda i: (i,))
    out = pl.pallas_call(
        kernel,
        grid=(np_,),
        in_specs=[spec, spec, spec],
        out_specs=[spec, spec, spec],
        out_shape=[jax.ShapeDtypeStruct((reads.shape[0],), jnp.int32)] * 3,
        interpret=interpret,
    )(reads.astype(jnp.int32), writes.astype(jnp.int32),
      hist.astype(jnp.int32))
    return tuple(o[:n] for o in out)


def _touch_kernel(ids_ref, r_ref, w_ref,
                  dr_ref, dw_ref, touched_ref, *, block: int):
    i = pl.program_id(0)
    # events down the sublanes ([k, 1] columns), the pages owned by this
    # grid step across the lanes ([1, block] row): the hit matrix reduces
    # over sublanes into lane-dense outputs
    pages = i * block + jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
    hit = ids_ref[...] == pages                             # [k, block]
    r = r_ref[...]
    w = w_ref[...]
    dr_ref[...] = jnp.sum(jnp.where(hit, r, 0), axis=0, keepdims=True)
    dw_ref[...] = jnp.sum(jnp.where(hit, w, 0), axis=0, keepdims=True)
    touched_ref[...] = jnp.max(jnp.where(hit, r + w, 0), axis=0,
                               keepdims=True)


def touch_update_pallas(n_pages: int, page_ids: jnp.ndarray,
                        reads: jnp.ndarray, writes: jnp.ndarray, *,
                        block: int = 512, interpret: bool = False):
    """page_ids: int32 [k] (in-bounds; padded events carry zero weights);
    reads/writes: int32 [k] per-event increments (0 or 1).  Returns dense
    int32 [n_pages] (d_reads, d_writes, touched) where touched is 1 for
    any page with at least one non-zero event (duplicates accumulate in
    the count vectors, dedupe in touched)."""
    k = page_ids.shape[0]
    kpad = (-k) % 128
    if kpad:
        page_ids = jnp.pad(page_ids, (0, kpad))
        reads = jnp.pad(reads, (0, kpad))
        writes = jnp.pad(writes, (0, kpad))
    npad = (-n_pages) % block
    n_full = n_pages + npad
    kernel = functools.partial(_touch_kernel, block=block)
    kfull = page_ids.shape[0]
    espec = pl.BlockSpec((kfull, 1), lambda i: (0, 0))  # every step sees all ids
    pspec = pl.BlockSpec((1, block), lambda i: (0, i))
    col = lambda a: a.astype(jnp.int32).reshape(kfull, 1)  # noqa: E731
    out = pl.pallas_call(
        kernel,
        grid=(n_full // block,),
        in_specs=[espec, espec, espec],
        out_specs=[pspec, pspec, pspec],
        out_shape=[jax.ShapeDtypeStruct((1, n_full), jnp.int32)] * 3,
        interpret=interpret,
    )(col(page_ids), col(reads), col(writes))
    return tuple(o[0, :n_pages] for o in out)
