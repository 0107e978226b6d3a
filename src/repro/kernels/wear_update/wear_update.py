"""Wear-counter scatter-add Pallas TPU kernel (NVM telemetry, Sec. 7.1).

Every write that lands on the slow (NVM-analogue) tier must bump that
physical slot's wear counter — the online signal behind the paper's
lifetime projection and wear-leveling feedback.  The update is a
scatter-add over a histogram array:

    wear[slot_ids[i]] += amount[i]        for every write event i

Same layout discipline as ``kernels/hotness_update``: a 1-D grid over
blocked spans of the counter array, everything in int32 VPU lanes.  A
scatter is race-prone across grid steps, so each step instead *owns* one
counter block and reduces the full event list against it — a [k, block]
compare/select/sum that reads the event arrays once per block and writes
each counter exactly once (deterministic, bit-exact vs. the numpy
oracle).  Event lists are short (one entry per page write in a pass), so
k stays in the hundreds while the block dimension rides the lanes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _wear_kernel(ids_ref, amt_ref, wear_ref, out_ref, *, block: int):
    i = pl.program_id(0)
    # events down the sublanes ([k, 1] columns), the counters owned by
    # this grid step across the lanes ([1, block] row)
    slots = i * block + jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
    hits = jnp.where(ids_ref[...] == slots, amt_ref[...], 0)   # [k, block]
    out_ref[...] = wear_ref[...] + jnp.sum(hits, axis=0, keepdims=True)


def wear_update_pallas(wear: jnp.ndarray, slot_ids: jnp.ndarray,
                       amount: jnp.ndarray, *, block: int = 512,
                       interpret: bool = False) -> jnp.ndarray:
    """wear: int32 [n]; slot_ids/amount: int32 [k].  Returns wear with
    ``amount[i]`` added at ``slot_ids[i]`` (duplicates accumulate).
    Out-of-range ids must be masked by the caller via ``amount == 0``."""
    n = wear.shape[0]
    pad = (-n) % block
    if pad:
        wear = jnp.pad(wear, (0, pad))
    k = slot_ids.shape[0]
    kpad = (-k) % 128
    if kpad:
        # padded events point at a real slot but carry zero amount
        slot_ids = jnp.pad(slot_ids, (0, kpad))
        amount = jnp.pad(amount, (0, kpad))
    n_full = wear.shape[0]
    kernel = functools.partial(_wear_kernel, block=block)
    kfull = slot_ids.shape[0]
    espec = pl.BlockSpec((kfull, 1), lambda i: (0, 0))  # every step sees all ids
    wspec = pl.BlockSpec((1, block), lambda i: (0, i))
    out = pl.pallas_call(
        kernel,
        grid=(n_full // block,),
        in_specs=[espec, espec, wspec],
        out_specs=wspec,
        out_shape=jax.ShapeDtypeStruct((1, n_full), jnp.int32),
        interpret=interpret,
    )(slot_ids.astype(jnp.int32).reshape(kfull, 1),
      amount.astype(jnp.int32).reshape(kfull, 1),
      wear.astype(jnp.int32).reshape(1, n_full))
    return out[0, :n]
