"""Per-page integrity checksum Pallas TPU kernel.

Companion to the ``page_gather`` data mover: where gather packs pages
for a tier move, this kernel folds each page's stored bits into one
uint32 position-weighted checksum (definition + detection proof in
ref.py).  Same scalar-prefetch DMA pipeline and the same chunked
``[lead, a, b]`` page view — the checksum of chunk j computes while
chunk j+1's block streams in, partial sums accumulate in the page's
resident output block — so a scrub or a promotion pre-flight verify
costs one dispatch over the slot list instead of a host round-trip per
page.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.page_gather.page_gather import _chunked

_INT_JNP = {1: jnp.int8, 2: jnp.int16, 4: jnp.int32}


def _checksum_kernel(idx_ref, src_ref, out_ref, *, itemsize):
    # int32 arithmetic wraps exactly like the uint32 definition (the TPU
    # has no unsigned reductions); narrower elements are zero-extended
    c = pl.program_id(1)
    u = jax.lax.bitcast_convert_type(src_ref[...], _INT_JNP[itemsize])
    u = u.astype(jnp.int32)
    if itemsize < 4:
        u = u & ((1 << (8 * itemsize)) - 1)
    # linear element index within the page via per-dim broadcasted iotas
    # (TPU forbids 1D iota), offset by the chunk's first element; the
    # leading block dim is 1 so its iota contributes nothing
    lin = jnp.zeros(u.shape, jnp.int32)
    stride = 1
    for d in range(u.ndim - 1, 0, -1):
        lin = lin + jax.lax.broadcasted_iota(jnp.int32, u.shape, d) * stride
        stride *= u.shape[d]
    lin = lin + c * stride
    s = jnp.sum(u * (2 * lin + 1))

    @pl.when(c == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += jnp.full(out_ref.shape, s, jnp.int32)


def page_checksum_pallas(pool: jnp.ndarray, idx: jnp.ndarray,
                         *, interpret: bool = False) -> jnp.ndarray:
    """pool: [n_slots, *page_shape]; idx: int32 [k] -> uint32 [k]."""
    k = idx.shape[0]
    view, cb, nc = _chunked(pool, itemsize=4)
    itemsize = jnp.dtype(pool.dtype).itemsize
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(k, nc),
        in_specs=[pl.BlockSpec((1, cb, *view.shape[2:]),
                               lambda i, c, idx: (idx[i], c, 0, 0))],
        out_specs=pl.BlockSpec((1, 1, 128), lambda i, c, idx: (i, 0, 0)),
    )
    out = pl.pallas_call(
        partial(_checksum_kernel, itemsize=itemsize),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((k, 1, 128), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(idx.astype(jnp.int32), view)
    return jax.lax.bitcast_convert_type(out[:, 0, 0], jnp.uint32)
