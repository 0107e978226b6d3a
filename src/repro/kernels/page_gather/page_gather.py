"""Page pack/unpack Pallas TPU kernel — the migration engine's data mover.

The optimistic (unlocked-DMA) migration path (core/migration.py) stages a
batch of discontiguous pages into one contiguous buffer before the
host<->device transfer — the TPU analogue of the paper's scatter-gather DMA
mode (Sec. 6.3): ``dma_memcpy_pg_to_pg`` over a page list.  The page
indices come in through scalar prefetch so the DMA engine can start
fetching page i+1's HBM block while page i streams out (double-buffered
automatically by the Pallas pipeline).

gather:  staging[i] = pool[idx[i]]   (pack for eviction / host copy-out)
scatter: pool[idx[i]] = staging[i]   (unpack after promotion / copy-in)

A page is moved in chunks: it is viewed as ``[lead, a, b]`` (``a, b`` its
last two dims — a free view that keeps the TPU tiling) and the grid walks
``(page, chunk)`` with chunks of the lead axis no larger than
``CHUNK_BYTES``, so a multi-megabyte KV page never has to fit VMEM whole.

``page_gather_quant_pallas`` fuses the demotion gather with per-page
int8 quantization for ``quantize_int8`` pinned-host tiers: one kernel
packs pool pages into (int8 staging, per-page scale) instead of
gather -> host copy -> numpy quantize — the page never round-trips
through host float32.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import int8_step

CHUNK_BYTES = 1 << 20     # per-block VMEM budget of the page movers


def page_view(page_shape: tuple[int, ...]) -> tuple[int, int, int]:
    """``(lead, a, b)``: a page as a 3-D block of its last two dims."""
    dims = (1, 1, *page_shape)
    return math.prod(dims[:-2]), dims[-2], dims[-1]


def lead_chunk(lead: int, row_bytes: int, budget: int = CHUNK_BYTES) -> int:
    """Largest divisor of ``lead`` whose chunk stays within ``budget``."""
    cap = max(budget // max(row_bytes, 1), 1)
    return max(d for d in range(1, min(lead, cap) + 1) if lead % d == 0)


def _chunked(pool: jnp.ndarray, itemsize: int | None = None):
    """(pool as [n, lead, a, b], chunk rows, n chunks)."""
    lead, a, b = page_view(pool.shape[1:])
    row = a * b * (itemsize or jnp.dtype(pool.dtype).itemsize)
    cb = lead_chunk(lead, row)
    return pool.reshape(pool.shape[0], lead, a, b), cb, lead // cb


def _copy_kernel(idx_ref, src_ref, dst_ref):
    dst_ref[...] = src_ref[...]


def page_gather_pallas(pool: jnp.ndarray, idx: jnp.ndarray,
                       *, interpret: bool = False) -> jnp.ndarray:
    """pool: [n_slots, *page_shape]; idx: int32 [k] -> [k, *page_shape]."""
    k = idx.shape[0]
    view, cb, nc = _chunked(pool)
    blk = (1, cb, *view.shape[2:])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(k, nc),
        in_specs=[pl.BlockSpec(blk, lambda i, c, idx: (idx[i], c, 0, 0))],
        out_specs=pl.BlockSpec(blk, lambda i, c, idx: (i, c, 0, 0)),
    )
    out = pl.pallas_call(
        _copy_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((k, *view.shape[1:]), pool.dtype),
        interpret=interpret,
    )(idx.astype(jnp.int32), view)
    return out.reshape(k, *pool.shape[1:])


def page_scatter_pallas(pool: jnp.ndarray, idx: jnp.ndarray,
                        pages: jnp.ndarray, *,
                        interpret: bool = False) -> jnp.ndarray:
    """pool[idx[i]] = pages[i]; returns the updated pool (donated input).

    Slots not referenced by idx are passed through untouched via
    input_output_aliasing.
    """
    k = idx.shape[0]
    view, cb, nc = _chunked(pool)
    blk = (1, cb, *view.shape[2:])

    def scatter_kernel(idx_ref, pages_ref, pool_ref, out_ref):
        out_ref[...] = pages_ref[...]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(k, nc),
        in_specs=[
            pl.BlockSpec(blk, lambda i, c, idx: (i, c, 0, 0)),       # pages
            # pool: aliased to the output and never read — left in place
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(blk, lambda i, c, idx: (idx[i], c, 0, 0)),
    )
    out = pl.pallas_call(
        scatter_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(view.shape, pool.dtype),
        input_output_aliases={2: 0},  # pool -> out (operand idx incl. prefetch)
        interpret=interpret,
    )(idx.astype(jnp.int32), pages.reshape(k, *view.shape[1:]), view)
    return out.reshape(pool.shape)


def _gather_quant_kernel(idx_ref, step_ref, src_ref, q_ref, scale_ref,
                         amax_scr):
    # phase 0 folds every chunk's absmax, phase 1 quantizes the chunks
    # with the finished page scale (the page is read twice, never whole)
    ph = pl.program_id(1)
    c = pl.program_id(2)
    x = src_ref[...].astype(jnp.float32)

    @pl.when((ph == 0) & (c == 0))
    def _init():
        amax_scr[...] = jnp.zeros_like(amax_scr)

    @pl.when(ph == 0)
    def _absmax():
        amax_scr[...] = jnp.maximum(amax_scr[...], jnp.max(jnp.abs(x)))

    @pl.when(ph == 1)
    def _quantize():
        scale = jnp.maximum(jnp.max(amax_scr[...]), 1e-8) / step_ref[0, 0]
        scale_ref[...] = jnp.full(scale_ref.shape, scale, jnp.float32)
        q_ref[...] = jnp.clip(jnp.round(x / scale), -127,
                              127).astype(jnp.int8)


def page_gather_quant_pallas(pool: jnp.ndarray, idx: jnp.ndarray, *,
                             interpret: bool = False):
    """Fused pack + int8 quantize: pool [n_slots, *page_shape]; idx int32
    [k] -> (int8 [k, *page_shape], f32 scale [k]).  Same scalar-prefetch
    DMA pipeline as ``page_gather_pallas`` with the per-page absmax /
    round / clip folded into the copy — the staging buffer leaves the
    kernel already quantized (scale = max(absmax, 1e-8)/127, matching
    the host-pool quantizer bit for bit).  The grid is (page, phase,
    chunk); during the absmax phase the int8 output block stays parked
    on the page's first chunk, so it is only written back once the
    quantize phase has filled it."""
    k = idx.shape[0]
    view, cb, nc = _chunked(pool, itemsize=4)
    blk = (1, cb, *view.shape[2:])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(k, 2, nc),
        in_specs=[
            pl.BlockSpec((1, 128), lambda i, ph, c, idx: (0, 0)),
            pl.BlockSpec(blk, lambda i, ph, c, idx: (idx[i], c, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec(blk, lambda i, ph, c, idx: (i, c * ph, 0, 0)),
            pl.BlockSpec((1, 1, 128), lambda i, ph, c, idx: (i, 0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((1, 128), jnp.float32)],
    )
    q, scale = pl.pallas_call(
        _gather_quant_kernel,
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct((k, *view.shape[1:]), jnp.int8),
            jax.ShapeDtypeStruct((k, 1, 128), jnp.float32),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(idx.astype(jnp.int32), jnp.full((1, 128), int8_step()), view)
    return q.reshape(k, *pool.shape[1:]), scale[:, 0, 0]
