"""Paged attention Pallas TPU kernel — block-table indirection over the
memos-managed KV page pool, read in place.

This is the kernel-level half of the paper's page machinery: the serving
engine hands the kernel a *block table* (logical page -> physical slot in
the HBM pool, maintained by the sub-buddy allocator + migration engine),
and the kernel streams exactly the pages a row's context covers, in
page-size granules.  SysMon's per-page read counters are charged from the
same block table by the engine — so the access stream the predictor sees
is exact.

Operands: the kernel takes the whole tier-0 pool
``[slots, layers, 2, page, Hkv, D]`` in HBM (``memory_space=pl.ANY``,
nothing slices or copies it) and the layer as a scalar-prefetch operand;
page ``i`` of row ``b`` is ``pool.at[block_table[b, i], layer, 0|1]``,
one contiguous ``page * Hkv * D`` run, moved by a manual DMA.

The live-page walk: the grid runs over tiles of query rows.  Inside a
tile one loop walks the (row, page block) pairs that hold live context —
row ``b`` needs ``ceil(lengths[b] / page)`` pages, so a row of length 0
(decode padding, prefill padding) costs nothing and outputs zeros, and
no table column past a row's context is fetched.  Each step fetches up
to ``pages_per_block`` pages into one VMEM block while the previous
block is scored: the K/V buffers are double-buffered, and the next
block's DMAs start — crossing into the next live row — before the
current block is waited on.

Layout: a fetched page is ``[page*Hkv, D]`` rows ordered (position,
kv-head), so a block's last two dims are tile-aligned for both f32 and
bf16.  All query heads ``[Hkv*G, D]`` score against all rows of the block
in one MXU matmul; a static head-match mask keeps each query head on its
own KV head's rows (the cross-head products are discarded — cheap at
decode widths, and it keeps the kernel free of sub-tile slicing).  The
online softmax and the PV product run in f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
BLOCK_ROWS = 1024        # KV rows (position x kv-head) scored per MXU step
MAX_ROW_TILE = 64        # query rows per grid step


def pages_per_block(page: int, n_kv: int, n_cols: int) -> int:
    """Pages fetched into one VMEM block: as many ``[page*Hkv, D]`` pages
    as fill ``BLOCK_ROWS`` rows, at most the table's width."""
    return max(1, min(n_cols, BLOCK_ROWS // (page * n_kv)))


def _row_tile(n_rows: int) -> int:
    """Largest divisor of ``n_rows`` not above ``MAX_ROW_TILE``."""
    return next(t for t in range(min(n_rows, MAX_ROW_TILE), 0, -1)
                if n_rows % t == 0)


def _paged_kernel(layer_ref, block_table, lengths,   # scalar prefetch
                  q_ref, pool_ref, o_ref,
                  k_buf, v_buf, sems, m_scr, l_scr, acc_scr, *,
                  page_size: int, n_kv: int, group: int, n_cols: int,
                  block_pages: int):
    tile_rows = q_ref.shape[0]
    row0 = pl.program_id(0) * tile_rows
    layer = layer_ref[0]
    rows = page_size * n_kv

    def length(r):
        return lengths[row0 + jnp.minimum(r, tile_rows - 1)]

    def n_live(r):
        return jnp.minimum(pl.cdiv(length(r), page_size), n_cols)

    def next_live(r):
        """First row at or after ``r`` with context, else ``tile_rows``."""
        return jax.lax.while_loop(
            lambda r: (r < tile_rows) & (length(r) <= 0), lambda r: r + 1, r)

    def copies(r, j, buf):
        """(live, DMA) for each page slot of block ``j`` of row ``r``."""
        n = n_live(r)
        base = (row0 + r) * n_cols
        out = []
        for i in range(block_pages):
            col = j * block_pages + i
            slot = block_table[base + jnp.minimum(col, n_cols - 1)]
            for kv, dst in ((0, k_buf), (1, v_buf)):
                out.append((col < n, pltpu.make_async_copy(
                    pool_ref.at[slot, layer, kv],
                    dst.at[buf, pl.ds(i * rows, rows)], sems.at[kv, buf])))
        return out

    def start(r, j, buf):
        for live, cp in copies(r, j, buf):
            pl.when(live)(cp.start)

    def wait(r, j, buf):
        for live, cp in copies(r, j, buf):
            pl.when(live)(cp.wait)

    # page slots a block leaves unfetched keep whatever an earlier block
    # put there; their scores are masked, and zeroed buffers keep that
    # stale data finite (0 * NaN would poison the PV matmul)
    k_buf[...] = jnp.zeros_like(k_buf)
    v_buf[...] = jnp.zeros_like(v_buf)
    o_ref[...] = jnp.zeros_like(o_ref)            # rows of length 0

    r_first = next_live(0)
    pl.when(r_first < tile_rows)(lambda: start(r_first, 0, 0))

    def body(state):
        r, j, buf = state
        last = (j + 1) * block_pages >= n_live(r)
        nr = jnp.where(last, next_live(r + 1), r)
        nj = jnp.where(last, 0, j + 1)
        pl.when(nr < tile_rows)(lambda: start(nr, nj, 1 - buf))

        @pl.when(j == 0)
        def _init():
            m_scr[...] = jnp.full_like(m_scr, NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)

        wait(r, j, buf)
        # QK in the operands' common dtype: bf16 x bf16 products are exact
        # in the f32 accumulator, so this equals the f32 upcast
        ct = jnp.promote_types(q_ref.dtype, k_buf.dtype)
        q = q_ref[r].astype(ct)                              # [Hkv*G, D]
        k = k_buf[buf].astype(ct)                            # [bk*rows, D]
        v = v_buf[buf].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        pos = j * (block_pages * page_size) + col // n_kv
        valid = (row // group == col % n_kv) & (pos < length(r))
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_scr[...]                                  # [Hkv*G, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))
        m_scr[...] = m_new

        @pl.when(last)
        def _finish():
            o_ref[r] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                        ).astype(o_ref.dtype)

        return nr, nj, 1 - buf

    jax.lax.while_loop(lambda s: s[0] < tile_rows, body,
                       (r_first, jnp.int32(0), jnp.int32(0)))


def paged_attention_pooled(q: jnp.ndarray, pool: jnp.ndarray,
                           layer: jnp.ndarray, block_table: jnp.ndarray,
                           lengths: jnp.ndarray, *,
                           interpret=False) -> jnp.ndarray:
    """q: [B, Hkv, G, D] one query per row (pre-scaled);
    pool: [n_slots, n_layers, 2, page, Hkv, D] the memos HBM page pool;
    layer: int32 scalar, the layer whose K/V planes are read;
    block_table: int32 [B, n_pages] (logical page i of row b -> pool slot);
    lengths: int32 [B] context lengths (0: the row attends to nothing and
    its output is zeros).  Returns [B, Hkv, G, D]."""
    B, Hkv, G, D = q.shape
    n_slots, n_layers, _, page, _, _ = pool.shape
    n_cols = block_table.shape[1]
    H = Hkv * G
    rows = page * Hkv
    bk = pages_per_block(page, Hkv, n_cols)
    tb = _row_tile(B)
    kernel = functools.partial(_paged_kernel, page_size=page, n_kv=Hkv,
                               group=G, n_cols=n_cols, block_pages=bk)
    q_spec = pl.BlockSpec((tb, H, D), lambda t, *_: (t, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B // tb,),
        in_specs=[q_spec, pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((2, bk * rows, D), pool.dtype),
            pltpu.VMEM((2, bk * rows, D), pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      block_table.reshape(-1), lengths, q.reshape(B, H, D),
      pool.reshape(n_slots, n_layers, 2, rows, D))
    return out.reshape(B, Hkv, G, D)
