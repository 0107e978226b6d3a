"""Paged decode attention Pallas TPU kernel — block-table indirection over
the memos-managed KV page pool.

This is the kernel-level half of the paper's page machinery: the serving
engine hands the kernel a *block table* (logical page -> physical slot in
the HBM pool, maintained by the sub-buddy allocator + migration engine),
and the kernel streams exactly the pages that are resident, in page-size
granules.  SysMon's per-page read counters are charged from the same block
table by the engine — so the access stream the predictor sees is exact.

Grid: (B, n_pages).  The page axis is innermost; the running softmax
state of all Hkv*G query heads persists in VMEM scratch.  Pages are
fetched through a *scalar-prefetched* block table (PrefetchScalarGridSpec),
i.e. the page index feeds the DMA engine ahead of compute — the TPU-native
analogue of the paper's DMA scatter-gather migration reads.

Layout: one grid step loads a whole page of every KV head as one
``[page*Hkv, D]`` block (rows ordered (position, kv-head)), so the block's
last two dims are tile-aligned for both f32 and bf16.  All query heads
``[Hkv*G, D]`` score against all rows in one MXU matmul; a static
head-match mask keeps each query head on its own KV head's rows (the
cross-head products are discarded — cheap at decode widths, and it keeps
the kernel free of sub-tile slicing).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(block_table, lengths,          # scalar-prefetch operands
                  q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *,
                  page_size: int, n_kv: int, group: int):
    b = pl.program_id(0)
    ip = pl.program_id(1)
    np_ = pl.num_programs(1)

    @pl.when(ip == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                 # [Hkv*G, D]
    k = k_ref[0].astype(jnp.float32)                 # [page*Hkv, D]
    v = v_ref[0].astype(jnp.float32)                 # [page*Hkv, D]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # [Hkv*G, page*Hkv]
    row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    pos = ip * page_size + col // n_kv
    valid = (row // group == col % n_kv) & (pos < lengths[b])
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_scr[...]                              # [Hkv*G, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())))
    m_scr[...] = m_new

    @pl.when(ip == np_ - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                    ).astype(o_ref.dtype)


def paged_attention_pooled(q: jnp.ndarray, k_pool: jnp.ndarray,
                           v_pool: jnp.ndarray, block_table: jnp.ndarray,
                           lengths: jnp.ndarray, *,
                           interpret: bool = False) -> jnp.ndarray:
    """q: [B, Hkv, G, D] one decode token per sequence (pre-scaled);
    k/v_pool: [n_slots, page, Hkv, D] memos HBM page pool;
    block_table: int32 [B, n_pages] (logical page i of seq b -> pool slot);
    lengths: int32 [B] current context lengths.
    Returns [B, Hkv, G, D]."""
    B, Hkv, G, D = q.shape
    n_slots, page, _, _ = k_pool.shape
    n_pages = block_table.shape[1]
    H = Hkv * G
    rows = page * Hkv
    kernel = functools.partial(_paged_kernel, page_size=page, n_kv=Hkv,
                               group=G)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_pages),
        in_specs=[
            pl.BlockSpec((1, H, D), lambda b, ip, bt, ln: (b, 0, 0)),
            pl.BlockSpec((1, rows, D), lambda b, ip, bt, ln: (bt[b, ip], 0, 0)),
            pl.BlockSpec((1, rows, D), lambda b, ip, bt, ln: (bt[b, ip], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, D), lambda b, ip, bt, ln: (b, 0, 0)),
        scratch_shapes=[
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
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(block_table, lengths, q.reshape(B, H, D),
      k_pool.reshape(n_slots, rows, D), v_pool.reshape(n_slots, rows, D))
    return out.reshape(B, Hkv, G, D)
