"""Pure-jnp oracle for the paged decode attention kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def paged_attention_pages_ref(q: jnp.ndarray, k_pages: jnp.ndarray,
                              v_pages: jnp.ndarray,
                              lengths: jnp.ndarray) -> jnp.ndarray:
    """Attention over pre-gathered pages (q pre-scaled).

    k_pages/v_pages: [B, n_pages, page, Hkv, D] — the caller already
    resolved the block table, e.g. by selecting between the tier-0 pool
    and a pinned-host pool per page (the dual-pool serving path)."""
    B, Hkv, G, D = q.shape
    n_pages, page = k_pages.shape[1:3]
    k = k_pages.reshape(B, n_pages * page, Hkv, D)
    v = v_pages.reshape(B, n_pages * page, Hkv, D)
    s = jnp.einsum("bhgd,bkhd->bhgk", q.astype(jnp.float32),
                   k.astype(jnp.float32))
    pos = jnp.arange(n_pages * page)[None, None, None, :]
    s = jnp.where(pos < lengths[:, None, None, None], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", w, v.astype(jnp.float32))
    return out.astype(q.dtype)


def paged_attention_ref(q: jnp.ndarray, pool: jnp.ndarray,
                        layer: jnp.ndarray, block_table: jnp.ndarray,
                        lengths: jnp.ndarray) -> jnp.ndarray:
    """Same signature as paged_attention_pooled (q pre-scaled): gathers
    the rows' pages of ``layer`` straight out of the whole
    [n_slots, n_layers, 2, page, Hkv, D] pool, then attends (shared with
    the dual-pool path).  A row of length 0 attends to nothing and
    outputs zeros, as the kernel's does."""
    out = paged_attention_pages_ref(q, pool[block_table, layer, 0],
                                    pool[block_table, layer, 1], lengths)
    return jnp.where(lengths[:, None, None, None] > 0, out,
                     jnp.zeros_like(out))
