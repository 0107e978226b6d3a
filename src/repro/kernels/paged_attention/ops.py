"""jit'd public wrapper for paged decode attention.

Same three execution paths as ``kernels/page_gather`` / ``wear_update``:

  * TPU            — the Pallas kernel, compiled: it reads the whole
                     tier-0 pool in place and DMAs only each row's live
                     pages (``paged_attention.py``);
  * explicit       — ``interpret=`` (``True`` or a
                     ``pltpu.InterpretParams``) runs the Pallas kernel in
                     interpreter mode (kernel-parity tests only);
  * other backends — the pure-jnp gather+softmax reference, jit-compiled
                     by XLA: it gathers the rows' pages of the layer
                     straight out of the pool.  Interpreter-mode Pallas
                     emulates every DMA, which would dominate the serving
                     decode step on CPU hosts — the XLA path keeps the
                     fused multi-token dispatch compute-bound instead of
                     emulation-bound.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .paged_attention import paged_attention_pooled
from .ref import paged_attention_pages_ref, paged_attention_ref


@partial(jax.jit, static_argnames=("interpret",))
def paged_attention(q: jnp.ndarray, pool: jnp.ndarray, layer,
                    block_table: jnp.ndarray, lengths: jnp.ndarray, *,
                    interpret=None) -> jnp.ndarray:
    """q: [B, Hq, D] one query per row; pool: the whole tier-0 pool
    [n_slots, n_layers, 2, page, Hkv, D], read in place; layer: int32
    scalar, the layer attended; block_table: [B, n_pages]; lengths: [B]
    (row b reads its first ceil(lengths[b] / page) pages; length 0 reads
    nothing and outputs zeros).  Returns [B, Hq, D]."""
    B, Hq, D = q.shape
    Hkv = pool.shape[4]
    G = Hq // Hkv
    scale = D ** -0.5
    qg = (q * scale).reshape(B, Hkv, G, D)
    args = (qg, pool, jnp.asarray(layer, jnp.int32),
            block_table.astype(jnp.int32), lengths.astype(jnp.int32))
    if interpret is None and jax.default_backend() != "tpu":
        out = paged_attention_ref(*args)
    else:
        out = paged_attention_pooled(*args, interpret=interpret or False)
    return out.reshape(B, Hq, D)


def paged_attention_prefill(q_all: jnp.ndarray, pool: jnp.ndarray, layer,
                            block_tables: jnp.ndarray,
                            lengths: jnp.ndarray) -> jnp.ndarray:
    """Full-sequence prefill attention over freshly written KV pages.

    Treats every packed position as its own decode-style query row:
    ``q_all`` [L, Hq, D] attends through per-row block tables
    [L, n_pages] (each row lists only its *own segment's* pages, padded
    with slot 0) masked to ``lengths`` [L] = causal prefix length.  The
    mask drives every out-of-prefix score to -1e30 exactly as the decode
    path does, so position p of a packed segment produces bitwise the
    same output as a decode step at position p over the same pool —
    segments can never attend across packing boundaries because foreign
    pages simply aren't in the row's table.  It is ``paged_attention``
    itself: a row fetches only the pages of its causal prefix, and a
    padding row (length 0) fetches none.
    """
    return paged_attention(q_all, pool, layer, block_tables, lengths)


def paged_attention_prefill_pages(q_all: jnp.ndarray, k_pages: jnp.ndarray,
                                  v_pages: jnp.ndarray,
                                  lengths: jnp.ndarray) -> jnp.ndarray:
    """Dual-pool prefill attention over pre-gathered per-row pages
    (pinned-tier variant of ``paged_attention_prefill``)."""
    return paged_attention_pages(q_all, k_pages, v_pages, lengths)


@jax.jit
def paged_attention_pages(q: jnp.ndarray, k_pages: jnp.ndarray,
                          v_pages: jnp.ndarray,
                          lengths: jnp.ndarray) -> jnp.ndarray:
    """Decode attention over pre-gathered pages (the dual-pool serving
    path: the caller selects each page from the tier-0 pool or the
    pinned-host pool before attending).  q: [B, Hq, D]; k/v_pages:
    [B, n_pages, page, Hkv, D]; lengths: [B].  XLA everywhere — the
    Pallas pooled kernel reads straight from a single pool and does not
    apply; identical math to ``paged_attention`` on the same pages."""
    B, Hq, D = q.shape
    Hkv = k_pages.shape[3]
    G = Hq // Hkv
    scale = D ** -0.5
    qg = (q * scale).reshape(B, Hkv, G, D)
    out = paged_attention_pages_ref(qg, k_pages, v_pages,
                                    lengths.astype(jnp.int32))
    return out.reshape(B, Hq, D)
