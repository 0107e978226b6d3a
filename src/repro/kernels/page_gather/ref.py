"""Pure-jnp oracles for page gather/scatter and the fused int8 variants."""
import jax
import jax.numpy as jnp


def int8_step():
    """The quantizer's 127 as an opaque operand: XLA rewrites division by
    a literal into multiplication by its inexact reciprocal, which would
    break bit parity with the host-pool (numpy) quantizer."""
    return jax.lax.optimization_barrier(jnp.float32(127.0))


def page_gather_ref(pool, idx):
    return pool[idx]


def page_scatter_ref(pool, idx, pages):
    return pool.at[idx].set(pages)


def _page_scale(pages):
    """Per-page int8 scale: max(absmax, 1e-8)/127, matching the host-pool
    quantizer (``core.tiers.HostPool.write_batch``) bit for bit."""
    axes = tuple(range(1, pages.ndim))
    return (jnp.maximum(jnp.max(jnp.abs(pages), axis=axes), 1e-8)
            / int8_step())


def _bcast(scale, ndim):
    return scale.reshape((-1,) + (1,) * (ndim - 1))


def quantize_pages_ref(pages):
    """float pages [k, *page] -> (int8 [k, *page], f32 scale [k])."""
    pages = pages.astype(jnp.float32)
    scale = _page_scale(pages)
    q = jnp.clip(jnp.round(pages / _bcast(scale, pages.ndim)), -127, 127)
    return q.astype(jnp.int8), scale.astype(jnp.float32)


def page_gather_quant_ref(pool, idx):
    """Fused gather + per-page int8 quantize (demotion staging)."""
    return quantize_pages_ref(pool[idx])


def page_gather_dequant_ref(pool_q, pool_scale, idx):
    """Fused gather + dequantize out of an int8 pool -> f32 pages."""
    q = pool_q[idx].astype(jnp.float32)
    return q * _bcast(pool_scale[idx].astype(jnp.float32), q.ndim)
