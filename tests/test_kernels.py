"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs ref.py oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention import flash_attention, flash_attention_ref
from repro.kernels.hotness_update import (sysmon_pass, sysmon_pass_ref,
                                          touch_update, touch_update_ref)
from repro.kernels.page_gather import (page_gather, page_gather_ref,
                                       page_scatter, page_scatter_ref)
from repro.kernels.paged_attention import paged_attention, paged_attention_ref
from repro.kernels.ssd_scan import ssd_scan, ssd_scan_ref, ssd_sequential_ref

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


# --- flash attention ----------------------------------------------------------

@pytest.mark.parametrize("B,S,Hq,Hkv,D", [
    (2, 128, 4, 2, 64), (1, 256, 4, 4, 64), (2, 96, 8, 2, 80),
    (1, 64, 6, 3, 128),
])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 32), (False, 0)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, S, Hq, Hkv, D, causal, window, dtype):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(k1, (B, S, Hq, D), dtype)
    k = jax.random.normal(k2, (B, S, Hkv, D), dtype)
    v = jax.random.normal(k3, (B, S, Hkv, D), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          bq=64, bk=64, interpret=True)
    qf = (q.transpose(0, 2, 1, 3).reshape(B * Hq, S, D)
          * jnp.asarray(D ** -0.5, dtype))
    kf = k.transpose(0, 2, 1, 3).reshape(B * Hkv, S, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Hkv, S, D)
    ref = flash_attention_ref(qf, kf, vf, causal=causal, window=window)
    ref = ref.reshape(B, Hq, S, D).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


# --- paged attention -----------------------------------------------------------

_LIVE = "random"         # lengths drawn in [1, page * n_pages]


@pytest.mark.parametrize("B,Hq,Hkv,D,page,n_pages,n_layers,layer,lengths", [
    (3, 8, 2, 64, 16, 4, 1, 0, _LIVE),
    (2, 4, 4, 128, 8, 8, 1, 0, _LIVE),
    (1, 16, 2, 64, 32, 2, 1, 0, _LIVE),
    # a layer past the first of a 3-layer pool
    (3, 8, 2, 64, 16, 4, 3, 2, _LIVE),
    # rows of length 0, 1 and exact page multiples
    (5, 8, 2, 64, 16, 4, 2, 1, (0, 1, 16, 64, 0)),
    # more live pages than one compute block holds (8 pages of 16 x 8)
    (3, 16, 8, 128, 16, 20, 2, 1, (300, 129, 128)),
    # head_dim 96
    (2, 32, 32, 96, 16, 5, 2, 1, (70, 33)),
], ids=["b3", "b2", "b1", "layer2", "edge-lengths", "multi-block", "d96"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_sweep(B, Hq, Hkv, D, page, n_pages, n_layers, layer,
                               lengths, dtype):
    """The Pallas kernel (interpreted, DMAs and semaphores emulated)
    against the gather reference, reading one layer of a whole
    [slots, layers, 2, page, Hkv, D] pool through per-row tables whose
    columns past a row's context are padded with slot 0."""
    from repro.kernels.paged_attention.paged_attention import pages_per_block
    n_slots = B * n_pages + 7
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q = jax.random.normal(ks[0], (B, Hq, D), dtype)
    pool = jax.random.normal(
        ks[1], (n_slots, n_layers, 2, page, Hkv, D), dtype)
    if lengths == _LIVE:
        lengths = np.random.RandomState(0).randint(1, page * n_pages + 1, B)
    lengths = np.asarray(lengths, np.int32)
    bt = np.asarray(jax.random.permutation(ks[3], n_slots - 1) + 1)
    bt = bt[:B * n_pages].reshape(B, n_pages).astype(np.int32)
    live = np.arange(n_pages)[None, :] < -(-lengths[:, None] // page)
    bt = np.where(live, bt, 0)                       # padded with slot 0
    if n_pages > 8:
        assert -(-lengths.max() // page) > pages_per_block(page, Hkv,
                                                           n_pages)
    out = paged_attention(q, pool, layer, jnp.asarray(bt),
                          jnp.asarray(lengths),
                          interpret=pltpu.InterpretParams())
    out = np.asarray(out, np.float32)
    G = Hq // Hkv
    qg = (q * jnp.asarray(D ** -0.5, dtype)).reshape(B, Hkv, G, D)
    ref = paged_attention_ref(qg, pool, jnp.int32(layer), jnp.asarray(bt),
                              jnp.asarray(lengths)).reshape(B, Hq, D)
    assert not np.isnan(out).any()
    np.testing.assert_array_equal(out[lengths == 0], 0.0)
    np.testing.assert_allclose(out, np.asarray(ref, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


# --- SSD scan ---------------------------------------------------------------

@pytest.mark.parametrize("B,L,H,P,N,chunk", [
    (2, 64, 4, 8, 16, 16), (1, 128, 8, 16, 32, 32), (2, 48, 2, 8, 8, 16),
])
def test_ssd_scan_sweep(B, L, H, P, N, chunk):
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    x = jax.random.normal(ks[0], (B, L, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, L, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    Bm = jax.random.normal(ks[3], (B, L, N))
    Cm = jax.random.normal(ks[4], (B, L, N))
    y, h = ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
    yr, hr = ssd_scan_ref(x, dt, A, Bm, Cm, chunk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr),
                               atol=1e-4, rtol=1e-4)
    # also against the sequential ground truth
    ys, hs = ssd_sequential_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ys),
                               atol=1e-3, rtol=1e-3)


def test_ssd_scan_padding():
    """Non-multiple L pads with identity steps."""
    B, L, H, P, N = 1, 37, 2, 8, 8
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    x = jax.random.normal(ks[0], (B, L, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, L, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    Bm = jax.random.normal(ks[3], (B, L, N))
    Cm = jax.random.normal(ks[4], (B, L, N))
    y, _ = ssd_scan(x, dt, A, Bm, Cm, chunk=16, interpret=True)
    ys, _ = ssd_sequential_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ys),
                               atol=1e-3, rtol=1e-3)


# --- page gather / scatter ------------------------------------------------------

@pytest.mark.parametrize("n_slots,k,shape", [(32, 4, (8, 4)), (64, 16, (16,)),
                                             (16, 16, (4, 4, 2))])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int8])
def test_page_gather_scatter(n_slots, k, shape, dtype):
    pool = jnp.arange(n_slots * int(np.prod(shape))).reshape(
        (n_slots, *shape)).astype(dtype)
    idx = jax.random.permutation(jax.random.PRNGKey(4), n_slots)[:k]
    idx = idx.astype(jnp.int32)
    out = page_gather(pool, idx, interpret=True)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(page_gather_ref(pool, idx)))
    pages = (jnp.ones((k, *shape)) * 7).astype(dtype)
    new = page_scatter(pool.copy(), idx, pages, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(new), np.asarray(page_scatter_ref(pool, idx, pages)))


@pytest.mark.parametrize("n_slots,k,shape", [(32, 4, (8, 4)), (16, 8, (4,))])
def test_page_gather_quant_parity(n_slots, k, shape):
    """Fused gather + int8 quantize: the Pallas kernel (interpret mode),
    the XLA dispatch path, and a numpy oracle matching the host-pool
    quantizer agree bit for bit."""
    from repro.kernels.page_gather import page_gather_quant, quantize_pages_ref
    from repro.kernels.page_gather.page_gather import page_gather_quant_pallas
    pool = jax.random.normal(jax.random.PRNGKey(6), (n_slots, *shape),
                             jnp.float32) * 3.0
    idx = jax.random.permutation(jax.random.PRNGKey(7), n_slots)[:k]
    idx = idx.astype(jnp.int32)

    def np_oracle(pool, idx):
        pages = np.asarray(pool)[np.asarray(idx)]
        axes = tuple(range(1, pages.ndim))
        scale = np.maximum(np.max(np.abs(pages), axis=axes), 1e-8) / 127.0
        b = scale.reshape((-1,) + (1,) * (pages.ndim - 1))
        q = np.clip(np.round(pages / b), -127, 127).astype(np.int8)
        return q, scale.astype(np.float32)

    qn, sn = np_oracle(pool, idx)
    qx, sx = page_gather_quant(pool, idx)             # XLA dispatch path
    np.testing.assert_array_equal(np.asarray(qx), qn)
    np.testing.assert_array_equal(np.asarray(sx), sn)
    qp, sp = page_gather_quant_pallas(pool, idx, interpret=True)
    np.testing.assert_array_equal(np.asarray(qp), qn)
    np.testing.assert_array_equal(np.asarray(sp), sn)
    qr, sr = quantize_pages_ref(pool[idx])
    np.testing.assert_array_equal(np.asarray(qr), qn)


def test_page_quant_roundtrip_matches_host_pool():
    """scatter_quant -> gather_dequant reproduces the HostPool int8
    round trip exactly (same scale rule, same clip/round)."""
    from repro.core.hierarchy import MediumSpec
    from repro.core import costmodel as cm
    from repro.core.tiers import HostPool
    from repro.kernels.page_gather import (page_gather_dequant,
                                           page_scatter_quant)
    spec = MediumSpec("NVM", 8, cm.NVM, residency="host", quantize_int8=True)
    hp = HostPool(spec, (4, 2), jnp.float32)
    vals = np.asarray(jax.random.normal(jax.random.PRNGKey(8), (3, 4, 2)),
                      np.float32) * 5.0
    phys = np.asarray([1, 4, 6])
    hp.write_batch(phys, vals)
    want = hp.read_batch(phys)

    pq = jnp.zeros((8, 4, 2), jnp.int8)
    ps = jnp.ones((8,), jnp.float32)
    pq, ps = page_scatter_quant(pq, ps, jnp.asarray(phys, jnp.int32),
                                jnp.asarray(vals))
    np.testing.assert_array_equal(np.asarray(pq[phys]), hp.data[phys])
    got = page_gather_dequant(pq, ps, jnp.asarray(phys, jnp.int32))
    np.testing.assert_array_equal(np.asarray(got), want)


# --- fused SysMon pass -----------------------------------------------------------

@pytest.mark.parametrize("n,block", [(300, 128), (1024, 256), (17, 64)])
def test_sysmon_pass_kernel(n, block):
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    reads = jax.random.randint(ks[0], (n,), 0, 10)
    writes = jax.random.randint(ks[1], (n,), 0, 10)
    hist = jax.random.randint(ks[2], (n,), 0, 256)
    wd, nh, fut = sysmon_pass(reads, writes, hist, block=block, interpret=True)
    wdr, nhr, futr = sysmon_pass_ref(reads, writes, hist)
    np.testing.assert_array_equal(np.asarray(wd), np.asarray(wdr))
    np.testing.assert_array_equal(np.asarray(nh), np.asarray(nhr))
    np.testing.assert_array_equal(np.asarray(fut), np.asarray(futr))


@pytest.mark.parametrize("n,k", [(64, 9), (300, 200), (512, 1)])
def test_touch_update_kernel(n, k):
    """Per-sampling touch scatter-add: Pallas (interpret), XLA fallback,
    and numpy oracle all agree, including duplicate ids, masked (padded)
    events, and the touched dedupe."""
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    ids = jax.random.randint(ks[0], (k,), 0, n)
    is_write = jax.random.bernoulli(ks[1], 0.4, (k,))
    valid = jax.random.bernoulli(ks[2], 0.8, (k,))
    want = touch_update_ref(n, np.asarray(ids), np.asarray(is_write),
                            np.asarray(valid))
    for interpret in (True, None):      # Pallas interpreter / XLA scatter
        got = touch_update(n, ids, is_write, valid, interpret=interpret,
                           block=128)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), w)
    # scalar is_write broadcast + no mask
    got = touch_update(n, ids, True, interpret=True, block=128)
    want = touch_update_ref(n, np.asarray(ids), True)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), w)


# --- page checksum -----------------------------------------------------------------

@pytest.mark.parametrize("n_slots,k,shape", [(16, 4, (8, 4)), (8, 8, (16,)),
                                             (12, 5, (3, 2, 8, 128))])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int8])
def test_page_checksum_kernel_parity(n_slots, k, shape, dtype):
    """Chunked Pallas checksum (interpret mode) vs the numpy oracle over
    the stored bits, bit-exact (uint32 wraparound included)."""
    from repro.kernels.page_checksum import checksum_np, page_checksum
    rng = np.random.RandomState(k)
    raw = rng.randint(-100, 100, (n_slots, *shape))
    pool = jnp.asarray(raw).astype(dtype)
    idx = jnp.asarray(rng.permutation(n_slots)[:k], jnp.int32)
    got = page_checksum(pool, idx, interpret=True)
    want = checksum_np(np.asarray(pool)[np.asarray(idx)])
    np.testing.assert_array_equal(np.asarray(got), want)
