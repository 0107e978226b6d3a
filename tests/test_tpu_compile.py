"""Compile every kernel of the served path for a described TPU v5e chip.

Nothing runs: each kernel is lowered and compiled by the TPU compiler
for one chip of a ``v5e:2x2`` topology that is described, not attached,
at qwen3_4b's published KV widths (page 16, 8 KV heads, head_dim 128,
36 layers).  Interpret-mode parity tests cannot see tiling rules or
VMEM limits; these compiles do, and they cost no chip time.

The topology is described inside a module fixture (never at import), so
every test worker collects the same tests and only workers that run
these tests load the TPU compiler.  The persistent compilation cache is
off around these compiles: an entry written for a described chip cannot
be read back without one.
"""
from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch

CFG = get_arch("qwen3_4b")
PAGE = 16
FAST_SLOTS = 128          # the HBM tier of the chip smoke
PIN_SLOTS = 64
MAX_PAGES = 34            # block-table width: 512 prompt + 32 new tokens
KV_DTYPES = [jnp.bfloat16, jnp.float32]


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def topo(no_persistent_cache):
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def pinned(topo):
    return SingleDeviceSharding(topo.devices[0], memory_kind="pinned_host")


@pytest.fixture(scope="module")
def page_shape():
    return (CFG.n_layers, 2, PAGE, CFG.n_kv_heads, CFG.head_dim)


@pytest.fixture
def as_tpu(monkeypatch):
    """Steer the ops wrappers onto their TPU branch (they ask
    ``jax.default_backend()``, which sees this host's CPU)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _compile(fn, *args, **jit_kw):
    compiled = jax.jit(fn, **jit_kw).lower(*args).compile()
    return compiled, compiled.as_text()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _producers(hlo: str) -> dict[str, tuple[str, str]]:
    """Instruction name -> (opcode, operand list) of compiled HLO text."""
    out = {}
    for m in re.finditer(r"%([\w.-]+) = \S+(?: \S+)*? ([\w-]+)\(([^)]*)\)",
                         hlo):
        out[m.group(1)] = (m.group(2), m.group(3))
    return out


@pytest.mark.parametrize("rows", [4, 512], ids=["decode", "prefill"])
@pytest.mark.parametrize("dtype", KV_DTYPES, ids=["bf16", "f32"])
def test_paged_attention_compiles(one_chip, page_shape, as_tpu, rows,
                                  dtype):
    """The kernel over the whole ``[slots, layers, 2, page, Hkv, D]`` pool
    with a ``layer`` operand, inside a program shaped like the decode
    step: per layer, the K/V append into the donated pool, then
    attention.  The kernel must read the pool in place — its pool
    operand a bitcast of the appended pool, no slice or copy of a layer
    plane or of the pool anywhere — and keep its trace name."""
    from repro.kernels.paged_attention import paged_attention
    hkv, d = CFG.n_kv_heads, CFG.head_dim
    pool = _spec((FAST_SLOTS, *page_shape), dtype, one_chip)

    def step(pool, q, kv_new, slot, off, tables, lengths):
        for layer in range(2):
            pool = pool.at[slot, layer, 0, off].set(kv_new, mode="drop")
            pool = pool.at[slot, layer, 1, off].set(kv_new, mode="drop")
            q = q + paged_attention(q, pool, layer, tables, lengths)
        return q, pool

    i32 = lambda *shape: _spec(shape, jnp.int32, one_chip)  # noqa: E731
    _, hlo = _compile(
        step, pool, _spec((rows, CFG.n_heads, d), jnp.bfloat16, one_chip),
        _spec((rows, hkv, d), dtype, one_chip), i32(rows), i32(rows),
        i32(rows, MAX_PAGES), i32(rows), donate_argnums=(0,))
    calls = re.findall(r"%(paged_attention(?:\.\d+)?) = .*custom-call\("
                       r"([^)]*)\).*tpu_custom_call", hlo)
    assert len(calls) == 2, "the kernel must trace as paged_attention"
    ops = _producers(hlo)
    for _, operands in calls:
        pool_arg = operands.split(",")[-1].strip().lstrip("%")
        assert ops[pool_arg][0] == "bitcast", ops[pool_arg]
    planes = [f"[{FAST_SLOTS},{PAGE},{hkv},{d}]",
              f"[{FAST_SLOTS},{PAGE * hkv},{d}]"]
    assert not any(p in hlo for p in planes)
    whole = "[" + ",".join(map(str, (FAST_SLOTS, *page_shape))) + "]"
    assert not re.search(re.escape(whole) + r"\{[^}]*\} copy", hlo)


@pytest.mark.parametrize("dtype", KV_DTYPES, ids=["bf16", "f32"])
@pytest.mark.parametrize("kernel", ["gather", "scatter", "gather_quant",
                                    "checksum"])
def test_page_movers_compile(one_chip, page_shape, kernel, dtype):
    from repro.kernels.page_checksum.page_checksum import page_checksum_pallas
    from repro.kernels.page_gather.page_gather import (
        page_gather_pallas, page_gather_quant_pallas, page_scatter_pallas)
    k = 8
    pool = _spec((FAST_SLOTS, *page_shape), dtype, one_chip)
    idx = _spec((k,), jnp.int32, one_chip)
    if kernel == "scatter":
        _, hlo = _compile(page_scatter_pallas, pool, idx,
                          _spec((k, *page_shape), dtype, one_chip),
                          donate_argnums=(0,))
    else:
        fn = {"gather": page_gather_pallas,
              "gather_quant": page_gather_quant_pallas,
              "checksum": page_checksum_pallas}[kernel]
        _, hlo = _compile(fn, pool, idx)
    assert "tpu_custom_call" in hlo


def test_touch_update_compiles(one_chip):
    from repro.kernels.hotness_update.hotness_update import \
        touch_update_pallas
    n_pages, k = 1024, 4 * 34
    ev = _spec((k,), jnp.int32, one_chip)
    _, hlo = _compile(lambda i, r, w: touch_update_pallas(n_pages, i, r, w),
                      ev, ev, ev)
    assert "tpu_custom_call" in hlo


def test_wear_update_compiles(one_chip):
    from repro.kernels.wear_update.wear_update import wear_update_pallas
    ev = _spec((4,), jnp.int32, one_chip)
    _, hlo = _compile(wear_update_pallas,
                      _spec((PIN_SLOTS,), jnp.int32, one_chip), ev, ev)
    assert "tpu_custom_call" in hlo


def test_sysmon_pass_compiles(one_chip):
    from repro.kernels.hotness_update.hotness_update import sysmon_pass_pallas
    arr = _spec((1024,), jnp.int32, one_chip)
    _, hlo = _compile(sysmon_pass_pallas, arr, arr, arr)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("dtype", KV_DTYPES, ids=["bf16", "f32"])
@pytest.mark.parametrize("op", ["gather", "scatter"])
def test_pinned_pool_movers_compile(one_chip, pinned, page_shape, as_tpu,
                                    op, dtype):
    """The pinned-host pool's gather/scatter: the pool lives in host
    memory and is moved explicitly to the device and back inside the
    program, around the Pallas page mover."""
    from repro.core.tiers import pinned_gather, pinned_scatter
    k = 8
    pool = _spec((PIN_SLOTS, *page_shape), dtype, pinned)
    idx = _spec((k,), jnp.int32, one_chip)
    if op == "gather":
        compiled, hlo = _compile(pinned_gather, pool, idx)
    else:
        compiled, hlo = _compile(pinned_scatter, pool, idx,
                                 _spec((k, *page_shape), dtype, one_chip))
        out = compiled.output_shardings
        assert out.memory_kind == "pinned_host"
    assert "tpu_custom_call" in hlo
