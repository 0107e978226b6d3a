"""TierStore — the N-tier hybrid page store (MCHA analogue, Sec. 5.1).

Logical pages live in one of the pools described by a
:class:`~repro.core.hierarchy.MemoryHierarchy` — an ordered list of
:class:`~repro.core.hierarchy.MediumSpec` tiers (fastest first):

  * **device** tiers — one jax array pool each (tier 0 is HBM and is what
    compute reads from; additional device tiers simulate e.g. a DRAM
    channel while keeping migration on-accelerator);
  * **host** tiers — numpy pools (the NVM/CXL analogue), optionally
    int8-quantized to model NVM's cheap-read/expensive-write asymmetry,
    and storing bfloat16 payloads as their uint16 bit-pattern (no silent
    widening to float32);
  * **pinned_host** tiers — host-capacity jax pools addressable from
    device programs: migrations scatter into them in one program instead
    of staging numpy copies, int8 quantization fuses into the
    gather/scatter dispatch,
    and the fused serving dispatch appends KV and charges wear counters
    into them directly.

A page table maps logical page -> (tier, slot); per-page version counters
are bumped by every write so the optimistic (unlocked-DMA) migration path
can detect pages dirtied mid-copy, exactly like the paper's post-hoc
dirty-bit check (Sec. 6.3).

Slot allocation inside every pool goes through a per-tier color-aware
SubBuddy allocator so bank/slab-targeted placement (Algorithm 2) is
honored in each tier independently.

NVM wear telemetry (Sec. 7.1) attaches to **any** host tier whose spec
sets ``wear_tracked``: slot ids handed out by that tier's allocator are
*logical*; a per-tier ``repro.nvm`` wear tracker maps them to physical
rows through a remap table, charges a per-physical-slot write counter on
every write (single-page and batched paths alike — migration demotion
commits included), and lets a per-tier Start-Gap leveler rotate the
physical rows without the allocator, page table, or migration engines
noticing.

``TierConfig`` survives as the two-tier compatibility shim: constructing
``TierStore(TierConfig(...))`` routes through
``MemoryHierarchy.two_tier(...)`` and reproduces the pre-redesign
fast/slow behavior bit for bit.
"""
from __future__ import annotations

import warnings
from dataclasses import dataclass, replace

import jax
import jax.numpy as jnp
import numpy as np

from repro.faults.injector import get_injector
from repro.faults.integrity import PageIntegrity
from repro.kernels.page_checksum import page_checksum
from repro.kernels.page_gather import (page_gather, page_gather_dequant,
                                       page_scatter, page_scatter_quant)

from .allocator import SubBuddyAllocator, SubBuddyConfig
from .hierarchy import MediumSpec, MemoryHierarchy

NO_SLOT = -1


@dataclass
class TierConfig:
    """Two-tier compatibility config (the pre-redesign API surface).

    Kept as a thin shim: ``TierStore`` converts it to
    ``MemoryHierarchy.two_tier(...)`` + :class:`StoreConfig`.  New code
    should build a :class:`StoreConfig` directly.
    """

    n_pages: int                 # logical page count
    fast_slots: int              # HBM pool capacity (pages)
    slow_slots: int              # host pool capacity (pages)
    page_shape: tuple[int, ...]  # payload shape per page
    dtype: jnp.dtype = jnp.float32
    n_banks: int | None = None   # None -> auto-size to the smallest pool
    n_slabs: int | None = None
    quantize_slow: bool = False  # int8-quantize cold pages (soft-NVM analogue)
    track_wear: bool = True      # per-slot NVM wear counters (Sec. 7.1)
    wear_leveling: bool = True   # Start-Gap rotation over the slow pool
    gap_write_interval: int | None = None  # None -> costmodel 95% target

    def hierarchy(self) -> MemoryHierarchy:
        return MemoryHierarchy.two_tier(
            self.fast_slots, self.slow_slots,
            quantize_slow=self.quantize_slow, track_wear=self.track_wear,
            wear_leveling=self.wear_leveling,
            gap_write_interval=self.gap_write_interval)


@dataclass
class StoreConfig:
    """Generic store config: a hierarchy plus the logical page space."""

    n_pages: int
    page_shape: tuple[int, ...]
    hierarchy: MemoryHierarchy
    dtype: jnp.dtype = jnp.float32
    # color geometry; None auto-sizes (up to 32 x 16) so every color
    # exists in the smallest pool — explicit values that don't fit are
    # clamped with a warning
    n_banks: int | None = None
    n_slabs: int | None = None

    # -- two-tier compat accessors (fast = tier 0, slow = deepest) -----------
    @property
    def fast_slots(self) -> int:
        return self.hierarchy[0].slots

    @property
    def slow_slots(self) -> int:
        return self.hierarchy[self.hierarchy.deepest].slots

    @property
    def quantize_slow(self) -> bool:
        return self.hierarchy[self.hierarchy.deepest].quantize_int8


def _shrink_to_fit(n_banks: int, n_slabs: int, slots: int) -> tuple[int, int]:
    """Halve banks, then slabs, until every color exists in a pool of
    ``slots`` pages (the PFN space always contains all colors; a slot
    pool only does when n_colors <= n_slots)."""
    while n_banks * n_slabs > max(slots, 1) and n_banks > 1:
        n_banks //= 2
    while n_banks * n_slabs > max(slots, 1) and n_slabs > 1:
        n_slabs //= 2
    return n_banks, n_slabs


def _clamp_geometry(cfg: StoreConfig) -> StoreConfig:
    """Resolve the *monitor* color geometry (SysMon's bank/slab frequency
    tables): the default (``n_banks``/``n_slabs`` = None) auto-sizes
    silently up to 32 x 16 so every color exists in the smallest pool; an
    *explicitly requested* geometry that can't fit everywhere is clamped
    with a warning — silently changing what the caller asked for hid real
    misconfigurations.  Each tier's *allocator* geometry is derived
    separately from its own ``MediumSpec.slots`` (see ``_tier_geometry``);
    ``target_color`` folds the monitor's frequency space onto each tier's
    allocator geometry."""
    explicit = cfg.n_banks is not None or cfg.n_slabs is not None
    want_banks = 32 if cfg.n_banks is None else cfg.n_banks
    want_slabs = 16 if cfg.n_slabs is None else cfg.n_slabs
    min_slots = min(t.slots for t in cfg.hierarchy)
    n_banks, n_slabs = _shrink_to_fit(want_banks, want_slabs, min_slots)
    if explicit and (n_banks, n_slabs) != (want_banks, want_slabs):
        warnings.warn(
            f"TierStore color geometry {want_banks}x{want_slabs} "
            f"(banks x slabs) exceeds the smallest pool "
            f"({min_slots} slots); monitor geometry clamped to "
            f"{n_banks}x{n_slabs} (each tier's allocator keeps its own "
            "geometry sized to its pool)",
            UserWarning, stacklevel=3)
    return replace(cfg, n_banks=n_banks, n_slabs=n_slabs)


def _tier_geometry(want_banks: int | None, want_slabs: int | None,
                   spec: MediumSpec) -> tuple[int, int]:
    """Per-tier allocator geometry derived from the tier's own capacity:
    the requested (or default 32x16) grid shrunk until every color exists
    in *this* tier's pool — a 64-slot HBM tier no longer forces a
    4096-slot NVM tier down to the same handful of colors."""
    return _shrink_to_fit(32 if want_banks is None else want_banks,
                          16 if want_slabs is None else want_slabs,
                          spec.slots)


# =============================================================================
# per-tier pools
# =============================================================================

def _pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


def _pad_idx_np(slots) -> np.ndarray:
    """Pad an index vector to the next power-of-two length by repeating
    its last entry — **in numpy**, before anything touches jax.

    Migration batch sizes are data-dependent, and every distinct
    gather/scatter length would otherwise compile its own XLA executable
    (including the padding concatenate itself, were it a jnp op) — pow2
    bucketing bounds the jit cache to log2(max) shapes.  A duplicated
    index is harmless: gathers just produce extra rows (staging buffers
    stay padded end-to-end; host copies slice in numpy), and scatters
    rewrite the same slot with the same value."""
    slots = np.asarray(slots, np.int64).reshape(-1)
    pad = _pow2(slots.size) - slots.size
    if pad:
        slots = np.concatenate([slots, np.repeat(slots[-1:], pad)])
    return slots


def _pad_pages(pages, k_padded: int):
    """Pad a page batch to match its padded index vector: numpy batches
    pad by repeating the last page; a jax batch must already be padded
    (it came out of a padded gather with the matching length)."""
    if pages.shape[0] == k_padded:
        return pages
    if isinstance(pages, np.ndarray):
        pad = k_padded - pages.shape[0]
        return np.concatenate([
            pages, np.repeat(pages[-1:], pad, axis=0)])
    raise ValueError(
        f"device page batch of {pages.shape[0]} rows does not match its "
        f"padded index vector ({k_padded}); pass staging buffers through "
        "unsliced, or pad on the host")


class DevicePool:
    """A jax-resident page pool ([slots, *page_shape] in the store dtype)."""

    def __init__(self, spec: MediumSpec, page_shape: tuple[int, ...], dtype):
        self.spec = spec
        self.dtype = dtype
        self.data = jnp.zeros((spec.slots, *page_shape), dtype)

    def write_one(self, slot: int, value) -> None:
        self.data = self.data.at[slot].set(jnp.asarray(value, self.dtype))

    def read_one(self, slot: int) -> np.ndarray:
        return np.asarray(self.data[slot], np.float32)

    def gather(self, slots) -> jnp.ndarray:
        """Pack discontiguous slots into one contiguous staging buffer on
        device (Pallas page_gather on TPU, XLA gather elsewhere).  The
        result is **pow2-padded** (trailing rows repeat the last page);
        host consumers slice to the true count in numpy."""
        idx = _pad_idx_np(slots)
        return page_gather(self.data, jnp.asarray(idx, jnp.int32))

    def scatter(self, slots, pages: jnp.ndarray) -> None:
        """pool[slots[i]] = pages[i]; the pool buffer is donated, slots
        not referenced pass through untouched.  ``pages`` may be the
        padded output of a matching-size gather, or an exact-count numpy
        batch (padded here)."""
        idx = _pad_idx_np(slots)
        pages = _pad_pages(pages, idx.size)
        self.data = page_scatter(self.data, jnp.asarray(idx, jnp.int32),
                                 jnp.asarray(pages).astype(self.dtype))


class HostPool:
    """A numpy page pool with the host-tier storage formats.

    float32/float64 payloads are stored natively; bfloat16 payloads are
    stored as their **uint16 bit-pattern** (bit-exact round trip, half the
    bytes — not silently widened to float32); ``quantize_int8`` stores
    int8 + a per-page scale (the lossy soft-NVM analogue).  All reads
    return float32.
    """

    def __init__(self, spec: MediumSpec, page_shape: tuple[int, ...], dtype):
        self.spec = spec
        self.page_shape = page_shape
        self.quantized = spec.quantize_int8
        self.bf16 = (not self.quantized) and jnp.dtype(dtype) == jnp.bfloat16
        self.scale = None
        if self.quantized:
            self.data = np.zeros((spec.slots, *page_shape), np.int8)
            self.scale = np.ones((spec.slots,), np.float32)
        elif self.bf16:
            self.data = np.zeros((spec.slots, *page_shape), np.uint16)
        else:
            self.data = np.zeros((spec.slots, *page_shape),
                                 np.dtype(jnp.dtype(dtype).name))

    def _bcast(self, scale: np.ndarray) -> np.ndarray:
        return scale.reshape((-1,) + (1,) * len(self.page_shape))

    def write_one(self, phys: int, value: np.ndarray) -> None:
        if self.quantized:
            scale = max(float(np.max(np.abs(value))), 1e-8) / 127.0
            self.data[phys] = np.clip(
                np.round(value / scale), -127, 127).astype(np.int8)
            self.scale[phys] = scale
        elif self.bf16:
            self.data[phys] = value.astype(jnp.bfloat16).view(np.uint16)
        else:
            self.data[phys] = value

    def read_one(self, phys: int) -> np.ndarray:
        if self.quantized:
            return self.data[phys].astype(np.float32) * self.scale[phys]
        if self.bf16:
            return self.data[phys].view(jnp.bfloat16).astype(np.float32)
        return np.asarray(self.data[phys], np.float32)

    def write_batch(self, phys: np.ndarray, values: np.ndarray) -> None:
        """pool[phys[i]] = values[i], quantizing per page when int8
        (bit-identical to the per-page write_one)."""
        if self.quantized:
            axes = tuple(range(1, values.ndim))
            scale = np.maximum(np.max(np.abs(values), axis=axes), 1e-8) / 127.0
            q = np.clip(np.round(values / self._bcast(scale)), -127, 127)
            self.data[phys] = q.astype(np.int8)
            self.scale[phys] = scale.astype(np.float32)
        elif self.bf16:
            self.data[phys] = values.astype(jnp.bfloat16).view(np.uint16)
        else:
            self.data[phys] = values

    def read_batch(self, phys: np.ndarray) -> np.ndarray:
        if self.quantized:
            return (self.data[phys].astype(np.float32)
                    * self._bcast(self.scale[phys]))
        if self.bf16:
            return self.data[phys].view(jnp.bfloat16).astype(np.float32)
        return np.asarray(self.data[phys], np.float32)

    def swap_rows(self, a: int, b: int) -> None:
        """Swap two physical rows in place (Start-Gap leveling advance)."""
        self.data[[a, b]] = self.data[[b, a]]
        if self.scale is not None:
            self.scale[[a, b]] = self.scale[[b, a]]


def _pin_host(x) -> jnp.ndarray:
    """Place ``x`` (numpy or jax) in the default device's pinned host
    memory.  Already-pinned arrays pass through without a copy.  A
    backend whose device exposes no ``pinned_host`` memory cannot hold a
    pinned-host tier, and says so."""
    dev = jax.devices()[0]
    kinds = sorted(m.kind for m in dev.addressable_memories())
    if "pinned_host" not in kinds:
        raise ValueError(
            f"a pinned_host tier needs pinned host memory, but {dev} "
            f"({dev.platform}) exposes only {kinds}; back the tier with "
            f"residency='host' instead")
    return jax.device_put(x, jax.sharding.SingleDeviceSharding(
        dev, memory_kind="pinned_host"))


# Device code never reads host memory directly: every program over a
# pinned pool moves the pool into device memory first and places the
# updated pool back in pinned host memory on the way out.  The TPU
# compiler turns the two placements into host<->device copies; backends
# that keep all memory on the host (CPU) treat them as no-ops and return
# the result in device memory, which ``PinnedHostPool.data`` pins again.
# Pinned pools are never donated: the CPU runtime aborts when a donated
# pinned buffer is aliased to a device-memory result, and on the TPU the
# pool is copied in and out anyway, so donation would only save the
# second host-side buffer.

def to_device_memory(x: jnp.ndarray) -> jnp.ndarray:
    """Inside a program: ``x`` moved into device memory."""
    return jax.device_put(x, jax.memory.Space.Device)


def to_pinned_host(x: jnp.ndarray) -> jnp.ndarray:
    """Inside a program: ``x`` placed in pinned host memory."""
    return jax.device_put(x, jax.memory.Space.Host)


@jax.jit
def pinned_gather(pool, idx):
    """staging[i] = pool[idx[i]] for a pinned ``pool``; staging on device."""
    return page_gather(to_device_memory(pool), idx)


@jax.jit
def pinned_scatter(pool, idx, pages):
    """pool[idx[i]] = pages[i] for a pinned ``pool``."""
    return to_pinned_host(page_scatter(to_device_memory(pool), idx, pages))


@jax.jit
def _pinned_gather_dequant(pool_q, pool_scale, idx):
    return page_gather_dequant(to_device_memory(pool_q),
                               to_device_memory(pool_scale), idx)


@jax.jit
def _pinned_scatter_quant(pool_q, pool_scale, idx, pages):
    q, scale = page_scatter_quant(to_device_memory(pool_q),
                                  to_device_memory(pool_scale), idx, pages)
    return to_pinned_host(q), to_pinned_host(scale)


@jax.jit
def _pinned_swap_rows(pool, pair, rev):
    pool = to_device_memory(pool)
    return to_pinned_host(pool.at[pair].set(pool[rev]))


@jax.jit
def _pinned_token_write(pool, phys, offset, layer_kv):
    pool = to_device_memory(pool)
    return to_pinned_host(
        pool.at[phys, :, :, offset].set(layer_kv.astype(pool.dtype)))


@jax.jit
def _pinned_checksum(pool, idx):
    return page_checksum(to_device_memory(pool), idx)


class PinnedHostPool:
    """A host-capacity page pool addressable from device programs.

    The pool is a single jax buffer in pinned host memory (the backend
    must expose ``memory_kind="pinned_host"``), so migration engines
    gather/scatter it inside the jax runtime — demotion commits scatter
    into it in one program instead of staging a numpy copy — and the fused
    serving dispatch appends KV into it and bumps its wear counters
    without a host round trip.  Every program over the pool moves it to
    device memory and back explicitly (``to_device_memory`` /
    ``to_pinned_host``); assigning ``data`` re-pins the result.

    ``quantize_int8`` keeps the pool as int8 + per-page scale with the
    quantization fused into the gather/scatter dispatch
    (``page_gather_quant`` / ``page_scatter_quant``: one kernel instead
    of gather -> host -> numpy quantize).  Non-quantized pools store the
    store dtype natively (bf16 stays bf16 — no uint16 bit-pattern
    gymnastics needed, the buffer is a real jax array).
    """

    def __init__(self, spec: MediumSpec, page_shape: tuple[int, ...], dtype):
        self.spec = spec
        self.page_shape = page_shape
        self.dtype = dtype
        self.quantized = spec.quantize_int8
        self._scale = None
        shape = (spec.slots, *page_shape)
        if self.quantized:
            self.data = np.zeros(shape, np.int8)
            self.scale = np.ones((spec.slots,), np.float32)
        else:
            self.data = np.zeros(shape, jnp.dtype(dtype))

    @property
    def data(self) -> jnp.ndarray:
        return self._data

    @data.setter
    def data(self, value) -> None:
        self._data = _pin_host(value)

    @property
    def scale(self) -> jnp.ndarray | None:
        return self._scale

    @scale.setter
    def scale(self, value) -> None:
        self._scale = None if value is None else _pin_host(value)

    # -- HostPool-compatible per-physical-slot API -----------------------------
    def write_one(self, phys: int, value: np.ndarray) -> None:
        self.write_batch(np.asarray([phys], np.int64), value[None])

    def read_one(self, phys: int) -> np.ndarray:
        return self.read_batch(np.asarray([phys], np.int64))[0]

    def write_batch(self, phys: np.ndarray, values: np.ndarray) -> None:
        self.scatter(phys, np.asarray(values, np.float32))

    def read_batch(self, phys: np.ndarray) -> np.ndarray:
        k = np.asarray(phys).size
        return np.asarray(self.gather(phys), np.float32)[:k]

    # -- raw stored rows (integrity checksums, fault injection) ----------------
    def read_raw(self, phys: int) -> np.ndarray:
        """One physical row's stored representation (int8 when quantized)."""
        return np.array(pinned_gather(self.data,
                                      jnp.asarray([phys], jnp.int32))[0])

    def write_raw(self, phys: int, row: np.ndarray) -> None:
        self.data = pinned_scatter(self.data, jnp.asarray([phys], jnp.int32),
                                   jnp.asarray(row[None], self.data.dtype))

    def checksums(self, phys) -> jnp.ndarray:
        """uint32 checksums of the stored bits of rows ``phys`` (padded)."""
        return _pinned_checksum(self.data, jnp.asarray(phys, jnp.int32))

    # -- device-addressable bulk API (jax in, jax out) -------------------------
    def gather(self, phys) -> jnp.ndarray:
        """Pow2-padded gather, like :meth:`DevicePool.gather` (fused
        dequantize for int8 pools); the staging buffer is in device
        memory."""
        idx = jnp.asarray(_pad_idx_np(phys), jnp.int32)
        if self.quantized:
            return _pinned_gather_dequant(self.data, self.scale, idx)
        return pinned_gather(self.data, idx)

    def scatter(self, phys, pages) -> None:
        """pool[phys[i]] = pages[i]; fuses the int8 quantize into the
        same dispatch for quantized pools."""
        idx = _pad_idx_np(phys)
        pages = _pad_pages(pages, idx.size)
        idx = jnp.asarray(idx, jnp.int32)
        if self.quantized:
            self.data, self.scale = _pinned_scatter_quant(
                self.data, self.scale, idx,
                jnp.asarray(pages).astype(jnp.float32))
        else:
            self.data = pinned_scatter(self.data, idx,
                                       jnp.asarray(pages).astype(self.dtype))

    def write_token(self, phys: int, offset: int, layer_kv) -> None:
        """One token's K/V ([L, 2, Hkv, Dh]) into row ``phys`` at in-page
        ``offset``, in one program (no host read-modify-write)."""
        self.data = _pinned_token_write(self.data, jnp.int32(phys),
                                        jnp.int32(offset),
                                        jnp.asarray(layer_kv))

    def swap_rows(self, a: int, b: int) -> None:
        pair = jnp.asarray([a, b], jnp.int32)
        rev = jnp.asarray([b, a], jnp.int32)
        self.data = _pinned_swap_rows(self.data, pair, rev)
        if self.scale is not None:
            self.scale = _pinned_swap_rows(self.scale, pair, rev)


class _LevelerView:
    """Adapter handing ``StartGapLeveler`` one host tier's pool (the
    leveler's ``slow_pool``/``slow_scale`` contract predates N tiers)."""

    def __init__(self, pool: HostPool | PinnedHostPool):
        self._pool = pool

    @property
    def slow_pool(self) -> np.ndarray:
        return self._pool.data

    @property
    def slow_scale(self) -> np.ndarray | None:
        return self._pool.scale

    def swap_rows(self, a: int, b: int) -> None:
        self._pool.swap_rows(a, b)


# =============================================================================
# the store
# =============================================================================

class TierStore:
    def __init__(self, cfg: TierConfig | StoreConfig):
        if isinstance(cfg, TierConfig):
            cfg = StoreConfig(n_pages=cfg.n_pages, page_shape=cfg.page_shape,
                              hierarchy=cfg.hierarchy(), dtype=cfg.dtype,
                              n_banks=cfg.n_banks, n_slabs=cfg.n_slabs)
        want_banks, want_slabs = cfg.n_banks, cfg.n_slabs   # pre-clamp ask
        cfg = _clamp_geometry(cfg)
        self.cfg = cfg
        self.hierarchy = cfg.hierarchy
        self.n_tiers = cfg.hierarchy.n_tiers

        def make_pool(t: MediumSpec):
            if t.is_device:
                return DevicePool(t, cfg.page_shape, cfg.dtype)
            if t.is_pinned:
                return PinnedHostPool(t, cfg.page_shape, cfg.dtype)
            return HostPool(t, cfg.page_shape, cfg.dtype)

        self.pools: list[DevicePool | HostPool | PinnedHostPool] = [
            make_pool(t) for t in cfg.hierarchy
        ]
        # pages start (unallocated) in the deepest tier, as in the paper's
        # everything-begins-on-NVM bring-up
        self.tier = np.full((cfg.n_pages,), cfg.hierarchy.deepest, np.int8)
        self.slot = np.full((cfg.n_pages,), NO_SLOT, np.int64)
        self.version = np.zeros((cfg.n_pages,), np.int64)
        # incremental dirty set (async memos validation): while an epoch
        # is open, every page whose version/tier/slot changes is recorded
        # here, so a commit validates in O(dirtied pages) instead of
        # re-reading the whole version array per planned page.  Tracking
        # is off outside an epoch — synchronous-only runs pay one branch.
        self._dirty_tracking = False
        self._dirty_pages: set[int] = set()
        # per-tier allocator geometry derived from each tier's own slots
        # (the monitor geometry in cfg.n_banks/n_slabs stays global)
        self.alloc = [SubBuddyAllocator(SubBuddyConfig(
            t.slots, *_tier_geometry(want_banks, want_slabs, t)))
            for t in cfg.hierarchy]
        # bytes moved per (src, dst) tier pair, for the balancer / figs;
        # _traffic_snap marks the last memos-pass boundary so spill/cascade
        # targeting can rank tiers by bandwidth headroom over the current
        # window (roll_traffic_window)
        self.traffic = {(i, j): 0 for i in range(self.n_tiers)
                        for j in range(self.n_tiers) if i != j}
        self._traffic_snap = dict(self.traffic)
        self.writes_to = {t: 0 for t in range(self.n_tiers)}
        self.reads_from = {t: 0 for t in range(self.n_tiers)}
        # per-tier NVM wear telemetry + Start-Gap leveling (host tiers with
        # wear_tracked set; lazy import — repro.nvm pulls in the cost model,
        # which sits next to this module in the core package)
        self.wear_by_tier: dict[int, object] = {}
        self.leveler_by_tier: dict[int, object] = {}
        for i in cfg.hierarchy.wear_tiers():
            from repro.nvm.leveling import StartGapLeveler
            from repro.nvm.wear import NvmWear
            spec = cfg.hierarchy[i]
            self.wear_by_tier[i] = NvmWear(spec.slots)
            if spec.wear_leveling:
                self.leveler_by_tier[i] = StartGapLeveler(
                    self.wear_by_tier[i], spec.gap_write_interval)
        # page integrity + bad-slot quarantine (armed only while the
        # global fault injector is — zero-cost dead branches otherwise)
        self.integrity = PageIntegrity(enabled=get_injector().enabled)
        self.quarantined: dict[int, set[int]] = {
            t: set() for t in range(self.n_tiers)}
        # pages unbound by a quarantine since the last drain; the serving
        # engine reads this back to fail the owning sequences cleanly
        self.quarantine_log: list[int] = []

    # -- two-tier compat surface ----------------------------------------------
    @property
    def fast_pool(self) -> jnp.ndarray:
        """Tier-0 device pool buffer (what the serving engine computes on)."""
        return self.pools[0].data

    @fast_pool.setter
    def fast_pool(self, value: jnp.ndarray) -> None:
        self.pools[0].data = value

    @property
    def _deepest_wear(self) -> int | None:
        wt = self.hierarchy.wear_tiers()
        return wt[-1] if wt else None

    @property
    def wear(self):
        """Deepest wear-tracked tier's tracker (two-tier compat alias)."""
        t = self._deepest_wear
        return None if t is None else self.wear_by_tier[t]

    @property
    def leveler(self):
        t = self._deepest_wear
        return self.leveler_by_tier.get(t) if t is not None else None

    @property
    def slow_pool(self) -> np.ndarray:
        """Deepest tier's raw pool array (compat; host tiers only)."""
        return self.pools[-1].data

    @property
    def slow_scale(self) -> np.ndarray | None:
        return self.pools[-1].scale

    # -- tier predicates -------------------------------------------------------
    def is_device_tier(self, tier: int) -> bool:
        return self.hierarchy[tier].is_device

    def is_pinned_tier(self, tier: int) -> bool:
        return self.hierarchy[tier].is_pinned

    def is_addressable_tier(self, tier: int) -> bool:
        """Device code can gather/scatter this tier's pool directly
        (device tiers and pinned-host tiers)."""
        return self.hierarchy[tier].is_device_addressable

    # -- dirty-set epochs (async memos validation) -----------------------------
    def begin_dirty_epoch(self) -> None:
        """Start recording pages whose plan-invalidating state changes:
        placement (tier/slot — allocate, release, moves) and external
        content writes (``write_page`` / ``bump_version``).  Opened when
        an async memos pass snapshots the store; the commit reads the set
        back and only those pages can be stale — the O(dirtied)
        replacement for replaying the whole version array.  Dispatch
        access charges are excluded by design: they account in-place
        appends that a commit-time migration re-reads anyway."""
        self._dirty_pages.clear()
        self._dirty_tracking = True

    def end_dirty_epoch(self) -> set[int]:
        """Stop recording and return the pages dirtied since
        :meth:`begin_dirty_epoch`."""
        self._dirty_tracking = False
        dirty, self._dirty_pages = self._dirty_pages, set()
        return dirty

    def _mark_dirty(self, pages) -> None:
        if self._dirty_tracking:
            self._dirty_pages.update(int(p) for p in np.atleast_1d(pages))

    def _mark_dirty_one(self, page: int) -> None:
        if self._dirty_tracking:
            self._dirty_pages.add(int(page))

    def bump_version(self, page: int) -> None:
        """Advance a page's version counter (the optimistic-migration
        dirty bit) through the store, so an open dirty epoch sees it.
        External writers (and conflict-injection test hooks) must use
        this instead of poking ``store.version`` directly."""
        self.version[page] += 1
        self._mark_dirty_one(page)

    # -- page lifecycle -----------------------------------------------------
    @property
    def page_nbytes(self) -> int:
        return int(np.prod(self.cfg.page_shape)) * jnp.dtype(self.cfg.dtype).itemsize

    def allocate(self, page: int, tier: int, color: int | None = None,
                 color_mask: int | None = None) -> bool:
        """Bind a logical page to a fresh slot in ``tier``."""
        assert self.slot[page] == NO_SLOT, f"page {page} already allocated"
        inj = get_injector()
        if inj.enabled and inj.maybe_alloc_fail(tier):
            return False               # injected pool-exhaustion pressure
        s = self.alloc[tier].alloc(0, color, color_mask)
        if s is None:
            return False
        self.tier[page] = tier
        self.slot[page] = s
        self._mark_dirty_one(page)
        return True

    def release(self, page: int) -> None:
        s = int(self.slot[page])
        if s != NO_SLOT:
            t = int(self.tier[page])
            self.alloc[t].free(s, 0)
            self.integrity.drop(t, [s])
            self.slot[page] = NO_SLOT
            self._mark_dirty_one(page)

    def quarantine_slot(self, tier: int, slot: int,
                        reason: str = "") -> bool:
        """Retire a failing slot: permanently withhold it from the tier's
        allocator, unbind any page living in it (recorded in
        ``quarantine_log`` so the serving engine can fail the owner
        cleanly), and drop its checksum.  Returns False if the slot was
        already quarantined or no longer allocated."""
        slot = int(slot)
        if slot in self.quarantined[tier]:
            return False
        if not self.alloc[tier].retire(slot):
            return False               # freed since detection: nothing to do
        self.quarantined[tier].add(slot)
        self.integrity.drop(tier, [slot])
        pages = np.nonzero((self.tier == tier) & (self.slot == slot))[0]
        for p in pages:
            self.slot[p] = NO_SLOT     # page is gone, not just cold
            self._mark_dirty_one(int(p))
            self.quarantine_log.append(int(p))
        from repro import obs
        from repro.faults.injector import note_recovered
        reg = obs.get_registry()
        reg.counter("faults.quarantined_slots",
                    "slots retired by quarantine").inc()
        note_recovered("quarantine")
        return True

    # -- data access ----------------------------------------------------------
    def write_page(self, page: int, value) -> None:
        t, s = int(self.tier[page]), int(self.slot[page])
        assert s != NO_SLOT
        if self.is_device_tier(t):
            self.pools[t].write_one(s, value)
        else:
            self._host_write(t, s, np.asarray(value, np.float32))
        self.bump_version(page)
        self.writes_to[t] += 1

    def read_page(self, page: int) -> np.ndarray:
        t, s = int(self.tier[page]), int(self.slot[page])
        assert s != NO_SLOT
        self.reads_from[t] += 1
        if self.is_device_tier(t):
            return self.pools[t].read_one(s)
        return self._host_read(t, s)

    # -- host-tier access (wear remap + accounting) ----------------------------
    def _phys(self, tier: int, slots: np.ndarray) -> np.ndarray:
        """Logical host-pool slots -> physical rows (wear-leveling remap)."""
        w = self.wear_by_tier.get(tier)
        return slots if w is None else w.phys(slots)

    def _account_host_writes(self, tier: int, phys: np.ndarray) -> None:
        """Charge wear counters and drive the tier's Start-Gap leveler
        after data has landed on the given physical rows."""
        w = self.wear_by_tier.get(tier)
        if w is None:
            return
        w.record_phys(phys)
        lv = self.leveler_by_tier.get(tier)
        if lv is not None:
            lv.note_writes(_LevelerView(self.pools[tier]),
                           np.asarray(phys).size)

    def note_leveling_writes(self, tier: int, n: int) -> None:
        """Drive ``tier``'s Start-Gap leveler for ``n`` demand writes that
        were charged elsewhere (the fused dispatch counts pinned-tier KV
        appends on device; the leveler itself only advances at dispatch
        boundaries, on the host)."""
        lv = self.leveler_by_tier.get(tier)
        if lv is not None and n:
            lv.note_writes(_LevelerView(self.pools[tier]), int(n))

    def _host_write(self, tier: int, slot: int, value: np.ndarray) -> None:
        w = self.wear_by_tier.get(tier)
        p = slot if w is None else w.phys_one(slot)
        self.pools[tier].write_one(p, value)
        self._account_host_writes(tier, np.asarray([p]))
        self.integrity.record(self, tier, [slot])

    def _host_read(self, tier: int, slot: int) -> np.ndarray:
        w = self.wear_by_tier.get(tier)
        p = slot if w is None else w.phys_one(slot)
        return self.pools[tier].read_one(p)

    # -- batched data access (the migration engine's bulk primitives) ----------
    def gather_device(self, tier: int, slots) -> jnp.ndarray:
        """Pack a device-addressable tier's (logical) slots into one
        contiguous jax staging buffer.  Pinned-host tiers translate
        through the wear remap and fuse dequantization into the gather."""
        if self.is_pinned_tier(tier):
            phys = self._phys(tier, np.asarray(slots, np.int64))
            return self.pools[tier].gather(phys)
        return self.pools[tier].gather(slots)

    def scatter_device(self, tier: int, slots, pages: jnp.ndarray) -> None:
        """pool[slots[i]] = pages[i] on a device-addressable tier.
        Pinned-host tiers go through the wear remap, fuse int8
        quantization into the same dispatch, and charge wear counters —
        the demotion commit is one program, not a numpy staging copy."""
        if self.is_pinned_tier(tier):
            phys = self._phys(tier, np.asarray(slots, np.int64))
            self.pools[tier].scatter(phys, pages)
            self._account_host_writes(tier, phys)
            self.integrity.record(self, tier, slots)
            return
        self.pools[tier].scatter(slots, pages)

    # tier-0 compat names (the serving hot path's pool primitives)
    def gather_fast(self, slots) -> jnp.ndarray:
        return self.gather_device(0, slots)

    def scatter_fast(self, slots, pages: jnp.ndarray) -> None:
        self.scatter_device(0, slots, pages)

    def host_read_batch(self, tier: int, slots: np.ndarray) -> np.ndarray:
        """[k, *page_shape] float32 view of a host tier's slots (vectorized
        dequantize for int8 soft-NVM tiers)."""
        phys = self._phys(tier, np.asarray(slots, np.int64))
        return self.pools[tier].read_batch(phys)

    def host_write_batch(self, tier: int, slots: np.ndarray,
                         values: np.ndarray) -> None:
        """pool[slots[i]] = values[i] on a host tier (bit-identical to the
        per-page path), charging wear where tracked."""
        phys = self._phys(tier, np.asarray(slots, np.int64))
        self.pools[tier].write_batch(phys, np.asarray(values, np.float32))
        self._account_host_writes(tier, phys)
        self.integrity.record(self, tier, slots)

    # deepest-tier compat names
    def slow_read_batch(self, slots: np.ndarray) -> np.ndarray:
        return self.host_read_batch(self.n_tiers - 1, slots)

    def slow_write_batch(self, slots: np.ndarray, values: np.ndarray) -> None:
        self.host_write_batch(self.n_tiers - 1, slots, values)

    def charge_fast_accesses(self, page_writes: np.ndarray,
                             n_reads: int) -> None:
        """Apply one decode dispatch's tier-0 access accounting in bulk:
        ``page_writes`` (int [n_pages], computed on device inside the fused
        step) bumps the per-page version counters (the dirty bit for
        optimistic migration) and the tier write counter; ``n_reads`` is the
        dispatch's total page-read count.  One vectorized add instead of a
        per-request Python loop per token.

        Deliberately does NOT mark the pages dirty for an open async-plan
        epoch: these are the dispatch's own in-place appends — the page
        never leaves its slot, and a commit-boundary migration reads the
        bytes fresh (``execute_plan`` stages at execute time), so the
        plan stays valid.  External writers go through ``write_page`` /
        ``bump_version``, which do mark."""
        page_writes = np.asarray(page_writes, np.int64)
        self.version += page_writes
        self.writes_to[0] += int(page_writes.sum())
        self.reads_from[0] += int(n_reads)

    def charge_accesses(self, page_writes: np.ndarray,
                        page_reads: np.ndarray) -> None:
        """Apply one dispatch's access accounting split by residency:
        per-page write/read counts (computed on device / closed-form on
        host) bump the version counters and each page's *current* tier's
        read/write counters — the pinned-serving dispatch touches both
        the tier-0 pool and the pinned deepest tier, so the charge can't
        assume tier 0 like ``charge_fast_accesses``.  Like that method,
        it does not dirty an open async-plan epoch — in-place dispatch
        appends never invalidate a pending plan."""
        page_writes = np.asarray(page_writes, np.int64)
        page_reads = np.asarray(page_reads, np.int64)
        self.version += page_writes
        for t in range(self.n_tiers):
            m = self.tier == t
            w = int(page_writes[m].sum())
            r = int(page_reads[m].sum())
            if w:
                self.writes_to[t] += w
            if r:
                self.reads_from[t] += r

    # -- bandwidth headroom (spill / cascade targeting) ------------------------
    def roll_traffic_window(self) -> None:
        """Mark a pass boundary for the per-tier inflow window."""
        self._traffic_snap = dict(self.traffic)

    def tier_inflow_bytes(self, tier: int) -> int:
        """Bytes that landed in ``tier`` since the last window roll."""
        return sum(self.traffic[(s, tier)] - self._traffic_snap[(s, tier)]
                   for s in range(self.n_tiers) if s != tier)

    def backing_tier_order(self, start: int = 1) -> list[int]:
        """Backing tiers ``start..deepest`` ordered by bandwidth headroom:
        tiers whose channel absorbed the smallest fraction of their
        ``MediumSpec.bandwidth_gbps`` over the current traffic window come
        first (unmodeled bandwidth = 0 counts as unconstrained), ties
        break toward the faster tier — which reduces to plain tier order
        for the default unmodeled hierarchies, so ``new_page`` cascades
        and bandwidth spills only re-route when a channel is actually
        saturated."""
        def utilization(t: int) -> float:
            bw = self.hierarchy[t].bandwidth_gbps
            if bw <= 0:
                return 0.0
            return self.tier_inflow_bytes(t) / (bw * 2**30)
        return sorted(range(start, self.n_tiers),
                      key=lambda t: (utilization(t), t))

    def commit_moves(self, pages: np.ndarray, dst_tier: int,
                     new_slots: np.ndarray) -> None:
        """Flip the page table for an executed bulk move: free the old slots
        (each page in its own source tier's allocator), bind the new ones,
        account per-pair traffic — one vectorized pass over the tier/slot
        arrays (the allocator free loop is host metadata only)."""
        pages = np.asarray(pages, np.int64)
        new_slots = np.asarray(new_slots, np.int64)
        if pages.size == 0:
            return
        src_tiers = self.tier[pages].copy()
        assert (src_tiers != dst_tier).all(), \
            "commit_moves: page already in the destination tier"
        for p, s in zip(pages, self.slot[pages]):
            self.alloc[int(self.tier[p])].free(int(s), 0)
            self.integrity.drop(int(self.tier[p]), [int(s)])
        self.tier[pages] = dst_tier
        self.slot[pages] = new_slots
        self._mark_dirty(pages)
        for t in np.unique(src_tiers):
            k = int((src_tiers == t).sum())
            self.traffic[(int(t), dst_tier)] += self.page_nbytes * k

    # -- migration primitive (single page, already-planned) --------------------
    def move_page(self, page: int, dst_tier: int, color: int | None = None,
                  color_mask: int | None = None) -> bool:
        """Synchronous ('locked CPU copy') single-page move between any
        two tiers."""
        src_tier = int(self.tier[page])
        if src_tier == dst_tier:
            return True
        if int(self.slot[page]) == NO_SLOT:
            return False                   # released page: nothing to move
        data = self.read_page(page)
        new_slot = self.alloc[dst_tier].alloc(0, color, color_mask)
        if new_slot is None and color is not None:
            # Algorithm 2 exhausted its slab walk: fall back to any color
            # rather than dropping the migration (capacity is the real bound).
            new_slot = self.alloc[dst_tier].alloc(0, None)
        if new_slot is None:
            return False
        old_slot = int(self.slot[page])
        if self.is_device_tier(dst_tier):
            self.pools[dst_tier].write_one(new_slot, data)
        else:
            self._host_write(dst_tier, new_slot, data)
        self.alloc[src_tier].free(old_slot, 0)
        self.integrity.drop(src_tier, [old_slot])
        self.tier[page] = dst_tier
        self.slot[page] = new_slot
        self._mark_dirty_one(page)
        self.traffic[(src_tier, dst_tier)] += self.page_nbytes
        return True

    def tier_used(self) -> list[int]:
        """Live page count per tier."""
        live = self.slot != NO_SLOT
        return [int(np.sum(self.tier[live] == t))
                for t in range(self.n_tiers)]

    def occupancy(self) -> dict:
        used = self.tier_used()
        out = {
            "fast_used": used[0], "fast_total": self.hierarchy[0].slots,
            "slow_used": used[-1],
            "slow_total": self.hierarchy[self.hierarchy.deepest].slots,
        }
        for i, spec in enumerate(self.hierarchy):
            out[f"t{i}_{spec.name.lower()}_used"] = used[i]
            out[f"t{i}_{spec.name.lower()}_total"] = spec.slots
        return out

    def publish_metrics(self, reg) -> None:
        """Publish per-tier occupancy / IO counters and per-(src, dst)
        migration traffic into an ``obs.MetricsRegistry``."""
        used = self.tier_used()
        for i, spec in enumerate(self.hierarchy):
            name = spec.name.lower()
            reg.gauge(f"store.t{i}_used",
                      f"live pages in tier {i} ({name})").set(used[i])
            reg.gauge(f"store.t{i}_slots",
                      f"capacity of tier {i} ({name})").set(spec.slots)
            reg.gauge(f"store.t{i}_reads",
                      f"page reads served from tier {i}").set(
                          self.reads_from[i])
            reg.gauge(f"store.t{i}_writes",
                      f"page writes landed in tier {i}").set(
                          self.writes_to[i])
        for (s, d), b in self.traffic.items():
            if b:      # sparse: most (src, dst) pairs never carry traffic
                reg.gauge(f"store.migration_bytes_t{s}_t{d}",
                          f"bytes migrated tier {s} -> tier {d}").set(b)
