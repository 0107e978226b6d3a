"""Paged KV cache on top of the memos TierStore.

Logical page = one ``page_size``-token span of one sequence, payload
[L, 2(K/V), page, Hkv, Dh] across all layers (pages migrate between the
hierarchy's tiers as a unit, like the OS paper's 4 KB pages).  The tier
layout comes from a :class:`~repro.core.hierarchy.MemoryHierarchy` —
two-tier HBM/host by default, or any deeper stack (e.g. the
HBM -> DRAM-sim -> NVM-sim demo) via ``PagedKVConfig.hierarchy``.  Tier 0
is the serving tier: block tables map (sequence, span) -> logical page ->
tier-0 pool slot for the paged_attention kernel, so a page must be
promoted to tier 0 before it can be attended to.

Each tier's slots are placed by its own color-aware sub-buddy allocator
(bank = pool-slot stripe = HBM-controller analogue).

SysMon charging: every decode step reads all pages of active sequences
and writes the tail page — the exact access stream (no sampling error),
DESIGN.md Sec. 2.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax.numpy as jnp
import numpy as np

from repro.core.hierarchy import MemoryHierarchy
from repro.core.tiers import NO_SLOT, StoreConfig, TierStore

SERVE_TIER = 0   # compute only ever reads tier 0 (the fastest device pool)


@dataclass
class PagedKVConfig:
    n_layers: int
    n_kv_heads: int
    head_dim: int
    page_size: int = 16
    fast_slots: int = 64          # HBM pool capacity (two-tier default)
    slow_slots: int = 512         # host pool capacity (two-tier default)
    dtype: object = jnp.float32
    # full tier stack; None -> MemoryHierarchy.two_tier(fast, slow)
    hierarchy: MemoryHierarchy | None = None
    # logical page count; None -> total backing capacity (tiers 1..deepest)
    n_pages: int | None = None


class PagedKVCache:
    def __init__(self, cfg: PagedKVConfig):
        self.cfg = cfg
        hier = cfg.hierarchy or MemoryHierarchy.two_tier(cfg.fast_slots,
                                                         cfg.slow_slots)
        n_pages = (cfg.n_pages if cfg.n_pages is not None
                   else sum(t.slots for t in hier.tiers[1:]))
        shape = (cfg.n_layers, 2, cfg.page_size, cfg.n_kv_heads, cfg.head_dim)
        self.store = TierStore(StoreConfig(
            n_pages=n_pages, page_shape=shape, hierarchy=hier,
            dtype=cfg.dtype))
        self.n_pages = n_pages
        self._free_ids = list(range(n_pages - 1, -1, -1))

    @property
    def pinned_tier(self) -> int | None:
        """The deepest tier when it is a pinned-host pool (addressable
        from device code, so the fused dispatch can serve KV out of it
        and append to it); None otherwise."""
        deepest = self.store.hierarchy.deepest
        return deepest if self.store.hierarchy[deepest].is_pinned else None

    # -- logical page lifecycle ------------------------------------------------
    def new_page(self, tier: int = SERVE_TIER) -> int | None:
        """Bind a fresh logical page, preferring ``tier`` and cascading
        down the hierarchy when a pool is full (HBM full -> next tier,
        promote later).  The backing tiers are tried in bandwidth-headroom
        order — per-``MediumSpec`` peak bandwidth against the (src, dst)
        traffic counters' current window — so a saturated middle channel
        is skipped; with unmodeled bandwidths this reduces to plain tier
        order."""
        if not self._free_ids:
            return None
        pid = self._free_ids.pop()
        order = [tier] + self.store.backing_tier_order(start=tier + 1)
        for t in order:
            if self.store.allocate(pid, t):
                return pid
        self._free_ids.append(pid)
        return None

    def free_page(self, pid: int) -> None:
        self.store.release(pid)
        self._free_ids.append(pid)

    def is_resident(self, pid: int) -> bool:
        return int(self.store.tier[pid]) == SERVE_TIER and \
            int(self.store.slot[pid]) != NO_SLOT

    def fast_slot(self, pid: int) -> int:
        assert self.is_resident(pid), f"page {pid} not HBM-resident"
        return int(self.store.slot[pid])

    def resident_mask(self, pids) -> np.ndarray:
        """bool [k]: which of ``pids`` are live in the serving (tier-0)
        pool."""
        pids = np.asarray(pids, np.int64)
        return (self.store.tier[pids] == SERVE_TIER) & \
            (self.store.slot[pids] != NO_SLOT)

    def servable_mask(self, pids) -> np.ndarray:
        """bool [k]: which of ``pids`` the fused dispatch can attend to —
        tier-0 residents plus, when the deepest tier is pinned-host,
        residents of that pool (served in place, no promotion needed)."""
        pids = np.asarray(pids, np.int64)
        live = self.store.slot[pids] != NO_SLOT
        ok = self.store.tier[pids] == SERVE_TIER
        pt = self.pinned_tier
        if pt is not None:
            ok = ok | (self.store.tier[pids] == pt)
        return ok & live

    def fast_slots_of(self, pids) -> np.ndarray:
        """int32 [k] tier-0 pool slots for a batch of logical pages — the
        vectorized block-table fill (all pages must be HBM-resident)."""
        pids = np.asarray(pids, np.int64)
        assert self.resident_mask(pids).all(), \
            f"non-resident pages in {pids.tolist()}"
        return self.store.slot[pids].astype(np.int32)

    def fill_tables(self, pages_rows: list[list[int]],
                    n_cols: int) -> tuple[np.ndarray, np.ndarray]:
        """(page_tables, block_tables) int32 [B, n_cols] for a batch of
        sequences' logical page lists: logical ids feed SysMon charging,
        tier-0 pool slots feed the paged_attention kernel.  One vectorized
        page-table lookup per row (no per-page loops); unused columns are
        zero and must be masked by position/length downstream."""
        B = len(pages_rows)
        page_tables = np.zeros((B, n_cols), np.int32)
        block_tables = np.zeros((B, n_cols), np.int32)
        for i, pg in enumerate(pages_rows):
            pg = pg[:n_cols]
            page_tables[i, :len(pg)] = pg
            block_tables[i, :len(pg)] = self.fast_slots_of(pg)
        return page_tables, block_tables

    def fill_tables_mixed(self, pages_rows: list[list[int]], n_cols: int
                          ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(page_tables, block_tables, pool_sel) for the dual-pool fused
        dispatch: every page must be *servable* (tier 0 or the pinned
        deepest tier).  ``block_tables`` holds the slot in the page's own
        pool — tier-0 pool slot, or the pinned pool's **logical** slot:
        the dispatch translates pinned slots through the wear-leveling
        remap it carries in its scan, so in-dispatch Start-Gap advances
        keep addressing the right rows mid-scan (host pre-translation
        would go stale after the first in-scan swap); ``pool_sel`` is 1
        where the page is pinned-resident."""
        pt = self.pinned_tier
        assert pt is not None, "fill_tables_mixed needs a pinned deepest tier"
        store = self.store
        B = len(pages_rows)
        page_tables = np.zeros((B, n_cols), np.int32)
        block_tables = np.zeros((B, n_cols), np.int32)
        pool_sel = np.zeros((B, n_cols), np.int32)
        for i, pg in enumerate(pages_rows):
            pg = np.asarray(pg[:n_cols], np.int64)
            assert self.servable_mask(pg).all(), \
                f"non-servable pages in {pg.tolist()}"
            sel = (store.tier[pg] == pt).astype(np.int32)
            page_tables[i, :len(pg)] = pg
            block_tables[i, :len(pg)] = store.slot[pg].astype(np.int32)
            pool_sel[i, :len(pg)] = sel
        return page_tables, block_tables, pool_sel

    # -- data access -------------------------------------------------------------
    def write_token_kv(self, pid: int, layer_kv: jnp.ndarray,
                       offset: int) -> None:
        """layer_kv: [L, 2, Hkv, Dh] for one token at in-page ``offset``.
        Device-tier path writes straight into the pool slot; host tiers
        read-modify-write the page.  Bumps the version (the dirty bit for
        optimistic migration)."""
        t, slot = int(self.store.tier[pid]), int(self.store.slot[pid])
        assert slot != NO_SLOT
        if self.store.is_device_tier(t):
            pool = self.store.pools[t]
            pool.data = pool.data.at[slot, :, :, offset].set(
                layer_kv.astype(pool.dtype))
        elif self.store.is_pinned_tier(t):
            # pinned pool: one jitted in-place token write (no host
            # read-modify-write round trip), charged to the wear remap
            wear = self.store.wear_by_tier.get(t)
            phys = slot if wear is None else wear.phys_one(slot)
            pool = self.store.pools[t]
            assert not pool.quantized, \
                "token-granular appends need a lossless pinned pool"
            pool.write_token(phys, offset, layer_kv)
            self.store._account_host_writes(t, np.asarray([phys]))
            self.store.integrity.record(self.store, t, [slot])
        else:
            page = self.store._host_read(t, slot)
            page[:, :, offset] = np.asarray(layer_kv, np.float32)
            self.store._host_write(t, slot, page)
        self.store.writes_to[t] += 1
        self.store.bump_version(pid)

    def occupancy(self) -> dict:
        return self.store.occupancy()
