"""Migration engines (paper Sec. 6.3, Fig. 10 step 4): plan/execute split,
generic over the tiers of a :class:`~repro.core.hierarchy.MemoryHierarchy`.

Migration is two phases with a narrow interface between them:

  * **plan** (host) — the memos pass walks the hotness list, picks each
    page's destination slot per Algorithm 2 (coldest bank, then coldest
    non-reserved slab; reserved-slab routing for Thrashing/Rarely-touched
    pages), and reserves the slots in the destination tier's sub-buddy
    allocator.  The output is a ``MigrationPlan``: parallel arrays of
    (page, src tier, src slot, dst slot) plus a destination tier and a
    per-page version snapshot for the dirty check.  One plan moves pages
    from *any* mix of source tiers into one destination tier.
  * **execute** (device) — the plan is applied as bulk data movement per
    (source, destination) residency pair:

      - device -> device: Pallas ``page_gather`` out of the source pool,
        ``page_scatter`` into the destination pool — the whole move stays
        on-accelerator (the HBM -> DRAM-sim path);
      - device -> host: gather into contiguous device staging, then
        chunked double-buffered async device->host copies;
      - host -> device: staged host->device uploads + donated-pool scatter;
      - host -> host: one vectorized numpy copy.

Two engines implement execute:

  * ``MigrationEngine`` — the numpy **reference** implementation: a
    host-side per-page copy loop.  Retained as the parity oracle
    (tests/test_batched_migration.py) and as the slow baseline in
    benchmarks/migration_bw.py.
  * ``BatchedMigrationEngine`` — the **device-resident** fast path
    described above; one compiled dispatch per chunk instead of one per
    page.

Both engines expose the same two paths, matching the paper:

  * ``locked``     — synchronous copy, commit unconditionally; used for
                     small batches of hot/WD pages moving toward tier 0.
  * ``optimistic`` — unlocked DMA-style copy: snapshot per-page version
                     counters, copy without blocking writers, commit only
                     pages whose version did not advance mid-copy (the
                     paper's post-hoc dirty-bit check), retry dirtied
                     pages iteratively.  Used for bulk cold/RD demotions,
                     which are rarely dirtied mid-copy.

The engines make identical allocator calls in identical order, so for the
same inputs they produce identical tier/slot tables and pool contents —
that equivalence is what the parity suite pins down.  (When one plan
mixes several *source* tiers the batched engine moves them grouped by
source tier; logical state stays identical, only the physical write order
onto wear-leveled pools may differ from the reference's interleaving.)
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, Iterable

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.faults.errors import TransientMigrationFault
from repro.faults.injector import get_injector, note_recovered

from . import placement
from .tiers import NO_SLOT, TierStore, _pad_idx_np, _pad_pages, _pow2

# Bump when engine semantics / data layout change; recorded in benchmark
# result JSONs so trajectory comparisons across machines and revisions
# aren't apples-to-oranges.
ENGINE_VERSION = "4.1"  # 1.x: per-page reference loop; 2.x: batched bulk
                        # mover + NVM wear accounting on the slow path;
                        # 3.x: N-tier plans (per-page src tier, device<->
                        # device moves); 4.x: replayable reservations
                        # (async plan/commit) + pinned-host tier routing;
                        # 4.1: page-granular async commits (clean subset
                        # executes, only dirtied pages degrade) + O(1)
                        # allocator adoption on quiet tiers


def bench_env() -> dict:
    """Execution-environment record shared by every benchmark result JSON."""
    return {
        "jax_version": jax.__version__,
        "backend": jax.default_backend(),
        "engine_version": ENGINE_VERSION,
    }


@dataclass
class MigrationStats:
    migrated: int = 0
    dirty_discards: int = 0
    retries: int = 0
    retries_exhausted: int = 0    # pages still dirty at the retry cap
    failed: int = 0               # pages dropped by exhausted move faults
    bytes_moved: int = 0
    to_fast: int = 0              # moves into tier 0
    to_slow: int = 0              # moves into any slower tier
    by_pair: dict = field(default_factory=dict)   # (src, dst) -> pages moved

    def note_move(self, src_tier: int, dst_tier: int, n: int = 1) -> None:
        if n:
            key = (int(src_tier), int(dst_tier))
            self.by_pair[key] = self.by_pair.get(key, 0) + n

    def merge(self, other: "MigrationStats") -> None:
        self.migrated += other.migrated
        self.dirty_discards += other.dirty_discards
        self.retries += other.retries
        self.retries_exhausted += other.retries_exhausted
        self.failed += other.failed
        self.bytes_moved += other.bytes_moved
        self.to_fast += other.to_fast
        self.to_slow += other.to_slow
        for k, v in other.by_pair.items():
            self.by_pair[k] = self.by_pair.get(k, 0) + v

    def to_dict(self) -> dict:
        """JSON-safe form: the (src, dst) tuple keys of ``by_pair``
        serialize as ``"src->dst"`` strings."""
        return {
            "migrated": self.migrated,
            "dirty_discards": self.dirty_discards,
            "retries": self.retries,
            "retries_exhausted": self.retries_exhausted,
            "failed": self.failed,
            "bytes_moved": self.bytes_moved,
            "to_fast": self.to_fast,
            "to_slow": self.to_slow,
            "by_pair": {f"{s}->{d}": n
                        for (s, d), n in sorted(self.by_pair.items())},
        }

    @classmethod
    def from_dict(cls, d: dict) -> "MigrationStats":
        by_pair = {}
        for k, n in d.get("by_pair", {}).items():
            s, _, dst = k.partition("->")
            by_pair[(int(s), int(dst))] = int(n)
        return cls(
            migrated=int(d.get("migrated", 0)),
            dirty_discards=int(d.get("dirty_discards", 0)),
            retries=int(d.get("retries", 0)),
            retries_exhausted=int(d.get("retries_exhausted", 0)),
            failed=int(d.get("failed", 0)),
            bytes_moved=int(d.get("bytes_moved", 0)),
            to_fast=int(d.get("to_fast", 0)),
            to_slow=int(d.get("to_slow", 0)),
            by_pair=by_pair,
        )


# =============================================================================
# slot targeting (Algorithm 2) — shared by both engines
# =============================================================================

def target_color(store: TierStore, dst_tier: int,
                 bank_freq: np.ndarray | None,
                 slab_freq: np.ndarray | None,
                 reuse_class: int | None = None) -> tuple[int | None, int | None]:
    """color = bank*n_slabs + slab, per Algorithm 2 + reserved-slab rules."""
    cfg = store.alloc[dst_tier].cfg
    if bank_freq is None or slab_freq is None:
        return None, None
    forced_slab = (placement.slab_for_reuse_class(reuse_class)
                   if reuse_class is not None else None)

    # fold the monitor's bank/slab frequency space onto the allocator's
    # (the monitor tracks logical banks = device shards, which may be a
    # different cardinality from the slot pool's color geometry)
    def fold(freq: np.ndarray, n: int) -> np.ndarray:
        out = np.zeros(n, dtype=np.float64)
        for i, v in enumerate(np.asarray(freq)):
            out[i % n] += v
        return out

    bfreq = fold(bank_freq, cfg.n_banks)
    sfreq = fold(slab_freq, cfg.n_slabs)

    def rows_free(bank: int, slab: int) -> bool:
        # optimistic probe; the allocator falls back to any color when
        # the exact color is exhausted (see TierStore.move_page)
        return True

    if forced_slab is not None:
        bank = int(np.argmin(bfreq))
        slab = forced_slab % cfg.n_slabs
        return bank * cfg.n_slabs + slab, cfg.n_colors - 1
    reserved = tuple(r for r in (placement.RESERVED_THRASH_SLAB,
                                 placement.RESERVED_RARE_SLAB)
                     if r < cfg.n_slabs) if cfg.n_slabs > 2 else ()
    got = placement.coldest_bank_and_slab(bfreq, sfreq, rows_free,
                                          reserved=reserved)
    if got is None:
        return None, None
    bank, slab = got
    return bank * cfg.n_slabs + slab, cfg.n_colors - 1


def _alloc_target_slot_rec(store, dst_tier: int,
                           bank_freq: np.ndarray | None,
                           slab_freq: np.ndarray | None,
                           reuse_class: int | None
                           ) -> tuple[int | None, int, int]:
    """Reserve one destination slot per Algorithm 2, falling back to any
    color when the targeted slab walk is exhausted (capacity is the real
    bound, not color).  Returns (slot, color, mask) where color/mask
    record the allocator call that actually produced the slot (-1 = any
    color) — the asynchronous commit replays exactly these calls against
    the live allocator and treats any divergence as a plan conflict."""
    color, mask = target_color(store, dst_tier, bank_freq, slab_freq,
                               reuse_class)
    slot = store.alloc[dst_tier].alloc(0, color, mask)
    if slot is not None:
        return slot, (-1 if color is None else int(color)), \
            (-1 if mask is None else int(mask))
    if color is not None:
        slot = store.alloc[dst_tier].alloc(0, None)
    return slot, -1, -1


def _alloc_target_slot(store, dst_tier: int,
                       bank_freq: np.ndarray | None,
                       slab_freq: np.ndarray | None,
                       reuse_class: int | None) -> int | None:
    return _alloc_target_slot_rec(store, dst_tier, bank_freq, slab_freq,
                                  reuse_class)[0]


# =============================================================================
# plans
# =============================================================================

@dataclass
class MigrationPlan:
    """A reserved, executable bulk move into one destination tier.

    ``pages[i]`` moves from ``src_tiers[i]`` / ``src_slots[i]`` ->
    ``dst_slots[i]`` (reserved in ``dst_tier``).  Source tiers may be
    mixed within one plan.  ``trivial`` counts pages that were requested
    but already sit in ``dst_tier`` (the locked path reports them as
    migrated without moving data, like the reference).

    ``colors``/``masks`` record the Algorithm-2 allocator call that
    reserved each slot (-1 = any color): a plan produced against a
    :class:`StoreView` snapshot has its reservations *simulated* on
    cloned allocators, and ``commit_reservations`` lands them on the
    live store at commit time (clone adoption when the allocator saw no
    interleaved call, per-call slot-patching replay otherwise).  ``reads_by_tier``
    carries the staging read charge for optimistic plans (the unlocked
    copy stages every pending page, including ones later dropped for
    capacity, so the async commit charges the same reads the synchronous
    path would).
    """
    dst_tier: int
    pages: np.ndarray       # int64 [k]
    src_tiers: np.ndarray   # int8  [k]
    src_slots: np.ndarray   # int64 [k]
    dst_slots: np.ndarray   # int64 [k]
    trivial: int = 0
    colors: np.ndarray | None = None   # int64 [k], -1 = any
    masks: np.ndarray | None = None    # int64 [k], -1 = full mask
    reads_by_tier: dict = field(default_factory=dict)

    def __len__(self) -> int:
        return int(self.pages.size)


def plan_locked(store: TierStore, pages: Iterable[int], dst_tier: int,
                bank_freq: np.ndarray | None = None,
                slab_freq: np.ndarray | None = None,
                reuse_class: np.ndarray | None = None) -> MigrationPlan:
    """Phase 1 for the locked path: reserve destination slots for every
    movable page, in hotness-list order (allocator call sequence identical
    to the reference engine's, so both engines land pages in the same
    slots)."""
    bank_freq = None if bank_freq is None else np.array(bank_freq)
    mv_pages: list[int] = []
    src_tiers: list[int] = []
    src_slots: list[int] = []
    dst_slots: list[int] = []
    colors: list[int] = []
    masks: list[int] = []
    planned: dict[int, int] = {}            # page -> reserved dst slot
    trivial = 0

    def account(slot: int) -> None:
        # account the move so subsequent picks spread across banks
        if bank_freq is not None:
            cfg = store.alloc[dst_tier].cfg
            bank_freq[cfg.bank_of(slot) % len(bank_freq)] += 1

    for p in pages:
        p = int(p)
        cur_slot = planned.get(p, int(store.slot[p]))
        if int(store.tier[p]) == dst_tier or p in planned:
            # already there (or already planned this batch): the reference
            # reports these as migrated without moving data
            trivial += 1
            account(cur_slot)
            continue
        if cur_slot == NO_SLOT:
            continue                        # released page: nothing to move
        rc = None if reuse_class is None else int(reuse_class[p])
        new_slot, color, mask = _alloc_target_slot_rec(
            store, dst_tier, bank_freq, slab_freq, rc)
        if new_slot is None:
            continue
        mv_pages.append(p)
        src_tiers.append(int(store.tier[p]))
        src_slots.append(cur_slot)
        dst_slots.append(new_slot)
        colors.append(color)
        masks.append(mask)
        planned[p] = new_slot
        account(new_slot)
    return MigrationPlan(
        dst_tier=dst_tier,
        pages=np.asarray(mv_pages, np.int64),
        src_tiers=np.asarray(src_tiers, np.int8),
        src_slots=np.asarray(src_slots, np.int64),
        dst_slots=np.asarray(dst_slots, np.int64),
        trivial=trivial,
        colors=np.asarray(colors, np.int64),
        masks=np.asarray(masks, np.int64),
    )


def plan_optimistic(store, pages: Iterable[int], dst_tier: int,
                    bank_freq: np.ndarray | None = None,
                    slab_freq: np.ndarray | None = None,
                    reuse_class: np.ndarray | None = None) -> MigrationPlan:
    """Phase 1 for the optimistic path: the reservation sequence of one
    clean ``migrate_optimistic`` attempt (dedupe, skip already-there /
    released pages, one Algorithm-2 allocator call per page in list
    order, *no* bank-frequency accounting between picks) without touching
    any data.  Run against a :class:`StoreView` this simulates the whole
    demotion commit on the plan worker; the version check that the
    synchronous path does after staging becomes the commit-time snapshot
    validation."""
    pending = [int(p) for p in dict.fromkeys(int(p) for p in pages)
               if int(store.tier[p]) != dst_tier
               and int(store.slot[p]) != NO_SLOT]
    bank_freq = None if bank_freq is None else np.array(bank_freq)
    mv_pages: list[int] = []
    src_tiers: list[int] = []
    src_slots: list[int] = []
    dst_slots: list[int] = []
    colors: list[int] = []
    masks: list[int] = []
    reads_by_tier: dict[int, int] = {}
    for p in pending:
        # the unlocked copy stages every pending page before the dirty
        # check — mirror its read charge even for pages dropped below
        t = int(store.tier[p])
        reads_by_tier[t] = reads_by_tier.get(t, 0) + 1
    for p in pending:
        rc = None if reuse_class is None else int(reuse_class[p])
        new_slot, color, mask = _alloc_target_slot_rec(
            store, dst_tier, bank_freq, slab_freq, rc)
        if new_slot is None:
            continue          # capacity exhausted: drop, like the engines
        mv_pages.append(p)
        src_tiers.append(int(store.tier[p]))
        src_slots.append(int(store.slot[p]))
        dst_slots.append(new_slot)
        colors.append(color)
        masks.append(mask)
    return MigrationPlan(
        dst_tier=dst_tier,
        pages=np.asarray(mv_pages, np.int64),
        src_tiers=np.asarray(src_tiers, np.int8),
        src_slots=np.asarray(src_slots, np.int64),
        dst_slots=np.asarray(dst_slots, np.int64),
        trivial=0,
        colors=np.asarray(colors, np.int64),
        masks=np.asarray(masks, np.int64),
        reads_by_tier=reads_by_tier,
    )


class StoreView:
    """Immutable-world facade for the asynchronous plan phase.

    Snapshots the placement-visible store state (page table, version
    counters, cloned per-tier allocators) at a dispatch boundary; the
    plan worker runs ``plan_locked`` / ``plan_optimistic`` against it —
    they only touch ``tier``/``slot``/``alloc`` — so Algorithm-2 slot
    targeting simulates its reservations off-thread while the next
    dispatch runs.  Creating the view also records each tier allocator's
    generation counter and opens the store's dirty-page epoch: the commit
    validates per page against the epoch's dirty set (O(dirtied pages))
    and adopts any clone whose tier saw no interleaved allocator call
    (O(1)) instead of replaying every reservation."""

    def __init__(self, store: TierStore):
        self.tier = store.tier.copy()
        self.slot = store.slot.copy()
        self.version = store.version.copy()
        self.alloc = [a.clone() for a in store.alloc]
        self.alloc_gen = [a.gen for a in store.alloc]
        self.hierarchy = store.hierarchy
        self.n_tiers = store.n_tiers
        store.begin_dirty_epoch()


def _group_decision(store, decision: placement.PlacementDecision
                    ) -> tuple[dict, dict]:
    """(promotions, demotions) per destination tier, in hotness-list
    order — THE grouping both ``execute_decision`` and ``plan_decision``
    must share: the async commit's every-page-lands-in-the-same-slot
    guarantee holds only while their allocator call order is identical."""
    cur = store.tier
    tgt = decision.target_tier
    promos = {t: [] for t in range(store.n_tiers)}
    demos = {t: [] for t in range(store.n_tiers)}
    for p in decision.hotness_list:
        src, dst = int(cur[p]), int(tgt[p])
        if dst == src:
            continue
        (promos if dst < src else demos)[dst].append(int(p))
    return promos, demos


def plan_decision(store, decision: placement.PlacementDecision,
                  bank_freq: np.ndarray | None = None,
                  slab_freq: np.ndarray | None = None,
                  reuse_class: np.ndarray | None = None) -> list[MigrationPlan]:
    """Reserve every migration of a ``PlacementDecision`` without moving
    data: the same destination grouping and allocator call order as
    ``execute_decision`` (promotions per dst tier shallowest-first via
    the locked sequence, then demotions via the optimistic sequence), so
    a conflict-free commit lands every page in exactly the slot the
    synchronous pass would have picked.  ``store`` may be a live
    ``TierStore`` or a :class:`StoreView` snapshot."""
    n_tiers = store.n_tiers
    promos, demos = _group_decision(store, decision)
    plans: list[MigrationPlan] = []
    for dst in range(n_tiers):
        if promos[dst]:
            plans.append(plan_locked(store, promos[dst], dst, bank_freq,
                                     slab_freq, reuse_class))
    for dst in range(n_tiers):
        if demos[dst]:
            plans.append(plan_optimistic(store, demos[dst], dst, bank_freq,
                                         slab_freq, reuse_class))
    return plans


def _replay_calls(store: TierStore, plan: MigrationPlan) -> np.ndarray:
    """Re-issue one plan's recorded allocator calls on the live store, in
    order.  Interleaved allocator activity (tail-page provisioning,
    promotion frees) means the live free lists no longer match the
    snapshot clones, so a call may land on a *different* slot than the
    plan simulated — that is not a conflict: the page itself is still
    clean, and the slot actually obtained is exactly what a synchronous
    pass planning at this boundary would have taken, so the plan is
    patched to it in place.  Only a capacity failure (the tier is full
    even after the any-color fallback, mirroring the planners) drops a
    reservation.  Returns the bool landed-mask."""
    assert plan.colors is not None and plan.masks is not None, \
        "replay needs a plan with recorded allocator calls"
    ok = np.zeros(len(plan), bool)
    for i in range(len(plan)):
        c, m = int(plan.colors[i]), int(plan.masks[i])
        s = store.alloc[plan.dst_tier].alloc(
            0, None if c < 0 else c, None if m < 0 else m)
        if s is None and c >= 0:
            s = store.alloc[plan.dst_tier].alloc(0, None)
        if s is None:
            continue
        plan.dst_slots[i] = s
        ok[i] = True
    return ok


def commit_reservations(store: TierStore, view: StoreView,
                        plans: list[MigrationPlan]) -> list[np.ndarray]:
    """Make the live allocators hold each plan's reservations; returns
    one bool landed-mask per plan (False = no capacity left for that
    page at commit time).

    Fast path: a destination tier whose live generation counter still
    equals the snapshot's saw *no* allocator call during the dispatch, so
    the view's clone — which already holds every simulated reservation —
    simply becomes the live allocator (O(1), no per-call replay, slots
    land exactly as simulated).  Tiers with interleaved activity (e.g.
    tier 0 tail-page provisioning) fall back to per-call replay, which
    patches each reservation to the slot the live allocator actually
    hands out."""
    landed = [np.zeros(len(pl), bool) for pl in plans]
    by_tier: dict[int, list[int]] = {}
    for i, pl in enumerate(plans):
        by_tier.setdefault(pl.dst_tier, []).append(i)
    for t, idxs in by_tier.items():
        if store.alloc[t].gen == view.alloc_gen[t]:
            store.alloc[t] = view.alloc[t]
            for i in idxs:
                landed[i][:] = True
        else:
            for i in idxs:        # plan order == simulation order
                landed[i] = _replay_calls(store, plans[i])
    return landed


def subset_plan(plan: MigrationPlan, keep: np.ndarray) -> MigrationPlan:
    """The sub-plan of ``plan`` restricted to the kept pages (bool mask).
    ``trivial`` and ``reads_by_tier`` carry over whole: trivial pages
    were never moving, and the optimistic staging read charge covers
    every *pending* page — the synchronous unlocked copy stages dirtied
    pages too before discarding them."""
    keep = np.asarray(keep, bool)
    if keep.all():
        return plan
    return MigrationPlan(
        dst_tier=plan.dst_tier,
        pages=plan.pages[keep],
        src_tiers=plan.src_tiers[keep],
        src_slots=plan.src_slots[keep],
        dst_slots=plan.dst_slots[keep],
        trivial=plan.trivial,
        colors=None if plan.colors is None else plan.colors[keep],
        masks=None if plan.masks is None else plan.masks[keep],
        reads_by_tier=plan.reads_by_tier,
    )


def execute_decision(engine, decision: placement.PlacementDecision,
                     bank_freq: np.ndarray | None = None,
                     slab_freq: np.ndarray | None = None,
                     reuse_class: np.ndarray | None = None) -> MigrationStats:
    """Direction routing shared by both engines (Sec. 6.3 observed
    asymmetry): promotions — moves toward a faster tier, hot/WD pages —
    take the locked path (small, must be consistent *now*); demotions —
    bulk cold/RD moves toward slower tiers — take the optimistic DMA
    path.  Pages are grouped per destination tier (shallowest first, in
    hotness-list order within each group) so both engines make identical
    allocator calls in identical order."""
    st = MigrationStats()
    n_tiers = engine.store.n_tiers
    promos, demos = _group_decision(engine.store, decision)
    for dst in range(n_tiers):
        if promos[dst]:
            st.merge(engine.migrate_locked(promos[dst], dst, bank_freq,
                                           slab_freq, reuse_class))
    for dst in range(n_tiers):
        if demos[dst]:
            st.merge(engine.migrate_optimistic(demos[dst], dst, bank_freq,
                                               slab_freq, reuse_class))
    return st


def _classify(st: MigrationStats, dst_tier: int, n: int) -> None:
    """Two-tier compat stat buckets: moves into tier 0 count as to_fast,
    everything else as to_slow."""
    if dst_tier == 0:
        st.to_fast += n
    else:
        st.to_slow += n


def _note_retries_exhausted(st: MigrationStats, n: int) -> None:
    """Pages still dirty when the optimistic retry cap hit: dropped this
    pass (a later pass re-plans them) rather than livelocking the loop."""
    if n:
        st.retries_exhausted += n
        obs.get_registry().counter(
            "migrate.retries_exhausted",
            "pages dropped at the optimistic dirty-retry cap").inc(n)


# =============================================================================
# reference engine (numpy per-page loop) — the parity oracle
# =============================================================================

class MigrationEngine:
    def __init__(self, store: TierStore, *, max_retries: int = 3,
                 retry_backoff_s: float = 1e-3):
        self.store = store
        self.max_retries = max_retries
        self.retry_backoff_s = retry_backoff_s
        self.stats = MigrationStats()

    def _target_color(self, dst_tier: int, bank_freq: np.ndarray | None,
                      slab_freq: np.ndarray | None,
                      reuse_class: int | None = None) -> tuple[int | None, int | None]:
        return target_color(self.store, dst_tier, bank_freq, slab_freq,
                            reuse_class)

    # -- locked path -----------------------------------------------------------
    def migrate_locked(self, pages: Iterable[int], dst_tier: int,
                       bank_freq: np.ndarray | None = None,
                       slab_freq: np.ndarray | None = None,
                       reuse_class: np.ndarray | None = None) -> MigrationStats:
        st = MigrationStats()
        bank_freq = None if bank_freq is None else np.array(bank_freq)
        for p in pages:
            src_tier = int(self.store.tier[p])
            rc = None if reuse_class is None else int(reuse_class[p])
            color, mask = self._target_color(dst_tier, bank_freq, slab_freq, rc)
            ok = self.store.move_page(int(p), dst_tier, color, mask)
            if ok:
                st.migrated += 1
                st.bytes_moved += self.store.page_nbytes
                _classify(st, dst_tier, 1)
                if src_tier != dst_tier:       # trivial moves shift no bytes
                    st.note_move(src_tier, dst_tier)
                if bank_freq is not None:
                    # account the move so subsequent picks spread across banks
                    cfg = self.store.alloc[dst_tier].cfg
                    b = cfg.bank_of(int(self.store.slot[p])) % len(bank_freq)
                    bank_freq[b] += 1
        self.stats.merge(st)
        return st

    # -- optimistic (unlocked DMA) path ---------------------------------------
    def migrate_optimistic(
        self, pages: Iterable[int], dst_tier: int,
        bank_freq: np.ndarray | None = None,
        slab_freq: np.ndarray | None = None,
        reuse_class: np.ndarray | None = None,
        concurrent_writer: Callable[[], None] | None = None,
    ) -> MigrationStats:
        """Bulk copy without locking; commit only pages not dirtied mid-copy.

        ``concurrent_writer`` is a test/simulation hook invoked between the
        bulk copy and the version re-check, standing in for writes that land
        while the DMA is in flight.
        """
        st = MigrationStats()
        pending = [int(p) for p in dict.fromkeys(int(p) for p in pages)
                   if int(self.store.tier[p]) != dst_tier
                   and int(self.store.slot[p]) != NO_SLOT]
        bank_freq = None if bank_freq is None else np.array(bank_freq)
        for attempt in range(self.max_retries + 1):
            if not pending:
                break
            if attempt > 0:
                st.retries += 1
                # bounded exponential backoff: give the writer that keeps
                # dirtying these pages a chance to move off them
                time.sleep(self.retry_backoff_s * (1 << (attempt - 1)))
            # 1) snapshot versions, 2) unlocked bulk copy to staging
            vsnap = {p: int(self.store.version[p]) for p in pending}
            staged = {p: self.store.read_page(p) for p in pending}
            if concurrent_writer is not None:
                concurrent_writer()
                concurrent_writer = None  # writer fires once
            # 3) dirty check + commit clean pages
            dirty: list[int] = []
            for p in pending:
                if int(self.store.version[p]) != vsnap[p]:
                    dirty.append(p)      # discard: will retry next iteration
                    st.dirty_discards += 1
                    continue
                rc = None if reuse_class is None else int(reuse_class[p])
                new_slot = _alloc_target_slot(self.store, dst_tier, bank_freq,
                                              slab_freq, rc)
                if new_slot is None:
                    continue
                old_tier, old_slot = int(self.store.tier[p]), int(self.store.slot[p])
                if self.store.is_device_tier(dst_tier):
                    self.store.pools[dst_tier].write_one(new_slot, staged[p])
                else:
                    self.store._host_write(dst_tier, new_slot, staged[p])
                self.store.alloc[old_tier].free(old_slot, 0)
                self.store.tier[p] = dst_tier
                self.store.slot[p] = new_slot
                self.store._mark_dirty_one(p)
                self.store.traffic[(old_tier, dst_tier)] += self.store.page_nbytes
                st.migrated += 1
                st.bytes_moved += self.store.page_nbytes
                _classify(st, dst_tier, 1)
                st.note_move(old_tier, dst_tier)
            pending = dirty
        _note_retries_exhausted(st, len(pending))
        self.stats.merge(st)
        return st

    # -- policy-selected execution (Sec. 6.3 observed asymmetry) ---------------
    def execute(self, decision: placement.PlacementDecision,
                bank_freq: np.ndarray | None = None,
                slab_freq: np.ndarray | None = None,
                reuse_class: np.ndarray | None = None) -> MigrationStats:
        return execute_decision(self, decision, bank_freq, slab_freq,
                                reuse_class)


# =============================================================================
# batched device-resident engine — the fast path
# =============================================================================

class BatchedMigrationEngine:
    """Executes migration plans as bulk device ops (see module docstring).

    Drop-in for ``MigrationEngine``: same constructor, same
    ``migrate_locked`` / ``migrate_optimistic`` / ``execute`` signatures,
    same resulting tier/slot/pool state.  ``chunk_pages`` bounds the
    staging working set and is the unit of the double-buffered host↔device
    pipeline: while chunk *i* is converting on the host, chunk *i+1*'s
    gather/transfer is already in flight (JAX async dispatch +
    ``copy_to_host_async``).
    """

    def __init__(self, store: TierStore, *, max_retries: int = 3,
                 chunk_pages: int = 64, retry_backoff_s: float = 1e-3):
        self.store = store
        self.max_retries = max_retries
        self.chunk_pages = max(1, int(chunk_pages))
        self.retry_backoff_s = retry_backoff_s
        self.stats = MigrationStats()

    # -- bulk staging ----------------------------------------------------------
    def _stage_device_to_host(self, src_tier: int,
                              slots: np.ndarray) -> np.ndarray:
        """Gather a device tier's slots into contiguous device staging
        (Pallas page_gather), then stream chunks to the host.  Each chunk's
        device→host copy is started asynchronously before the next chunk's
        gather is dispatched, so transfer overlaps packing."""
        slots = np.asarray(slots, np.int64)
        if slots.size == 0:
            return np.zeros((0, *self.store.cfg.page_shape), np.float32)
        bufs = []
        for i in range(0, slots.size, self.chunk_pages):
            chunk = slots[i:i + self.chunk_pages]
            g = self.store.gather_device(src_tier, chunk)
            try:
                g.copy_to_host_async()
            except AttributeError:      # older jax array types
                pass
            bufs.append((g, chunk.size))
        # gathers come back pow2-padded; slice to true counts in numpy
        return np.concatenate([np.asarray(b, np.float32)[:n]
                               for b, n in bufs])

    def _stage_host_to_device(self, dst_tier: int, dst_slots: np.ndarray,
                              values: np.ndarray) -> None:
        """Scatter host pages into their planned device-pool slots (Pallas
        page_scatter, pool donated).  Chunk *i+1*'s host→device transfer is
        issued before chunk *i*'s scatter blocks, double-buffering the
        upload.  Chunks are pow2-padded on the host pre-transfer so ragged
        tails don't mint fresh executables."""
        dst_slots = np.asarray(dst_slots, np.int64)
        k = dst_slots.size
        if k == 0:
            return
        c = self.chunk_pages

        def staged_chunk(i):
            v = values[i:i + c]
            return jax.device_put(_pad_pages(v, _pow2(v.shape[0])))

        nxt = staged_chunk(0)
        for i in range(0, k, c):
            cur = nxt
            if i + c < k:
                nxt = staged_chunk(i + c)
            self.store.scatter_device(dst_tier, dst_slots[i:i + c], cur)

    def _move_group(self, src_tier: int, dst_tier: int,
                    src_slots: np.ndarray, dst_slots: np.ndarray) -> None:
        """Bulk-move one (src, dst) tier pair's data by residency:
        device-addressable pairs (device and pinned-host tiers) stay
        inside the jax runtime — gather + scatter programs, with int8
        quantization fused into the pinned pool's scatter — the
        device<->numpy-host pairs go through chunked staging, and
        host->host is one vectorized numpy copy.

        Injected transient faults retry with exponential backoff up to
        ``max_retries``; past the cap :class:`TransientMigrationFault`
        escapes and the caller drops the group for this pass.  Injection
        fires *before* any data moves, so a failed attempt never leaves
        a half-written group."""
        store = self.store
        src_dev = store.is_addressable_tier(src_tier)
        dst_dev = store.is_addressable_tier(dst_tier)
        inj = get_injector()
        attempts = (self.max_retries + 1) if inj.enabled else 1
        with obs.span("migrate.move_group", src=src_tier, dst=dst_tier,
                      pages=int(len(src_slots))):
            for a in range(attempts):
                try:
                    inj.maybe_migration_fault(src_tier, dst_tier,
                                              int(len(src_slots)))
                except TransientMigrationFault:
                    if a + 1 >= attempts:
                        raise
                    time.sleep(self.retry_backoff_s * (1 << a))
                    continue
                if a:
                    note_recovered("migrate_retry")
                break
            if src_dev and dst_dev:
                staged = store.gather_device(src_tier, src_slots)
                store.scatter_device(dst_tier, dst_slots, staged)
            elif src_dev:
                staged = self._stage_device_to_host(src_tier, src_slots)
                store.host_write_batch(dst_tier, dst_slots, staged)
            elif dst_dev:
                staged = store.host_read_batch(src_tier, src_slots)
                self._stage_host_to_device(dst_tier, dst_slots, staged)
            else:
                staged = store.host_read_batch(src_tier, src_slots)
                store.host_write_batch(dst_tier, dst_slots, staged)

    # -- integrity pre-flight --------------------------------------------------
    def _preflight_verify(self, plan: MigrationPlan,
                          st: MigrationStats) -> MigrationPlan:
        """Verify checksums of the plan's covered-tier source pages before
        any data moves: a corrupt page's slot is quarantined (owner fails
        cleanly), its reserved destination slot freed, and the plan
        shrunk — corrupted bits are never copied forward into a faster
        tier.  No-op while integrity is disarmed."""
        store = self.store
        if not store.integrity.enabled or len(plan) == 0:
            return plan
        keep = np.ones(len(plan), bool)
        for src_t in np.unique(plan.src_tiers):
            t = int(src_t)
            if store.is_device_tier(t):
                continue
            idx = np.nonzero(plan.src_tiers == src_t)[0]
            bad = set(store.integrity.verify(store, t, plan.src_slots[idx]))
            for i in idx:
                if int(plan.src_slots[i]) in bad:
                    keep[i] = False
                    st.failed += 1
                    store.quarantine_slot(t, int(plan.src_slots[i]),
                                          "promotion-preflight")
                    store.alloc[plan.dst_tier].free(int(plan.dst_slots[i]), 0)
        return plan if keep.all() else subset_plan(plan, keep)

    # -- plan execution --------------------------------------------------------
    def execute_plan(self, plan: MigrationPlan) -> MigrationStats:
        """Apply a reserved plan as one bulk move per source tier (locked
        semantics: commit unconditionally).  Groups whose move faults past
        the retry cap are dropped from the commit — their pages stay in
        the source tier, their reservations are returned."""
        st = MigrationStats()
        store = self.store
        if plan.reads_by_tier:
            # optimistic plans stage every *pending* page before the dirty
            # check — charge the reads the synchronous unlocked copy would
            for t, n in plan.reads_by_tier.items():
                store.reads_from[int(t)] += int(n)
        plan = self._preflight_verify(plan, st)
        k = len(plan)
        if k:
            keep = np.ones(k, bool)
            for src_t in np.unique(plan.src_tiers):
                idx = np.nonzero(plan.src_tiers == src_t)[0]
                try:
                    self._move_group(int(src_t), plan.dst_tier,
                                     plan.src_slots[idx], plan.dst_slots[idx])
                except TransientMigrationFault:
                    keep[idx] = False
                    st.failed += idx.size
                    for i in idx:
                        store.alloc[plan.dst_tier].free(
                            int(plan.dst_slots[i]), 0)
                    continue
                if not plan.reads_by_tier:
                    store.reads_from[int(src_t)] += idx.size
                st.note_move(int(src_t), plan.dst_tier, idx.size)
            if not keep.all():
                plan = subset_plan(plan, keep)
                k = len(plan)
            if k:
                store.commit_moves(plan.pages, plan.dst_tier, plan.dst_slots)
        st.migrated = k + plan.trivial
        st.bytes_moved = (k + plan.trivial) * store.page_nbytes
        _classify(st, plan.dst_tier, st.migrated)
        self.stats.merge(st)
        return st

    # -- locked path -----------------------------------------------------------
    def migrate_locked(self, pages: Iterable[int], dst_tier: int,
                       bank_freq: np.ndarray | None = None,
                       slab_freq: np.ndarray | None = None,
                       reuse_class: np.ndarray | None = None) -> MigrationStats:
        plan = plan_locked(self.store, pages, dst_tier, bank_freq, slab_freq,
                           reuse_class)
        return self.execute_plan(plan)

    # -- optimistic (unlocked DMA) path ---------------------------------------
    def migrate_optimistic(
        self, pages: Iterable[int], dst_tier: int,
        bank_freq: np.ndarray | None = None,
        slab_freq: np.ndarray | None = None,
        reuse_class: np.ndarray | None = None,
        concurrent_writer: Callable[[], None] | None = None,
    ) -> MigrationStats:
        """Bulk unlocked copy: stage the whole batch, then commit only pages
        whose version counter did not advance mid-copy; dirtied pages retry
        on the next iteration (destination slots are only reserved after
        the dirty check, so aborted pages reserve nothing)."""
        st = MigrationStats()
        store = self.store
        pending = np.asarray(
            [int(p) for p in dict.fromkeys(int(p) for p in pages)
             if int(store.tier[p]) != dst_tier
             and int(store.slot[p]) != NO_SLOT], np.int64)
        if store.integrity.enabled and pending.size:
            # promotion pre-flight: quarantine corrupt source pages (their
            # slot drops to NO_SLOT) before anything is staged
            for t in np.unique(store.tier[pending]):
                t = int(t)
                if store.is_device_tier(t):
                    continue
                sel = pending[store.tier[pending] == t]
                for s in store.integrity.verify(store, t, store.slot[sel]):
                    st.failed += 1
                    store.quarantine_slot(t, int(s), "promotion-preflight")
            pending = pending[store.slot[pending] != NO_SLOT]
        bank_freq = None if bank_freq is None else np.array(bank_freq)
        for attempt in range(self.max_retries + 1):
            if pending.size == 0:
                break
            if attempt > 0:
                st.retries += 1
                # bounded exponential backoff: let the writer that keeps
                # dirtying these pages move off them before the re-stage
                time.sleep(self.retry_backoff_s * (1 << (attempt - 1)))
            # 1) snapshot versions, 2) unlocked bulk copy to staging —
            # one gather/read per source tier, all before the dirty check.
            # device->device staging never leaves the accelerator (the
            # dirty check only needs the host-side version array); only
            # device->host moves pay the chunked transfer.
            vsnap = store.version[pending].copy()
            src_tiers = store.tier[pending].copy()
            src_slots = store.slot[pending].copy()
            dst_dev = store.is_addressable_tier(dst_tier)
            staged = {}                      # src tier -> group buffer
            local_of = np.zeros(pending.size, np.int64)  # pos within group
            groups = {int(t): np.nonzero(src_tiers == t)[0]
                      for t in np.unique(src_tiers)}
            for src_t, idx in groups.items():
                local_of[idx] = np.arange(idx.size)
                if not store.is_addressable_tier(src_t):
                    staged[src_t] = store.host_read_batch(src_t,
                                                          src_slots[idx])
                elif dst_dev:
                    # both ends device-addressable: staging never leaves
                    # the jax runtime (pinned tiers included)
                    staged[src_t] = store.gather_device(src_t,
                                                        src_slots[idx])
                elif store.is_device_tier(src_t):
                    staged[src_t] = self._stage_device_to_host(
                        src_t, src_slots[idx])
                else:   # pinned src -> numpy-host dst
                    staged[src_t] = np.asarray(
                        store.gather_device(src_t, src_slots[idx]),
                        np.float32)[:idx.size]     # drop the pow2 padding
                store.reads_from[src_t] += idx.size
            if concurrent_writer is not None:
                concurrent_writer()
                concurrent_writer = None  # writer fires once
            # 3) dirty check + bulk-commit clean pages
            dirty_mask = store.version[pending] != vsnap
            st.dirty_discards += int(dirty_mask.sum())
            clean = np.nonzero(~dirty_mask)[0]
            commit_idx: list[int] = []
            dst_slots: list[int] = []
            for i in clean:
                rc = (None if reuse_class is None
                      else int(reuse_class[pending[i]]))
                s = _alloc_target_slot(store, dst_tier, bank_freq, slab_freq,
                                       rc)
                if s is None:
                    continue          # capacity exhausted: drop, like the ref
                commit_idx.append(int(i))
                dst_slots.append(s)
            if commit_idx:
                idx = np.asarray(commit_idx, np.int64)
                slots = np.asarray(dst_slots, np.int64)
                ok = np.ones(idx.size, bool)
                for src_t, gidx in groups.items():
                    m = src_tiers[idx] == src_t
                    sel = idx[m]                         # pending positions
                    if sel.size == 0:
                        continue
                    li = local_of[sel]
                    buf = staged[src_t]
                    if isinstance(buf, np.ndarray):
                        vals = buf[li]
                    else:
                        # device staging: pow2-pad the sub-gather too, so
                        # the commit's shapes stay bucketed (the matching
                        # scatter pads its slot vector the same way)
                        vals = buf[jnp.asarray(_pad_idx_np(li), jnp.int32)]
                    sslots = slots[m]
                    try:
                        self._commit_group_write(src_t, dst_tier, sslots,
                                                 vals, dst_dev)
                    except TransientMigrationFault:
                        # move faulted past the retry cap: return the
                        # reservations, leave the pages where they are
                        # (a later pass re-plans them)
                        ok[m] = False
                        st.failed += int(sel.size)
                        for s_ in sslots:
                            store.alloc[dst_tier].free(int(s_), 0)
                        continue
                    st.note_move(src_t, dst_tier, int(sel.size))
                if not ok.all():
                    idx, slots = idx[ok], slots[ok]
                if idx.size:
                    store.commit_moves(pending[idx], dst_tier, slots)
                    st.migrated += idx.size
                    st.bytes_moved += idx.size * store.page_nbytes
                    _classify(st, dst_tier, idx.size)
            pending = pending[dirty_mask]
        _note_retries_exhausted(st, int(pending.size))
        self.stats.merge(st)
        return st

    def _commit_group_write(self, src_tier: int, dst_tier: int,
                            dst_slots: np.ndarray, vals,
                            dst_dev: bool) -> None:
        """One optimistic-commit group write, behind the same injected
        fault + retry-with-backoff discipline as :meth:`_move_group`
        (injection fires before the write, so a retried attempt never
        double-writes)."""
        inj = get_injector()
        attempts = (self.max_retries + 1) if inj.enabled else 1
        for a in range(attempts):
            try:
                inj.maybe_migration_fault(src_tier, dst_tier,
                                          int(len(dst_slots)))
            except TransientMigrationFault:
                if a + 1 >= attempts:
                    raise
                time.sleep(self.retry_backoff_s * (1 << a))
                continue
            if a:
                note_recovered("migrate_retry")
            break
        store = self.store
        if not dst_dev:
            store.host_write_batch(dst_tier, dst_slots, vals)
        elif store.is_addressable_tier(src_tier):
            store.scatter_device(dst_tier, dst_slots, vals)
        else:
            self._stage_host_to_device(dst_tier, dst_slots, vals)

    # -- policy-selected execution ---------------------------------------------
    def execute(self, decision: placement.PlacementDecision,
                bank_freq: np.ndarray | None = None,
                slab_freq: np.ndarray | None = None,
                reuse_class: np.ndarray | None = None) -> MigrationStats:
        return execute_decision(self, decision, bank_freq, slab_freq,
                                reuse_class)


def make_engine(store: TierStore, kind: str = "batched", **kw):
    """Engine factory: ``"batched"`` (device-resident bulk mover, default)
    or ``"reference"`` (numpy per-page oracle)."""
    if kind == "batched":
        return BatchedMigrationEngine(store, **kw)
    if kind == "reference":
        return MigrationEngine(store, **kw)
    raise ValueError(f"unknown migration engine {kind!r}")
