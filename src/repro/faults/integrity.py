"""Per-page checksums for the slow tiers — detection half of recovery.

Checksums (definition + single-bit detection proof in
``repro.kernels.page_checksum``) are keyed by **(tier, logical slot)**:
logical slots are stable under the wear-leveling remap, so a Start-Gap
advance that physically relocates a row never invalidates its checksum
— the data moves with the remap.  Device tier 0 is trusted (HBM is not
the asymmetric media the fault model targets); every host/pinned tier
is covered.

Lifecycle: recorded on every write that lands in a covered tier
(demotion commits, host write paths, in-dispatch pinned KV appends at
the step boundary), dropped when the slot is freed, verified on
promotion pre-flight, on the serving engine's pre-dispatch sweep, and
by the budgeted round-robin :meth:`scrub` at memos-pass boundaries.  A
mismatch means the stored bits changed outside any write path — the
slot is quarantined and the owning sequence fails cleanly.
"""
from __future__ import annotations

import numpy as np


def _pad_pow2(idx: np.ndarray) -> np.ndarray:
    """Pow2-pad an index vector (mirrors tiers._pad_idx_np; re-stated
    here because faults sits below core in the import order)."""
    idx = np.asarray(idx, np.int64).reshape(-1)
    pad = (1 << max(idx.size - 1, 0).bit_length()) - idx.size
    if pad:
        idx = np.concatenate([idx, np.repeat(idx[-1:], pad)])
    return idx


class PageIntegrity:
    def __init__(self, enabled: bool = False):
        self.enabled = enabled
        self.sums: dict[tuple[int, int], int] = {}   # (tier, slot) -> uint32
        self._scrub_cursor = 0

    def covers(self, store, tier: int) -> bool:
        return not store.is_device_tier(tier)

    # -- checksum computation over the *stored* bits ---------------------------
    def slot_checksums(self, store, tier: int, slots) -> np.ndarray:
        # kernel import is deferred: repro.kernels pulls in repro.core,
        # which imports this module — a top-level import would cycle
        from repro.kernels.page_checksum import checksum_np
        slots = np.asarray(slots, np.int64).reshape(-1)
        phys = store._phys(tier, slots)
        pool = store.pools[tier]
        if isinstance(pool.data, np.ndarray):
            return checksum_np(pool.data[phys])
        # pinned jax pool: one checksum dispatch over the padded row list
        out = np.asarray(pool.checksums(_pad_pow2(phys)))
        return out[:slots.size]

    # -- lifecycle -------------------------------------------------------------
    def record(self, store, tier: int, slots) -> None:
        if not self.enabled or not self.covers(store, tier):
            return
        slots = np.asarray(slots, np.int64).reshape(-1)
        if slots.size == 0:
            return
        sums = self.slot_checksums(store, tier, slots)
        for s, c in zip(slots, sums):
            self.sums[(tier, int(s))] = int(c)

    def drop(self, tier: int, slots) -> None:
        if not self.enabled:
            return
        for s in np.atleast_1d(np.asarray(slots, np.int64)):
            self.sums.pop((tier, int(s)), None)

    def verify(self, store, tier: int, slots) -> list[int]:
        """Return the subset of ``slots`` whose stored bits no longer
        match their recorded checksum (unrecorded slots pass — there is
        nothing to verify against)."""
        if not self.enabled or not self.covers(store, tier):
            return []
        slots = np.asarray(slots, np.int64).reshape(-1)
        known = np.asarray([(tier, int(s)) in self.sums for s in slots])
        if not known.any():
            return []
        slots = slots[known]
        sums = self.slot_checksums(store, tier, slots)
        return [int(s) for s, c in zip(slots, sums)
                if self.sums[(tier, int(s))] != int(c)]

    def scrub(self, store, budget: int) -> list[tuple[int, int]]:
        """Verify up to ``budget`` recorded slots, round-robin across
        passes; returns the (tier, slot) pairs that failed."""
        if not self.enabled or not self.sums or budget <= 0:
            return []
        keys = sorted(self.sums.keys())
        start = self._scrub_cursor % len(keys)
        batch = [keys[(start + i) % len(keys)]
                 for i in range(min(budget, len(keys)))]
        self._scrub_cursor = (start + len(batch)) % max(len(keys), 1)
        bad: list[tuple[int, int]] = []
        by_tier: dict[int, list[int]] = {}
        for t, s in batch:
            by_tier.setdefault(t, []).append(s)
        for t, slots in by_tier.items():
            bad.extend((t, s) for s in self.verify(store, t, slots))
        return bad
