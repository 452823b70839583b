"""Device-tier expert cache: fixed slot buffers + a pluggable policy.

TPU-friendly layout: one stacked device buffer per weight matrix
(``[n_slots, d, ff]`` etc., static shapes), a host-side slot map, and
in-place slot updates (``buf.at[slot].set(w)`` on a donated buffer)
standing in for the host→HBM DMA. All decisions (hit/miss/evict)
happen on the host — control plane — exactly like the GPU baseline.

With a ``TieredMemoryManager`` attached (``tiers``), every install
reports which memory tier the expert's master copy was served from
(host or simulated disk — a disk fetch stalls the simulated clock),
and every eviction notifies the arbiter so the victim the *policy*
chose becomes the demotion target. Without one, behaviour is exactly
the pre-tiering single-host-tier cache.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cache_policies import CachePolicy
from repro.core.expert_store import ExpertStore
from repro.core.faults import FetchOutcome


@functools.partial(jax.jit, donate_argnums=0)
def _set_slot(buf, slot, w):
    """Write one expert matrix into slot ``slot`` of ``buf`` in place.
    Donating ``buf`` matters at full width: a copying update allocates
    a whole new [n_slots, d, ff] buffer per install, and with dispatch
    running ahead of the device a layer's installs then hold several
    such buffers at once."""
    return buf.at[slot].set(w.astype(buf.dtype))


class ExpertCache:
    """Cache for ONE MoE layer's experts.

    Parameters
    ----------
    layer : which MoE layer this cache serves (keys the store).
    n_slots : device slots; must equal ``policy.capacity``.
    policy : eviction policy (see ``repro.core.cache_policies``).
    store : host-tier master copies the misses stream from.
    shapes : per-weight-matrix shapes, e.g. ``{"w1": (d, ff), ...}``.
        The slot buffers take the store's dtype (``ExpertStore.dtype``).
    tiers : optional ``TieredMemoryManager`` — see module docstring.

    Counters (cumulative): ``hits``/``misses`` demand accesses,
    ``prefetches`` speculative installs actually transferred,
    ``bytes_transferred`` real store bytes moved host→device.
    ``last_miss_tiers`` holds the serving tier of each miss of the most
    recent ``access`` call, aligned with its returned miss list (the
    engine copies it into the step trace).
    """

    def __init__(self, layer: int, n_slots: int, policy: CachePolicy,
                 store: ExpertStore, shapes: Dict[str, tuple],
                 tiers=None, faults=None):
        assert policy.capacity == n_slots
        self.layer = layer
        self.n_slots = n_slots
        self.policy = policy
        self.store = store
        self.tiers = tiers
        self.faults = faults  # Optional[FaultInjector], shared stack-wide
        self.buffers = {k: jnp.zeros((n_slots, *s), store.dtype)
                        for k, s in shapes.items()}
        self.slot_of: Dict[int, int] = {}
        self._free: List[int] = list(range(n_slots))
        # counters
        self.hits = 0
        self.misses = 0
        self.prefetches = 0
        self.bytes_transferred = 0
        self.last_miss_tiers: Tuple[str, ...] = ()
        # fault-injection counters / last-call fault state
        self.fetch_failures = 0       # demand fetches abandoned (degraded)
        self.corrupt_refetches = 0    # checksum-mismatch redeliveries
        self.last_failed: Tuple[int, ...] = ()
        self.last_prefetch_failed: Tuple[int, ...] = ()
        self.last_prefetch_outcomes: Dict[int, FetchOutcome] = {}

    # ------------------------------------------------------------------
    def cached_ids(self) -> Tuple[int, ...]:
        """Resident expert ids, sorted (the trace's cache snapshot)."""
        return tuple(sorted(self.slot_of))

    def contains(self, eid: int) -> bool:
        """Hit test without touching policy state."""
        return eid in self.slot_of

    def expert_tier(self, eid: int) -> str:
        """Tier the master copy of ``eid`` would be served from."""
        if self.tiers is not None:
            return self.tiers.expert_tier((self.layer, eid))
        return "host"

    def plan_fetches(self, eids: Sequence[int]) -> Dict[int, FetchOutcome]:
        """Pre-decide the fate of each would-be demand fetch among
        ``eids`` (cached ids are hits — no fetch event is consumed).
        The caller learns the degraded set BEFORE compute and hands the
        same outcomes back to ``access`` (and to the transfer engine),
        so randomness is consumed exactly once per fetch."""
        if self.faults is None or self.faults.plan.is_null:
            return {}
        out = {}
        for eid in eids:
            if eid not in self.slot_of:
                out[eid] = self.faults.fetch_plan(
                    (self.layer, eid), tier=self.expert_tier(eid))
        return out

    def _install(self, eid: int, pinned: frozenset = frozenset(), *,
                 demand: bool = True,
                 outcome: Optional[FetchOutcome] = None
                 ) -> Tuple[int, Optional[int], str]:
        """Fetch eid from the store into a slot. Returns
        (slot, evicted, tier served from). A caller-supplied ``outcome``
        with corrupt deliveries exercises the REAL checksum path: the
        payload is actually corrupted, the mismatch detected, and the
        fetch redelivered."""
        nbytes = self.store.expert_nbytes((self.layer, eid))
        with jax.profiler.TraceAnnotation(
                "expert_cache.install", layer=self.layer, expert=int(eid),
                bytes=nbytes, demand=int(demand)):
            evicted = None
            if self._free:
                slot = self._free.pop()
            else:
                victim = self.policy.choose_victim(pinned)
                slot = self.slot_of.pop(victim)
                self.policy.remove(victim)
                evicted = victim
                if self.tiers is not None:
                    self.tiers.expert_evicted((self.layer, victim))
            tier = "host"
            if self.tiers is not None:
                tier = self.tiers.fetch_expert((self.layer, eid), demand=demand)
            w = self.store.fetch((self.layer, eid))
            if outcome is not None and outcome.corrupt_deliveries and \
                    self.faults is not None:
                key = (self.layer, eid)
                for _ in range(outcome.corrupt_deliveries):
                    bad = self.faults.corrupt_payload(w)
                    if self.store.verify(key, bad):
                        w = bad  # crc collision: corruption slips through
                        continue
                    self.corrupt_refetches += 1
                    w = self.store.fetch(key)
            for k, v in w.items():
                self.buffers[k] = _set_slot(self.buffers[k], slot, jnp.asarray(v))
            self.slot_of[eid] = slot
            self.policy.on_insert(eid)
        self.bytes_transferred += nbytes
        return slot, evicted, tier

    def access(self, eids: Sequence[int],
               outcomes: Optional[Dict[int, FetchOutcome]] = None
               ) -> Tuple[List[int], List[int], List[int]]:
        """Demand access for this token: returns (hits, misses, evicted).

        All of ``eids`` are pinned while installing so an expert needed
        by the current token can never evict another one of them; the
        caller chunks to ≤ capacity if the working set exceeds it.
        ``last_miss_tiers`` is left aligned with the returned misses.

        ``outcomes`` (from ``plan_fetches``) carries pre-planned fault
        fates: a miss whose outcome is abandoned is NOT installed — it
        still counts as a miss (the attempts were made) and lands in
        ``last_failed``; the engine degrades around it.
        """
        assert len(set(eids)) <= self.n_slots, "working set exceeds cache"
        pinned = frozenset(eids)
        hits, misses, evicted = [], [], []
        miss_tiers: List[str] = []
        failed: List[int] = []
        for eid in eids:
            if eid in self.slot_of:
                hits.append(eid)
                self.policy.on_access(eid)
            else:
                misses.append(eid)
                out = outcomes.get(eid) if outcomes else None
                if out is not None and not out.success:
                    failed.append(eid)
                    miss_tiers.append(self.expert_tier(eid))
                    continue
                _, ev, tier = self._install(eid, pinned, outcome=out)
                miss_tiers.append(tier)
                if ev is not None:
                    evicted.append(ev)
        self.hits += len(hits)
        self.misses += len(misses)
        self.fetch_failures += len(failed)
        self.last_miss_tiers = tuple(miss_tiers)
        self.last_failed = tuple(failed)
        self.policy.tick()
        return hits, misses, evicted

    def prefetch(self, eids: Sequence[int]) -> List[int]:
        """Speculatively admit eids (no demand stall). Returns the ids
        actually transferred (already-cached ones are free). Under
        fault injection each transfer's fate is planned here
        (``last_prefetch_outcomes`` aligns with the returned list);
        abandoned prefetches are not installed and land in
        ``last_prefetch_failed`` — harmless, the demand path refetches.
        """
        moved = []
        fates: Dict[int, FetchOutcome] = self.plan_fetches(eids)
        failed: List[int] = []
        for eid in eids:
            if eid in self.slot_of:
                self.policy.on_access(eid)
                continue
            out = fates.get(eid)
            if out is not None and not out.success:
                failed.append(eid)
                continue
            self._install(eid, demand=False, outcome=out)
            moved.append(eid)
        self.prefetches += len(moved)
        self.last_prefetch_failed = tuple(failed)
        self.last_prefetch_outcomes = {e: fates[e] for e in moved
                                       if e in fates}
        return moved

    def gather(self, eids: Sequence[int]) -> Dict[str, jnp.ndarray]:
        """Stacked device weights [len(eids), ...] for cached experts."""
        slots = jnp.asarray([self.slot_of[e] for e in eids], jnp.int32)
        return {k: v[slots] for k, v in self.buffers.items()}

    def device_nbytes(self) -> int:
        """Device bytes this cache's slot buffers pin (static — slots
        are allocated up front, not per resident expert)."""
        return sum(int(np.prod(v.shape)) * v.dtype.itemsize
                   for v in self.buffers.values())
