"""Tiered KV page store: demote, don't discard.

The block pool (:mod:`repro.serving.blockpool`) is **tier 0** — device
HBM, the only tier kernels can address. This module adds the rest of the
hierarchy behind one interface:

    tier 0 (device pool)  →  tier 1 (host RAM, numpy slabs)
                          →  tier 2 (disk, optional)

The capacity argument (LIMINAL's limit study; "Inference Optimization of
Foundation Models on AI Accelerators", PAPERS.md): once attention reads
are paged, decode throughput is bounded jointly by HBM *capacity* and
bandwidth — and host DRAM is ~2 orders of magnitude larger than HBM at a
PCIe-class link cost that a roofline can price against re-prefill
(:func:`repro.core.dispatch.find_swap_threshold`). So instead of freeing
a victim's KV pages (preemption) or a finished conversation's prefix
pages (retire), the serving stack **demotes** them here and the
:class:`~repro.serving.prefix.PrefixIndex` keeps their chain-hash keys
matchable with a tier tag — a returning session *promotes* its persisted
prefix back into freshly allocated tier-0 pages (one bulk host→device
copy) instead of recomputing it.

Division of labor:

  * :class:`TieredPool` (this module) owns the **slabs** — host-side
    copies of one page's per-layer K/V arrays, keyed by a monotonically
    increasing host id (``hid``). It is content-agnostic: a slab is
    whatever tuple of numpy arrays the engine gathered. Capacity is
    bounded (``host_pages`` / ``disk_pages``); overflow spills LRU-first
    down the hierarchy and **truly evicts** — purging the index entry —
    only when the bottom tier is full (or absent).
  * The :class:`~repro.serving.prefix.PrefixIndex` owns the **keys**:
    ``demote_page``/``promote_hid`` rebind an entry between a tier-0
    page id and a tiered ``hid`` so one chain-hash lookup spans the whole
    hierarchy.
  * The engine owns the **copies**: one bulk device→host gather per
    demotion batch, one bulk host→device scatter per promotion batch
    (the only tier that ever touches jax is tier 0).

Nothing here imports jax — the store is plain host memory + files, and
the property tests drive it with dummy slabs.
"""
from __future__ import annotations

import dataclasses
import os
import pickle
from collections import OrderedDict
from typing import Optional


@dataclasses.dataclass
class TierStats:
    """Counters for the engine summary and stats."""

    demoted: int = 0          # pages accepted into the hierarchy (tier >= 1)
    promoted: int = 0         # pages popped back toward tier 0
    disk_demotions: int = 0   # host -> disk spills (tier 1 -> 2)
    evicted: int = 0          # pages that fell off the bottom (KV lost;
    #                           the index entry is purged — re-prefill)


class TieredPool:
    """Bounded host(+disk) store for demoted KV page slabs.

    ``demote(slab)`` accepts one page's host-side slab and returns its
    ``hid`` handle (or ``None`` when the hierarchy has nowhere to put it
    — zero host pages and no disk tier). Admission of a new slab never
    fails by *rejecting the new page*: capacity pressure spills the
    **least-recently-used** resident slab downward instead (host → disk,
    disk → gone), because the page being demoted right now belongs to the
    most recently active session. ``pop(hid)`` removes and returns a slab
    for promotion; ``drop(hid)`` discards without copying.

    The optional ``index`` (a :class:`~repro.serving.prefix.PrefixIndex`)
    is kept consistent on every internal movement: host→disk retags the
    entry (``set_tier``), a true eviction purges it (``purge_hid``) so a
    chain-hash key can never resolve to a slab that no longer exists.
    """

    def __init__(self, host_pages: int, *, index=None,
                 disk_dir: Optional[str] = None, disk_pages: int = 0):
        if host_pages < 0 or disk_pages < 0:
            raise ValueError("tier capacities must be >= 0")
        if disk_pages and not disk_dir:
            raise ValueError("disk_pages > 0 requires disk_dir")
        self.host_pages = host_pages
        self.disk_pages = disk_pages if disk_dir else 0
        self.disk_dir = disk_dir
        self.index = index
        self._host: "OrderedDict[int, tuple]" = OrderedDict()  # hid -> slab
        self._disk: "OrderedDict[int, str]" = OrderedDict()    # hid -> path
        self._next_hid = 0
        self.stats = TierStats()
        if self.disk_pages:
            os.makedirs(disk_dir, exist_ok=True)

    def __len__(self) -> int:
        return len(self._host) + len(self._disk)

    @property
    def host_used(self) -> int:
        return len(self._host)

    @property
    def disk_used(self) -> int:
        return len(self._disk)

    def ids(self) -> set:
        """Live hids across every tier (the index-check ground truth)."""
        return set(self._host) | set(self._disk)

    def tier_of(self, hid: int) -> int:
        if hid in self._host:
            return 1
        if hid in self._disk:
            return 2
        raise KeyError(f"unknown hid {hid}")

    # -- downward dataflow ---------------------------------------------------

    def demote(self, slab) -> Optional[int]:
        """Admit one page slab into the hierarchy; returns its ``hid`` or
        ``None`` when there is no capacity anywhere (the caller then
        treats the page as truly evicted and purges its index entry)."""
        hid = self._next_hid
        self._next_hid += 1
        if self.host_pages > 0:
            while len(self._host) >= self.host_pages:
                self._spill_lru()
            self._host[hid] = slab
            self.stats.demoted += 1
            return hid
        if self._disk_store(hid, slab):
            self.stats.demoted += 1
            if self.index is not None:
                self.index.set_tier(hid, 2)
            return hid
        return None

    def _spill_lru(self) -> None:
        """Push the least-recently-used host slab down one tier."""
        hid, slab = self._host.popitem(last=False)
        if self._disk_store(hid, slab):
            self.stats.disk_demotions += 1
            if self.index is not None:
                self.index.set_tier(hid, 2)
        else:
            self.stats.evicted += 1
            if self.index is not None:
                self.index.purge_hid(hid)

    def _disk_store(self, hid: int, slab) -> bool:
        if not self.disk_pages:
            return False
        while len(self._disk) >= self.disk_pages:
            old, path = self._disk.popitem(last=False)
            os.remove(path)
            self.stats.evicted += 1
            if self.index is not None:
                self.index.purge_hid(old)
        # pickle, not np.savez: slabs may be extension dtypes (ml_dtypes
        # bfloat16) that the npy format round-trips unreliably; pickle
        # preserves bytes + dtype exactly, which the bit-identity
        # invariant needs
        path = os.path.join(self.disk_dir, f"page-{hid}.kv")
        with open(path, "wb") as f:
            pickle.dump(slab, f, protocol=pickle.HIGHEST_PROTOCOL)
        self._disk[hid] = path
        return True

    # -- upward dataflow -----------------------------------------------------

    def pop(self, hid: int):
        """Remove and return a slab for promotion back to tier 0."""
        slab = self._host.pop(hid, None)
        if slab is None:
            path = self._disk.pop(hid)   # KeyError on unknown hid
            with open(path, "rb") as f:
                slab = pickle.load(f)
            os.remove(path)
        self.stats.promoted += 1
        return slab

    def touch(self, hid: int) -> None:
        """Refresh LRU recency (a session re-matched this slab)."""
        if hid in self._host:
            self._host.move_to_end(hid)
        elif hid in self._disk:
            self._disk.move_to_end(hid)

    def drop(self, hid: int) -> None:
        """Discard a slab without promoting it (entry superseded)."""
        if self._host.pop(hid, None) is None:
            path = self._disk.pop(hid, None)
            if path is not None:
                os.remove(path)

    # -- invariants ----------------------------------------------------------

    def check(self) -> None:
        assert len(self._host) <= max(self.host_pages, 0), \
            "host tier over capacity"
        assert len(self._disk) <= self.disk_pages, "disk tier over capacity"
        assert not (set(self._host) & set(self._disk)), \
            "hid resident in two tiers at once"
        for path in self._disk.values():
            assert os.path.exists(path), f"disk slab file missing: {path}"
        if self.index is not None:
            # every index entry pointing into the hierarchy must resolve
            assert self.index.demoted_ids() <= self.ids(), \
                "index maps a hid the tiered store no longer holds"
