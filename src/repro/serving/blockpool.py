"""Block-paged KV cache management (host side): a refcounted page pool
with copy-on-write prefix sharing.

The paper's §3/Fig. 2 critique of static dataflow applies to memory as
much as compute: a dense ``(num_slots, max_seq)`` cache provisions every
slot for the worst-case sequence, so short requests strand capacity and
admission is bounded by slots, not by actual KV bytes. This module
replaces that with a **block pool**: KV storage is a flat array of
fixed-size pages shared by all sequences, each sequence owns an ordered
list of page ids (its *block table*), and pages cycle through an explicit
LIFO free-list.

Pages are **refcounted**, which buys two things on top of plain paging:

  * **Prefix sharing.** N requests with the same system prompt /
    few-shot header map their page-aligned common prefix onto *one*
    physical copy: admission consults a
    :class:`~repro.serving.prefix.PrefixIndex` (hash chain of page-sized
    token chunks -> live page), bumps the refcount of every matched page
    (:meth:`BlockPool.share`), and prefills only the unshared suffix.
    ``free`` decrements; a page returns to the free list — and leaves the
    index — only when its last owner lets go, so a victim's release never
    tears pages out from under the sequences still reading them.

  * **Copy-on-write.** Shared pages are immutable: the first write into a
    page with refcount > 1 forks it — the manager allocates a fresh page,
    patches the writer's block table, and drops one ref
    (:meth:`PagedSlotManager.fork_for_write`); the engine copies the
    ``(layers, page_size, kv_heads, head_dim)`` slab on device. Everything
    downstream (decode, preemption, release) then treats the fork like any
    privately owned page.

Device layout (see :func:`repro.models.transformer.init_cache` with a
:class:`~repro.models.kvlayout.PagedLayout`):

    k/v pool: (num_layers, num_pages, page_size, kv_heads, head_dim)

Logical position ``p`` of the sequence in slot ``s`` lives at physical
``(block_tables[s, p // page_size], p % page_size)``. Block tables are a
dense ``(num_slots, max_pages_per_seq)`` int32 array handed to the jitted
decode/prefill-chunk steps each tick — **cached device-side** by the
manager and rebuilt only when some table actually changed (alloc, lazy
growth, release, COW fork), so steady-state decode ticks reuse the
device-resident operand. Unassigned entries hold the out-of-bounds
sentinel ``num_pages`` — KV scatters through them are dropped
(``mode="drop"``), and reads clamp to a real page whose contents the
attention length-mask discards. Correctness of empty slots in a partially
occupied batch depends on that sentinel: a 0 entry would alias a real
page another sequence may own.

Two classes:

  * :class:`BlockPool` — the refcounting free-list allocator (no device
    state). Invariant: every page is either on the free list with
    refcount 0, or allocated with refcount >= 1; the sum of refcounts
    equals the ownership multiset across slot block tables plus the
    manager's session-cache refs
    (:meth:`PagedSlotManager.check` enforces the cross-structure half).
  * :class:`PagedSlotManager` — drop-in replacement for
    :class:`repro.serving.kvcache.SlotManager` that additionally owns the
    per-slot block tables and (optionally) the prefix index. Allocation
    is **lazy**: admission reserves pages for the tokens that will
    actually be prefilled (shared prefix excluded) plus one decode growth
    page of headroom, and each decode tick grows a sequence's table
    page-by-page through :meth:`ensure` — so a pool can be overcommitted
    below worst-case footprint and the engine's scheduler preempts a
    victim (refs dropped, request re-queued) when :meth:`ensure` reports
    the pool dry. The block tables make preemption relocation-free: a
    re-admitted sequence just gets fresh pages — or re-maps its shared
    prefix if the pages survived through another owner.

**The memory hierarchy (tier 0 of three).** With a
:class:`~repro.serving.tiers.TieredPool` attached, this pool becomes
tier 0 of an HBM → host → disk page hierarchy and the manager stops
discarding KV it might want back:

  * **Session cache (tier-0 retention).** :meth:`retain_session` — the
    retire/preempt hook — registers a departing sequence's full pages in
    the prefix index and transfers the slot's ref on each to a
    manager-held LRU *session set* instead of freeing them. A returning
    conversation (same prompt + generated history) then re-maps its
    whole prefix by refcount bump, zero copies.
  * **Demotion under pressure.** When allocation runs dry,
    :meth:`reclaim_session` drops session refs LRU-first; pages whose
    last ref that was get their slabs bulk-copied device→host (the
    engine's gather) and land in the tiered store, with the index entry
    rebound from page id to store handle — matchable, just not
    addressable. Only falling off the hierarchy's bottom truly evicts.
  * **Promotion at admission.** :meth:`_make_slot` spans tiers: a match
    whose demoted span reaches the plan's ``swap_threshold`` (the
    ``dispatch.find_swap_threshold`` roofline: link copy cost vs
    chunked-prefill recompute) allocates fresh tier-0 pages for those
    chunks and hands the engine ``pending_promotions`` — one bulk
    host→device upload — instead of re-prefilling them.

The demoted bytes are the bytes the original run wrote, so a resumed or
returning sequence decodes bit-identically to one that never left.
"""
from __future__ import annotations

import dataclasses
from collections import OrderedDict
from typing import Callable, Optional

import numpy as np

from repro.models.kvlayout import pages_for, pow2_bucket  # noqa: F401
# (pages_for re-export: the one page ceil-div definition, shared with
# layouts/engine)
from repro.serving.kvcache import Slot, SlotManager
from repro.serving.prefix import PrefixIndex, shared_prefix_groups
from repro.serving.tiers import TieredPool


class BlockPool:
    """Refcounted fixed-size page allocator over ``num_pages`` pages."""

    def __init__(self, num_pages: int, page_size: int):
        if num_pages <= 0 or page_size <= 0:
            raise ValueError("num_pages and page_size must be positive")
        self.num_pages = num_pages
        self.page_size = page_size
        # LIFO: a just-freed (hot) page is reused first
        self._free: list[int] = list(range(num_pages - 1, -1, -1))
        self._ref: dict[int, int] = {}     # page -> refcount (>= 1)

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def used_pages(self) -> int:
        return len(self._ref)

    @property
    def total_refs(self) -> int:
        """Sum of refcounts = what a share-less pool would have used."""
        return sum(self._ref.values())

    def allocated_pages(self) -> set:
        """Snapshot of page ids with refcount >= 1."""
        return set(self._ref)

    def refcount(self, page: int) -> int:
        return self._ref.get(page, 0)

    def pages_for(self, positions: int) -> int:
        """Pages needed to store ``positions`` KV entries."""
        return pages_for(positions, self.page_size)

    def alloc(self, n: int) -> Optional[list[int]]:
        """Pop ``n`` pages off the free list (refcount 1 each); None if
        not enough remain."""
        if n < 0:
            raise ValueError("cannot allocate a negative page count")
        if n > len(self._free):
            return None
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._ref[p] = 1
        return pages

    def share(self, pages: list[int]) -> None:
        """Add one owner to each (already allocated) page."""
        for p in pages:
            if p not in self._ref:
                raise ValueError(f"cannot share unallocated page {p}")
        for p in pages:
            self._ref[p] += 1

    def free(self, pages: list[int]) -> list[int]:
        """Drop one ref per page; pages reaching refcount 0 return to the
        free list. Returns the pages that actually **died** — the caller
        (slot manager) purges those from the prefix index so a stale key
        can never resolve to a recycled page."""
        for p in pages:
            if p not in self._ref:
                raise ValueError(f"double free / foreign page {p}")
        dead = []
        for p in pages:
            self._ref[p] -= 1
            if self._ref[p] == 0:
                del self._ref[p]
                self._free.append(p)
                dead.append(p)
        return dead

    def check(self) -> None:
        """Invariant check (used by the property tests): every page is on
        exactly one side of the free/allocated split, and every allocated
        page has a positive refcount."""
        free = set(self._free)
        assert len(free) == len(self._free), "free list holds duplicates"
        assert not (free & set(self._ref)), "page both free and allocated"
        assert free | set(self._ref) == set(range(self.num_pages)), \
            "page leaked out of the pool"
        assert all(r >= 1 for r in self._ref.values()), \
            "allocated page with refcount < 1"


@dataclasses.dataclass
class GroupPlan:
    """One decode tick's shared-prefix grouping, host side.

    Built by :meth:`PagedSlotManager.group_plan` from the refcount-derived
    groups of :func:`~repro.serving.prefix.shared_prefix_groups`, with
    every dimension pow2-bucketed (groups NG, prefix pages LP, members M)
    so steady workloads hit a handful of jit shapes. Padding groups carry
    zero counts; padding table entries hold the pool's out-of-bounds
    sentinel; solo rows have ``gid == NG`` and ``prefix_len == 0``.

    ``operands()`` lazily uploads the arrays as the
    :class:`~repro.kernels.group_attention.DecodeGroups` pytree the model
    steps take — cached, so the steady-state tick reuses the same device
    buffers (the manager only rebuilds the plan when a block table
    changed).
    """

    gid: np.ndarray            # (B,)  group id per slot row; NG = solo
    member: np.ndarray         # (B,)  rank within the group; 0 for solo
    prefix_len: np.ndarray     # (B,)  shared positions; 0 = solo
    tables: np.ndarray         # (NG, LP) physical pages of each prefix
    n_pages: np.ndarray        # (NG,) live prefix pages per group
    g_prefix_len: np.ndarray   # (NG,) shared positions per group
    num_members: np.ndarray    # (NG,) live members per group
    member_rows: np.ndarray    # (NG, M) slot row of each member; B = pad
    n_grouped: int             # total rows covered by some group
    pages_deduped: int         # sum over groups of (members - 1) * pages
    _operands: object = dataclasses.field(default=None, repr=False)

    def operands(self):
        if self._operands is None:
            import jax.numpy as jnp

            from repro.kernels.group_attention import DecodeGroups
            self._operands = DecodeGroups(
                tables=jnp.asarray(self.tables),
                n_pages=jnp.asarray(self.n_pages),
                g_prefix_len=jnp.asarray(self.g_prefix_len),
                num_members=jnp.asarray(self.num_members),
                member_rows=jnp.asarray(self.member_rows),
                gid=jnp.asarray(self.gid),
                member=jnp.asarray(self.member),
                prefix_len=jnp.asarray(self.prefix_len),
            )
        return self._operands


@dataclasses.dataclass
class PagedSlot(Slot):
    pages: list = dataclasses.field(default_factory=list)
    # prefix-sharing admission metadata (all zero when sharing is off)
    shared_len: int = 0          # prefix positions mapped onto shared pages
    prefill_start: int = 0       # first position the engine must prefill
    prefill_level: int = 0       # same-wave ordering: prefill after every
    #                              slot whose pending pages this one mapped
    pending_fork: Optional[tuple] = None   # (src, dst): slab copy the
    #                              engine owes before this slot's prefill
    # tiered-promotion admission metadata (empty without a TieredPool)
    pending_promotions: list = dataclasses.field(default_factory=list)
    #                              [(slab, dst_page)]: host→device uploads
    #                              the engine owes before this slot's
    #                              prefill (promoted prefix content)
    session_mapped: int = 0      # matched pages served out of the tier-0
    #                              session cache (refcount bump, no copy)


class PagedSlotManager(SlotManager):
    """Slot occupancy + block tables over a shared :class:`BlockPool`.

    Inherits the ``SlotManager`` tick-loop interface (``lengths`` /
    ``tick`` and the admission scan) so the engine can switch cache kinds
    without touching its loop. Admission requires pages for the tokens
    about to be prefilled plus one growth page — minus whatever prefix the
    :class:`~repro.serving.prefix.PrefixIndex` maps onto existing pages
    (``prefix_index=None`` disables sharing); decode-time growth goes
    through :meth:`ensure` (lazy allocation), writes into shared pages
    fork through :meth:`fork_for_write`, and release drops one ref per
    page — the free list only sees pages whose last owner let go.
    """

    def __init__(self, num_slots: int, max_seq: int, pool: BlockPool,
                 prefix_index: Optional[PrefixIndex] = None,
                 tiers: Optional[TieredPool] = None):
        self.pool = pool
        self.prefix = prefix_index
        if prefix_index is not None and \
                prefix_index.page_size != pool.page_size:
            raise ValueError("prefix index / pool page_size mismatch")
        if tiers is not None and prefix_index is None:
            raise ValueError(
                "a TieredPool needs a prefix index — the index is the "
                "cross-tier map that makes demoted pages matchable")
        self.tiers = tiers
        # tier-0 session cache: pages of finished/preempted sequences the
        # manager holds one ref on, LRU order (page -> None); drained by
        # reclaim_session under pool pressure
        self._session: "OrderedDict[int, None]" = OrderedDict()
        # min demoted-span (pages) worth promoting instead of
        # re-prefilling; the engine sets it from plan.paged.swap_threshold
        self.swap_threshold = 1
        # engine hook: reclaim_cb(pages_needed) -> bool, demotes session
        # pages (device→host gather included) and returns whether enough
        # pool capacity was freed
        self.reclaim_cb: Optional[Callable[[int], bool]] = None
        self.max_pages_per_seq = pool.pages_for(max_seq)
        # dense (num_slots, max_pages_per_seq) block-table operand, cached
        # device-side; rebuilt only when a table changed (alloc / ensure /
        # release / COW fork) so steady-state decode ticks reuse it
        self._bt_cache = None
        self._bt_dirty = True
        # the shared-prefix group plan is a pure function of the block
        # tables + refcounts, so it shares the block-table dirty
        # discipline: every event that invalidates _bt_cache (admission,
        # growth, COW fork, release) invalidates the plan too
        self._gp_cache = None
        self._gp_dirty = True
        super().__init__(num_slots, max_seq)

    def _empty_slot(self) -> PagedSlot:
        return PagedSlot()

    def try_assign(self, request_id: int, prompt_len: int, max_new: int,
                   tokens=None) -> Optional[int]:
        idx = super().try_assign(request_id, prompt_len, max_new,
                                 tokens=tokens)
        if idx is not None:
            self._bt_dirty = True
            self._gp_dirty = True
            if self.prefix is not None and tokens is not None:
                # promise this slot's full prompt pages to later arrivals
                # (entries pending at this slot's wave level until its
                # prefill commits them)
                self.prefix.register(
                    tokens, self.slots[idx].pages,
                    level=self.slots[idx].prefill_level)
        return idx

    def _make_slot(self, request_id: int, prompt_len: int, max_new: int,
                   tokens=None) -> Optional[PagedSlot]:
        worst = self.pool.pages_for(prompt_len + max_new)
        if worst > self.pool.num_pages:
            # can never be satisfied, not even by an empty pool — raise like
            # the max_seq check (returning None would livelock admission,
            # and lazily admitting would guarantee an unservable mid-decode
            # growth failure with no preemptable victim once it runs alone)
            raise ValueError(
                f"request {request_id} needs {worst} pages > pool size "
                f"{self.pool.num_pages} (page_size {self.pool.page_size})")

        ps = self.pool.page_size
        # per covered chunk: ("share", page) -> refcount bump, or
        # ("promote", hid) -> fresh page + host→device upload
        kept: list[tuple] = []
        level = 0
        fork_src: Optional[int] = None
        session_mapped = 0
        if self.prefix is not None and tokens is not None and prompt_len:
            m = self.prefix.match(tokens)
            n_demoted = sum(1 for t in m.tiers if t > 0)
            # swap-vs-re-prefill: promoting is a per-admission decision —
            # either the whole demoted span is worth the link copies
            # (plan-tuned swap_threshold pages) or the match truncates at
            # the first demoted entry and those chunks re-prefill
            promote = (self.tiers is not None
                       and n_demoted >= self.swap_threshold)
            for pg, tier, hid in zip(m.pages, m.tiers, m.hids):
                if tier == 0:
                    kept.append(("share", pg))
                elif promote:
                    kept.append(("promote", hid))
                else:
                    break
            if kept and len(kept) * ps == prompt_len:
                # prompt fully covered: the tail page still must yield the
                # last-token logits, so the engine re-runs the final chunk.
                # A committed shared tail is forked (COW — the rewrite
                # lands in a private copy); a pending tail has no content
                # to copy yet, so just prefill that page ourselves; a
                # *promoted* tail needs neither — its fresh tier-0 page is
                # private already, so the re-run writes it in place.
                kind, val = kept[-1]
                if kind == "share":
                    if m.pending[len(kept) - 1] is not None:
                        kept.pop()
                    else:
                        fork_src = val
                        kept.pop()
            if m.pending_level >= 0:
                level = m.pending_level + 1
        n_shared = sum(1 for kind, _ in kept if kind == "share")
        n_promote = len(kept) - n_shared
        shared_len = (len(kept) + (1 if fork_src is not None else 0)) * ps

        # lazy: reserve what prefill will actually write (shared prefix
        # excluded; COW-fork destinations and promoted pages count as
        # writes) plus ONE decode growth page (capped at the request's
        # true total footprint) — without the headroom a request admitted
        # into a dry pool would pay the whole chunked prefill and be
        # preempted on its very first decode write, thrashing one token
        # per re-prefill. Further growth goes through ensure(),
        # preempting on exhaustion.
        need = min(self.pool.pages_for(prompt_len) + 1,
                   self.pool.pages_for(prompt_len + max_new)) - n_shared

        # Pin before any reclaim can run: share() the matched tier-0
        # pages (a session page's lone ref might otherwise be the one
        # reclaim demotes) and pop promoted slabs out of the tiered store
        # (reclaim demotes *into* the store and could otherwise LRU-evict
        # the very slabs this admission is about to upload).
        share_pages = [v for kind, v in kept if kind == "share"]
        self.pool.share(share_pages)
        promos = [(i, hid, self.tiers.pop(hid))
                  for i, (kind, hid) in enumerate(kept) if kind == "promote"]
        fresh = self._alloc_reclaiming(need)
        if fresh is None:
            # roll back: net refcounts restored; slabs re-demoted (their
            # entries rebound to the new handles, purged only if the
            # store is truly full)
            for page in self.pool.free(share_pages):
                self.prefix.drop_page(page)
            for _i, hid, slab in promos:
                new_hid = self.tiers.demote(slab)
                if new_hid is None:
                    self.prefix.purge_hid(hid)
                else:
                    self.prefix.rebind_hid(hid, new_hid)
                    self.prefix.set_tier(new_hid,
                                         self.tiers.tier_of(new_hid))
            if share_pages or promos:
                self._bt_dirty = True
                self._gp_dirty = True
            return None
        pages: list[int] = []
        fi = 0
        pending_promotions: list[tuple] = []
        slab_by_chunk = {i: slab for i, _hid, slab in promos}
        for i, (kind, val) in enumerate(kept):
            if kind == "share":
                pages.append(val)
                if val in self._session:
                    self._session.move_to_end(val)   # LRU recency
                    session_mapped += 1
            else:
                dst = fresh[fi]
                fi += 1
                pending_promotions.append((slab_by_chunk[i], dst))
                self.prefix.promote_hid(val, dst)
                pages.append(dst)
        pages += fresh[fi:]
        slot = PagedSlot(request_id, prompt_len, 0, max_new,
                         pages=pages,
                         shared_len=shared_len, prefill_level=level,
                         pending_promotions=pending_promotions,
                         session_mapped=session_mapped + n_promote)
        if fork_src is not None:
            # block table already points at the fork destination
            # (pages[len(kept)] = fresh[fi]); the engine copies the slab
            # before prefill, then re-runs the final chunk into it
            slot.pending_fork = (fork_src, fresh[fi])
        slot.prefill_start = min(shared_len, prompt_len)
        return slot

    def _alloc_reclaiming(self, n: int) -> Optional[list]:
        """``pool.alloc`` that spends the session cache before failing:
        on a dry pool, ask the engine to demote LRU session pages
        (``reclaim_cb``) and retry — finished-session KV is a cache, and
        a cache must never win a page fight against live admission or
        growth."""
        got = self.pool.alloc(n)
        if got is not None or self.reclaim_cb is None:
            return got
        if self.reclaim_cb(n - self.pool.free_pages):
            return self.pool.alloc(n)
        return None

    def ensure(self, idx: int, positions: int) -> bool:
        """Grow slot ``idx``'s block table to cover ``positions`` KV
        entries. False = the pool is dry (caller preempts and retries);
        the slot's existing pages are untouched either way."""
        s = self.slots[idx]
        need = self.pool.pages_for(positions) - len(s.pages)
        if need <= 0:
            return True
        got = self._alloc_reclaiming(need)
        if got is None:
            return False
        s.pages.extend(got)
        self._bt_dirty = True
        self._gp_dirty = True
        return True

    def fork_for_write(self, idx: int, start: int, end: int):
        """Copy-on-write hook: before slot ``idx`` writes KV positions
        ``[start, end)``, fork every covered page whose refcount > 1 —
        allocate a private destination, patch the block table, drop one
        ref on the source. Returns the ``(src, dst)`` pairs whose
        device slabs the engine must copy, or ``None`` when the pool is
        dry — side-effect free, so the caller preempts and retries
        against unchanged state and can never skip a pending slab copy.

        Every destination is reserved **up front** (one
        ``_alloc_reclaiming`` call): the session-cache reclaim a dry
        alloc may trigger demotes pages and mutates refcounts, so it
        must run before this fork takes any ref — never between a
        source's ref-drop and the engine's slab copy."""
        s = self.slots[idx]
        ps = self.pool.page_size
        to_fork: list[int] = []
        for pi in range(start // ps, (max(end, start + 1) - 1) // ps + 1):
            if pi >= len(s.pages):
                break                    # growth is ensure()'s job
            if self.pool.refcount(s.pages[pi]) > 1:
                to_fork.append(pi)
        if not to_fork:
            return []
        dsts = self._alloc_reclaiming(len(to_fork))
        if dsts is None:
            return None
        out: list[tuple[int, int]] = []
        for pi, dst in zip(to_fork, dsts):
            src = s.pages[pi]
            # a reclaim during the reservation may have dropped a session
            # ref and left src private after all — the fork is then
            # redundant but harmless, except its source can now die
            for page in self.pool.free([src]):
                if self.prefix is not None:
                    self.prefix.drop_page(page)
            s.pages[pi] = dst
            out.append((src, dst))
        self._bt_dirty = True
        self._gp_dirty = True
        return out

    def commit_prefix(self, idx: int, tokens) -> None:
        """Prefill for slot ``idx`` completed: the full prompt pages now
        hold real KV, so pending index entries become matchable-safe and
        this slot's own fresh full pages stay registered for the next
        arrival."""
        if self.prefix is not None:
            self.prefix.commit(tokens)

    def release(self, idx: int) -> None:
        s = self.slots[idx]
        if s.pages:
            for page in self.pool.free(s.pages):
                if self.prefix is not None:
                    self.prefix.drop_page(page)
            self._bt_dirty = True
            self._gp_dirty = True
        super().release(idx)

    # -- session cache (tier-0 retention + demotion under pressure) ----------

    def retain_session(self, idx: int, tokens) -> int:
        """Retire/preempt a slot *without discarding its KV*: register
        every full page of ``tokens`` (the slot's KV-valid token prefix)
        in the prefix index and transfer this slot's ref on each
        registered page to the manager's LRU session set — the tier-0
        session cache. A returning conversation re-maps those pages by
        refcount bump; pool pressure demotes them host-ward through
        :meth:`reclaim_session` instead. Pages the index does not hold
        (partial tail, superseded duplicates) are freed as usual.
        Returns how many pages the session set newly retained."""
        assert self.prefix is not None, "session cache needs a prefix index"
        s = self.slots[idx]
        self.prefix.register(tokens, s.pages)
        self.prefix.commit(tokens)
        indexed = self.prefix.shared_page_ids()
        retained = 0
        to_free: list[int] = []
        for p in s.pages:
            if p in indexed and p not in self._session:
                self._session[p] = None       # ref transfers to the cache
                retained += 1
            else:
                to_free.append(p)
        for page in self.pool.free(to_free):
            self.prefix.drop_page(page)
        s.pages = []
        self._bt_dirty = True
        self._gp_dirty = True
        self.release(idx)
        return retained

    def reclaim_session(self, need: int, gather) -> int:
        """Drop session-cache refs LRU-first until ``need`` pages return
        to the free list (or the cache is empty). A page whose *last* ref
        was the session's dies — its slab is bulk-copied device→host
        first (``gather(pages) -> {page: slab}``, one copy for the whole
        batch) and demoted into the tiered store, the index entry rebound
        to the store handle. A page some live slot still shares survives
        with its entry untouched; dropping the session ref just stops
        pinning it. Returns how many pages were actually freed."""
        if not self._session:
            return 0
        drop: list[int] = []
        expect = 0
        for p in self._session:               # LRU -> MRU order
            drop.append(p)
            if self.pool.refcount(p) == 1:
                expect += 1
            if expect >= need:
                break
        dying = [p for p in drop if self.pool.refcount(p) == 1]
        slabs = gather(dying) if dying and self.tiers is not None else {}
        freed = 0
        for p in drop:
            del self._session[p]
            if not self.pool.free([p]):
                continue                      # survives through a slot
            freed += 1
            hid = self.tiers.demote(slabs[p]) \
                if self.tiers is not None and p in slabs else None
            if hid is None or not self.prefix.demote_page(
                    p, hid, tier=self.tiers.tier_of(hid)):
                if hid is not None:
                    self.tiers.drop(hid)      # page wasn't indexed
                self.prefix.drop_page(p)
        self._bt_dirty = True
        self._gp_dirty = True
        return freed

    def session_pages(self) -> int:
        return len(self._session)

    def block_tables(self):
        """Dense (num_slots, max_pages_per_seq) int32 block-table operand
        for the jitted steps — a **cached device array**, rebuilt only
        when some slot's table changed since the last call, so
        steady-state decode ticks hand the model the same device-resident
        buffer instead of re-uploading an unchanged table every tick.

        Unassigned entries hold the out-of-bounds sentinel ``num_pages``:
        KV scatters through them are dropped (so an empty slot in the
        batch can never corrupt a page another sequence owns) and reads
        clamp to a real page whose contents the attention length-mask
        discards.
        """
        if self._bt_dirty or self._bt_cache is None:
            import jax.numpy as jnp
            bt = np.full((len(self.slots), self.max_pages_per_seq),
                         self.pool.num_pages, np.int32)
            for i, s in enumerate(self.slots):
                if s.pages:
                    bt[i, :len(s.pages)] = s.pages
            self._bt_cache = jnp.asarray(bt)
            self._bt_dirty = False
        return self._bt_cache

    def group_plan(self, threshold: int = 2) -> Optional[GroupPlan]:
        """Shared-prefix grouping for this tick's decode batch, or
        ``None`` when no group is worth dispatching — cached under the
        same dirty discipline as :meth:`block_tables` (rebuilt only when
        some table or refcount changed), so steady-state grouped decode
        reuses one host plan and its device operands tick after tick.

        A group survives only if it has >= 2 members **and** its
        deduplicated work ``members * prefix_pages >= threshold`` — below
        that the extra kernel stage costs more than the KV reads it
        saves (the plan's ``group_threshold`` knob, calibrated by
        ``dispatch.find_group_threshold``). Members must already cover
        their shared prefix (``length >= prefix_len``); a mid-prefill
        resident is left solo rather than read past its valid KV.
        """
        if not self._gp_dirty and self._gp_cache is not None \
                and self._gp_cache[0] == threshold:
            return self._gp_cache[1]
        plan = self._build_group_plan(threshold)
        self._gp_cache = (threshold, plan)
        self._gp_dirty = False
        return plan

    def _build_group_plan(self, threshold: int) -> Optional[GroupPlan]:
        ps = self.pool.page_size
        kept = []
        for key, members in shared_prefix_groups(self.slots,
                                                 self.pool.refcount):
            plen = len(key) * ps
            live = [i for i in members if self.slots[i].length >= plen]
            if len(live) >= 2 and len(live) * len(key) >= threshold:
                kept.append((key, live))
        if not kept:
            return None
        b = len(self.slots)
        ng = pow2_bucket(len(kept))
        lp = pow2_bucket(max(len(k) for k, _ in kept),
                         hi=self.max_pages_per_seq)
        m = pow2_bucket(max(len(ms) for _, ms in kept), hi=b)
        sentinel = self.pool.num_pages
        tables = np.full((ng, lp), sentinel, np.int32)
        n_pages = np.zeros(ng, np.int32)
        g_prefix_len = np.zeros(ng, np.int32)
        num_members = np.zeros(ng, np.int32)
        member_rows = np.full((ng, m), b, np.int32)
        gid = np.full(b, ng, np.int32)          # NG = solo sentinel
        member = np.zeros(b, np.int32)
        prefix_len = np.zeros(b, np.int32)
        n_grouped = 0
        pages_deduped = 0
        for g, (key, live) in enumerate(kept):
            tables[g, :len(key)] = key
            n_pages[g] = len(key)
            g_prefix_len[g] = len(key) * ps
            num_members[g] = len(live)
            member_rows[g, :len(live)] = live
            for r, i in enumerate(live):
                gid[i] = g
                member[i] = r
                prefix_len[i] = len(key) * ps
            n_grouped += len(live)
            pages_deduped += (len(live) - 1) * len(key)
        return GroupPlan(gid=gid, member=member, prefix_len=prefix_len,
                         tables=tables, n_pages=n_pages,
                         g_prefix_len=g_prefix_len,
                         num_members=num_members, member_rows=member_rows,
                         n_grouped=n_grouped, pages_deduped=pages_deduped)

    def check(self) -> None:
        """Cross-structure invariants for the property tests: free/ref
        conservation in the pool, and — the refcount invariant — the
        ownership multiset across slot block tables *plus the session
        cache's one-ref-per-page holdings* equals the pool's refcounts
        exactly. With a tiered store attached, every demoted index entry
        must resolve to a live slab."""
        self.pool.check()
        owned: dict[int, int] = {}
        for s in self.slots:
            if s.free:
                assert not s.pages, "free slot still holds pages"
            for p in s.pages:
                owned[p] = owned.get(p, 0) + 1
        for s in self.slots:
            assert len(set(s.pages)) == len(s.pages), \
                "one slot maps the same page twice (fork aliased)"
        for p in self._session:
            owned[p] = owned.get(p, 0) + 1
            assert self.prefix is not None \
                and p in self.prefix.shared_page_ids(), \
                f"session cache holds unindexed page {p}"
        assert {p: self.pool.refcount(p) for p in owned} == owned, \
            "refcounts out of sync with slot+session ownership multiset"
        assert set(owned) == self.pool.allocated_pages(), \
            "pool used-set out of sync with slot block tables"
        if self.tiers is not None:
            self.tiers.check()
        if self.prefix is not None:
            self.prefix.check(
                self.pool.allocated_pages(),
                self.tiers.ids() if self.tiers is not None else frozenset())
