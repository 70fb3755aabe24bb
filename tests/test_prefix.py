"""Prefix sharing: chain-hash index semantics, refcount/COW invariants
under random lifecycles (property-based via the hypothesis shim), and the
acceptance bar — greedy outputs bit-identical with sharing on vs off
(vs the dense engine too), including across a preemption of a sharing
sequence and through the fully-covered COW-fork path."""
import jax
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro import configs
from repro.models.api import get_model
from repro.models.kvlayout import pages_for
from repro.serving.blockpool import BlockPool, PagedSlotManager
from repro.serving.engine import Engine
from repro.serving.prefix import PrefixIndex
from repro.serving.request import SamplingParams
from repro.serving.tiers import TieredPool

settings.register_profile("fast", max_examples=20, deadline=None)
settings.load_profile("fast")


# ---------------------------------------------------------------------------
# PrefixIndex unit semantics
# ---------------------------------------------------------------------------


def test_index_matches_only_full_page_aligned_prefixes():
    ix = PrefixIndex(page_size=4)
    toks = list(range(10, 21))                 # 11 tokens = 2 full pages
    assert ix.register(toks, pages=[7, 8, 99]) == 2   # tail page ignored
    m = ix.match(toks)
    assert m.pages == [7, 8]
    assert ix.match(toks[:7]).pages == [7]     # 1 full page covered
    assert ix.match(toks[:3]).pages == []      # below one page: no match
    assert len(ix) == 2


def test_index_chain_hash_requires_matching_ancestry():
    ix = PrefixIndex(page_size=4)
    ix.register([1, 2, 3, 4, 5, 6, 7, 8], pages=[0, 1])
    # same second chunk, different first chunk -> chain key differs, and
    # the match must stop at the first divergent page
    m = ix.match([9, 9, 9, 9, 5, 6, 7, 8])
    assert m.pages == []
    m = ix.match([1, 2, 3, 4, 9, 9, 9, 9])
    assert m.pages == [0]


def test_index_first_registrant_wins_and_drop_purges():
    ix = PrefixIndex(page_size=2)
    ix.register([1, 2, 3, 4], pages=[5, 6])
    ix.register([1, 2, 9, 9], pages=[7, 8])    # chunk [1,2] already indexed
    assert ix.match([1, 2]).pages == [5]
    assert ix.match([1, 2, 9, 9]).pages == [5, 8]
    ix.drop_page(5)                            # page returned to free list
    assert ix.match([1, 2, 3, 4]).pages == []  # chain broken at the root
    assert 5 not in ix.shared_page_ids()
    ix.check(live_pages={6, 8})


def test_index_pending_levels_and_commit():
    ix = PrefixIndex(page_size=2)
    ix.register([1, 2, 3, 4], pages=[0, 1], level=0)   # promised, unwritten
    m = ix.match([1, 2, 3, 4, 5])
    assert m.pages == [0, 1]
    assert m.pending_level == 0 and m.tail_pending
    ix.commit([1, 2, 3, 4])
    m = ix.match([1, 2, 3, 4, 5])
    assert m.pending_level == -1 and not m.tail_pending


# ---------------------------------------------------------------------------
# Refcounted manager + COW fork: directed and property-based lifecycles
# ---------------------------------------------------------------------------


def _mgr(num_pages=16, page_size=4, num_slots=3, max_seq=32):
    pool = BlockPool(num_pages, page_size)
    return PagedSlotManager(num_slots, max_seq, pool,
                            prefix_index=PrefixIndex(page_size)), pool


def test_shared_admission_bumps_refcounts_and_skips_pages():
    mgr, pool = _mgr()
    toks = np.arange(100, 109, dtype=np.int32)          # 9 tokens, 2 full pages
    a = mgr.try_assign(0, 9, 4, tokens=toks)
    assert a is not None
    mgr.commit_prefix(a, toks)
    used_before = pool.used_pages
    b = mgr.try_assign(1, 9, 4, tokens=toks)
    assert b is not None
    sb = mgr.slots[b]
    assert sb.shared_len == 8 and sb.prefill_start == 8
    assert sb.pages[:2] == mgr.slots[a].pages[:2]       # same physical pages
    assert all(pool.refcount(p) == 2 for p in sb.pages[:2])
    # only the tail + headroom were newly allocated
    assert pool.used_pages == used_before + (len(sb.pages) - 2)
    mgr.check()


def test_shared_pages_survive_one_owners_release():
    mgr, pool = _mgr()
    toks = np.arange(100, 109, dtype=np.int32)
    a = mgr.try_assign(0, 9, 4, tokens=toks)
    mgr.commit_prefix(a, toks)
    b = mgr.try_assign(1, 9, 4, tokens=toks)
    shared = list(mgr.slots[b].pages[:2])
    mgr.release(a)                                      # victim lets go
    assert all(pool.refcount(p) == 1 for p in shared)   # survived via b
    assert mgr.prefix.match(toks).pages == shared       # still matchable
    mgr.release(b)                                      # last owner
    assert all(pool.refcount(p) == 0 for p in shared)
    assert mgr.prefix.match(toks).pages == []           # purged with pages
    mgr.check()
    assert pool.free_pages == pool.num_pages


def test_fork_for_write_privatizes_without_aliasing():
    mgr, pool = _mgr()
    toks = np.arange(100, 109, dtype=np.int32)
    a = mgr.try_assign(0, 9, 4, tokens=toks)
    mgr.commit_prefix(a, toks)
    b = mgr.try_assign(1, 9, 4, tokens=toks)
    shared = list(mgr.slots[b].pages)
    forks = mgr.fork_for_write(b, 0, 9)        # write over the shared span
    assert forks is not None and len(forks) == 2
    for src, dst in forks:
        assert src != dst
        assert pool.refcount(src) == 1         # back to a's exclusively
        assert pool.refcount(dst) == 1         # b's private copy
        assert dst in mgr.slots[b].pages and dst not in mgr.slots[a].pages
    assert mgr.fork_for_write(b, 0, 9) == []   # idempotent: all private now
    assert shared[2:] == mgr.slots[b].pages[2:]  # unshared tail untouched
    mgr.check()


def test_fork_for_write_reports_dry_pool():
    pool = BlockPool(num_pages=4, page_size=4)
    mgr = PagedSlotManager(3, 16, pool, prefix_index=PrefixIndex(4))
    toks = np.arange(50, 55, dtype=np.int32)            # 5 toks: 1 full page
    a = mgr.try_assign(0, 5, 1, tokens=toks)            # 2 pages
    mgr.commit_prefix(a, toks)
    b = mgr.try_assign(1, 5, 1, tokens=toks)            # shares 1, allocs 1
    c = mgr.try_assign(2, 1, 1)                         # takes the last page
    assert b is not None and c is not None
    assert pool.free_pages == 0
    assert mgr.fork_for_write(b, 0, 4) is None          # dry: caller preempts
    mgr.check()                                         # nothing corrupted
    mgr.release(c)                                      # preemption mechanics
    forks = mgr.fork_for_write(b, 0, 4)                 # retry succeeds,
    assert forks and pool.refcount(forks[0][0]) == 1    # page still shared
    mgr.check()


def test_fork_for_write_rolls_back_partial_forks_on_dry_pool():
    """A multi-page fork that runs dry mid-way must undo the forks it
    already made (table restored, ref re-taken, destination freed) — a
    fork left patched-but-uncopied would read uninitialized KV after the
    caller's preempt-and-retry skips the now-refcount-1 page."""
    pool = BlockPool(num_pages=7, page_size=4)
    mgr = PagedSlotManager(3, 16, pool, prefix_index=PrefixIndex(4))
    toks = np.arange(60, 69, dtype=np.int32)            # 9 toks: 2 full pages
    a = mgr.try_assign(0, 9, 1, tokens=toks)            # 3 pages
    mgr.commit_prefix(a, toks)
    b = mgr.try_assign(1, 9, 1, tokens=toks)            # shares 2, allocs 1
    c = mgr.try_assign(2, 5, 1)                         # takes 2 more
    assert b is not None and c is not None
    assert pool.free_pages == 1                         # room for ONE fork
    before = list(mgr.slots[b].pages)
    assert mgr.fork_for_write(b, 0, 8) is None          # second fork dry
    assert mgr.slots[b].pages == before                 # rolled back
    assert all(pool.refcount(p) == 2 for p in before[:2])
    assert pool.free_pages == 1
    mgr.check()
    mgr.release(c)                                      # preempt-and-retry
    forks = mgr.fork_for_write(b, 0, 8)
    assert forks is not None and len(forks) == 2        # both pages forked
    mgr.check()


def _assert_group_plan_consistent(mgr):
    """Decode-group plan invariants, checked against the manager's own
    ground truth after every lifecycle op:

      * every resident slot is in exactly one group or solo, and the solo
        sentinel is coherent (``gid == NG`` iff ``prefix_len == 0``);
      * each group's table is exactly its members' leading pages and
        every one of those pages is genuinely shared (refcount >= 2);
      * ``member_rows`` round-trips ``gid``/``member`` (the kernel's
        scatter and un-scatter agree on who sits where);
      * no member is grouped beyond its valid KV
        (``length >= prefix_len``).
    """
    plan = mgr.group_plan(threshold=2)
    if plan is None:
        return
    ng = plan.tables.shape[0]
    grouped_rows = set()
    for g in range(ng):
        nm = int(plan.num_members[g])
        if nm == 0:
            continue
        assert nm >= 2, "a 1-member group saves nothing"
        key = [int(p) for p in plan.tables[g, :int(plan.n_pages[g])]]
        assert key and all(mgr.pool.refcount(p) >= 2 for p in key)
        plen = int(plan.g_prefix_len[g])
        assert plen == len(key) * mgr.pool.page_size
        rows = [int(r) for r in plan.member_rows[g, :nm]]
        assert len(set(rows)) == nm, "member row listed twice"
        for r, i in enumerate(rows):
            s = mgr.slots[i]
            assert not s.free and i not in grouped_rows
            grouped_rows.add(i)
            assert list(s.pages[:len(key)]) == key
            assert s.length >= plen
            assert int(plan.gid[i]) == g and int(plan.member[i]) == r
            assert int(plan.prefix_len[i]) == plen
    for i in range(len(mgr.slots)):
        if i in grouped_rows:
            continue
        assert int(plan.gid[i]) == ng      # solo sentinel
        assert int(plan.prefix_len[i]) == 0


@given(st.integers(0, 10_000))
def test_sharing_manager_random_lifecycle(seed):
    """check() invariants — refcount == ownership multiset, no page both
    free and owned, fork never aliases, index maps only live pages —
    under random admit(shared-prefix tokens)/grow/fork/commit/release;
    plus the decode-group plan invariants after every op (the plan is
    rebuilt from live refcounts, so fork/release must re-key it)."""
    rng = np.random.default_rng(seed)
    page_size = int(rng.choice([2, 4]))
    num_pages = int(rng.integers(6, 32))
    num_slots = int(rng.integers(2, 5))
    max_seq = page_size * max(3, num_pages // num_slots)
    pool = BlockPool(num_pages, page_size)
    mgr = PagedSlotManager(num_slots, max_seq, pool,
                           prefix_index=PrefixIndex(page_size))
    # a tiny prompt pool with heavy prefix overlap: every prompt extends
    # one of two headers, so admissions genuinely share pages
    headers = [list(rng.integers(1, 50, size=2 * page_size)) for _ in range(2)]
    live: dict[int, np.ndarray] = {}
    rid = 0
    for _ in range(50):
        op = rng.random()
        if op < 0.4:
            toks = np.asarray(
                headers[int(rng.integers(2))][:int(rng.integers(
                    1, 2 * page_size + 1))]
                + list(rng.integers(1, 50, size=int(rng.integers(0, 6)))),
                np.int32)[:max_seq - 1]
            max_new = int(rng.integers(1, max_seq - len(toks) + 1))
            if pages_for(len(toks) + max_new, page_size) > num_pages:
                continue
            idx = mgr.try_assign(rid, len(toks), max_new, tokens=toks)
            if idx is not None:
                assert idx not in live, "slot double-assigned"
                live[idx] = toks
                rid += 1
                mgr.commit_prefix(idx, toks)   # content "written"
        elif op < 0.55 and live:
            idx = list(live)[rng.integers(len(live))]
            mgr.ensure(idx, int(rng.integers(1, max_seq + 1)))
        elif op < 0.75 and live:
            idx = list(live)[rng.integers(len(live))]
            pos = int(rng.integers(0, max_seq))
            mgr.fork_for_write(idx, pos, pos + 1)   # dry-pool None is fine
        elif live:
            idx = list(live)[rng.integers(len(live))]
            del live[idx]
            mgr.release(idx)
        mgr.check()                           # invariants after every op
        _assert_group_plan_consistent(mgr)
    for idx in list(live):
        mgr.release(idx)
    mgr.check()
    assert pool.free_pages == num_pages       # every ref returned
    assert len(mgr.prefix) == 0               # index died with its pages
    assert mgr.group_plan(threshold=2) is None  # nothing resident to group


@given(st.integers(0, 10_000))
def test_tiered_manager_random_lifecycle(seed):
    """The same random-lifecycle invariants with a tiered store behind
    the pool, plus the cross-tier ops: retire-with-retention
    (retain_session), demotion under pressure (reclaim_session with a
    dummy gather), promotion at re-admission (overlapping prompts re-hit
    demoted entries whenever the random swap_threshold allows), and true
    eviction off a deliberately tiny host tier. After every op:
    refcounts == slot+session ownership, every demoted index entry
    resolves to a live slab, tier capacities respected."""
    rng = np.random.default_rng(seed)
    page_size = int(rng.choice([2, 4]))
    num_pages = int(rng.integers(6, 24))
    num_slots = int(rng.integers(2, 5))
    host_pages = int(rng.integers(0, 6))      # 0 = evict-on-demote hierarchy
    max_seq = page_size * max(3, num_pages // num_slots)
    pool = BlockPool(num_pages, page_size)
    ix = PrefixIndex(page_size)
    tiers = TieredPool(host_pages, index=ix)
    mgr = PagedSlotManager(num_slots, max_seq, pool,
                           prefix_index=ix, tiers=tiers)
    mgr.swap_threshold = int(rng.integers(1, 4))

    def gather(pages):                        # engine's device→host stand-in
        return {p: ("slab", p) for p in pages}

    if rng.random() < 0.5:                    # engine wiring: cache loses
        mgr.reclaim_cb = \
            lambda need: mgr.reclaim_session(need, gather) >= need
    headers = [list(rng.integers(1, 50, size=2 * page_size)) for _ in range(2)]
    live: dict[int, np.ndarray] = {}
    rid = 0
    for _ in range(50):
        op = rng.random()
        if op < 0.35:
            toks = np.asarray(
                headers[int(rng.integers(2))][:int(rng.integers(
                    1, 2 * page_size + 1))]
                + list(rng.integers(1, 50, size=int(rng.integers(0, 6)))),
                np.int32)[:max_seq - 1]
            max_new = int(rng.integers(1, max_seq - len(toks) + 1))
            if pages_for(len(toks) + max_new, page_size) > num_pages:
                continue
            idx = mgr.try_assign(rid, len(toks), max_new, tokens=toks)
            if idx is not None:
                live[idx] = toks
                rid += 1
                mgr.commit_prefix(idx, toks)
        elif op < 0.45 and live:
            idx = list(live)[rng.integers(len(live))]
            mgr.ensure(idx, int(rng.integers(1, max_seq + 1)))
        elif op < 0.55 and live:
            idx = list(live)[rng.integers(len(live))]
            pos = int(rng.integers(0, max_seq))
            mgr.fork_for_write(idx, pos, pos + 1)
        elif op < 0.70 and live:              # retire into the session cache
            idx = list(live)[rng.integers(len(live))]
            mgr.retain_session(idx, live.pop(idx))
        elif op < 0.80:                       # pool pressure: demote LRU
            mgr.reclaim_session(int(rng.integers(1, 4)), gather)
        elif live:
            idx = list(live)[rng.integers(len(live))]
            del live[idx]
            mgr.release(idx)
        mgr.check()                           # cross-tier invariants
        _assert_group_plan_consistent(mgr)
    for idx in list(live):
        mgr.release(idx)
    mgr.reclaim_session(num_pages, gather)    # drain the session cache
    mgr.check()
    assert pool.free_pages == num_pages       # tier 0 fully reclaimed
    # whatever keys remain are demoted — every one resolves to a live slab
    assert len(ix) == len(ix.demoted_ids())
    assert ix.demoted_ids() <= tiers.ids()
    assert len(tiers) <= host_pages


# ---------------------------------------------------------------------------
# Engine: greedy outputs are bit-identical with sharing on vs off
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def smoke_model():
    cfg = configs.smoke(configs.get("qwen2-0.5b"))
    api = get_model(cfg)
    params = api.init_params(jax.random.PRNGKey(0))
    return cfg, params


def _engine(cfg, params, *, sharing, **kw):
    kw.setdefault("num_slots", 4)
    kw.setdefault("max_seq", 128)
    kw.setdefault("prefill_chunk", 16)
    kw.setdefault("page_size", 16)
    return Engine(cfg, params, cache_kind="paged",
                  prefix_sharing=sharing, **kw)


def test_shared_prefix_batch_identical_and_cheaper(smoke_model):
    """The acceptance bar: a batch sharing a (page-aligned-or-not) system
    prompt produces bit-identical greedy tokens with sharing on vs off
    AND vs the dense engine, while allocating fewer pages and skipping
    the shared prefill positions."""
    cfg, params = smoke_model
    rng = np.random.default_rng(3)
    header = rng.integers(1, cfg.vocab_size, size=40).astype(np.int32)
    prompts = [np.concatenate([
        header, rng.integers(1, cfg.vocab_size, size=n).astype(np.int32)])
        for n in (9, 23, 5, 17)]

    def reqs():
        return [(p, SamplingParams(max_new_tokens=4)) for p in prompts]

    on = _engine(cfg, params, sharing=True)
    off = _engine(cfg, params, sharing=False)
    dense = Engine(cfg, params, cache_kind="dense", num_slots=4,
                   max_seq=128, prefill_chunk=16)
    out_on = on.run(reqs())
    assert out_on == off.run(reqs()) == dense.run(reqs())
    # 40-token header = 2 full 16-token pages shared by 3 followers
    assert on.stats.shared_prefix_pages == 6
    assert on.stats.saved_prefill_tokens == 6 * 16
    assert on.stats.peak_pages_used < off.stats.peak_pages_used
    on.slots.check()
    assert on.pool.used_pages == 0 and len(on.prefix) == 0  # drained


def test_fully_covered_prompt_cow_forks_and_matches(smoke_model):
    """A later request whose page-aligned prompt is FULLY resident must
    fork the tail page (the final-chunk re-run that recovers last-token
    logits writes into a refcount-2 page) and still match sharing-off
    outputs exactly."""
    cfg, params = smoke_model
    rng = np.random.default_rng(5)
    prompt = rng.integers(1, cfg.vocab_size, size=32).astype(np.int32)
    outs = {}
    for sharing in (True, False):
        eng = _engine(cfg, params, sharing=sharing, num_slots=2)
        ra = eng.submit(prompt, SamplingParams(max_new_tokens=8))
        eng.step()            # a prefills + commits, stays resident
        rb = eng.submit(prompt, SamplingParams(max_new_tokens=8))
        while not (eng.requests[ra].finished and eng.requests[rb].finished):
            eng.step()
        outs[sharing] = {r: eng.requests[r].tokens for r in (ra, rb)}
        if sharing:
            assert eng.stats.cow_forks == 1
            assert eng.stats.shared_prefix_pages == 1   # fork dst is private
            eng.slots.check()
    assert outs[True] == outs[False]


def test_preempted_sharing_sequence_identical(smoke_model):
    """Preemption of a *sharing* sequence: its release only drops refs
    (the shared page survives through the leader), re-admission re-maps
    the surviving prefix, and greedy outputs still match a sharing-off
    run bit-exactly."""
    cfg, params = smoke_model
    rng = np.random.default_rng(7)
    header = rng.integers(1, cfg.vocab_size, size=16).astype(np.int32)
    prompts = [np.concatenate([
        header, rng.integers(1, cfg.vocab_size, size=n).astype(np.int32)])
        for n in (9, 10)]

    def reqs():
        return [(p, SamplingParams(max_new_tokens=26)) for p in prompts]

    kw = dict(num_slots=2, max_seq=80, page_size=16, prefill_chunk=16,
              num_pages=5)
    on = _engine(cfg, params, sharing=True, **kw)
    off = _engine(cfg, params, sharing=False, **kw)
    out_on = on.run(reqs())
    out_off = off.run(reqs())
    assert on.stats.preemptions > 0, "pool was never under pressure"
    assert on.stats.shared_prefix_pages > 0, "nothing was shared"
    assert out_on == out_off
    assert any(on.requests[r].preemptions > 0 for r in out_on)
    on.slots.check()
    assert on.pool.used_pages == 0             # every ref returned


def test_sharing_survives_waves_and_recycling(smoke_model):
    """More requests than slots: later admission waves must match the
    index only while the pages are alive, recycle dead pages safely, and
    stay bit-identical to sharing-off."""
    cfg, params = smoke_model
    rng = np.random.default_rng(11)
    header = rng.integers(1, cfg.vocab_size, size=32).astype(np.int32)
    prompts = [np.concatenate([
        header, rng.integers(1, cfg.vocab_size, size=n).astype(np.int32)])
        for n in (3, 19, 8, 27, 12)]

    def reqs():
        return [(p, SamplingParams(max_new_tokens=6)) for p in prompts]

    kw = dict(num_slots=2, max_seq=128)
    on = _engine(cfg, params, sharing=True, **kw)
    off = _engine(cfg, params, sharing=False, **kw)
    assert on.run(reqs()) == off.run(reqs())
    assert on.stats.shared_prefix_pages > 0
    on.slots.check()
    assert on.pool.used_pages == 0 and len(on.prefix) == 0


def test_victim_signal_tracks_live_refcounts(smoke_model):
    """exclusive_len must reflect refcounts at eviction time, not at
    admission: when the leader finishes, its follower becomes the sole
    owner of the once-shared pages and must stop looking cheap to
    evict."""
    cfg, params = smoke_model
    rng = np.random.default_rng(13)
    prompt = rng.integers(1, cfg.vocab_size, size=33).astype(np.int32)
    eng = _engine(cfg, params, sharing=True, num_slots=2)
    ra = eng.submit(prompt, SamplingParams(max_new_tokens=20))
    eng.step()
    rb = eng.submit(prompt, SamplingParams(max_new_tokens=20))
    eng.step()
    a, b = eng.requests[ra], eng.requests[rb]
    eng._refresh_shared_lens()
    assert b.shared_len == 32                 # 2 shared 16-token pages
    assert a.shared_len == 32                 # leader's copy is shared too
    eng.abort(ra)                             # leader gone: b sole owner
    eng._refresh_shared_lens()
    assert b.shared_len == 0                  # nothing shared anymore
    assert b.exclusive_len == b.total_len     # eviction reclaims it all


def test_page_aligned_prefix_collapses_pages(smoke_model):
    """A page-aligned shared prefix (two full pages) is held once however
    many requests share it: with sharing the peak page count drops by
    exactly the followers' copies of those pages, the followers skip
    their prefill, and greedy tokens equal the unshared run's."""
    cfg, params = smoke_model
    rng = np.random.default_rng(0)
    header = rng.integers(1, cfg.vocab_size, size=32).astype(np.int32)
    for n in (2, 4):
        prompts = [np.concatenate([header, rng.integers(
            1, cfg.vocab_size, size=8).astype(np.int32)]) for _ in range(n)]

        def reqs():
            return [(p, SamplingParams(max_new_tokens=4)) for p in prompts]

        on = _engine(cfg, params, sharing=True, num_slots=n)
        off = _engine(cfg, params, sharing=False, num_slots=n)
        assert on.run(reqs()) == off.run(reqs())
        assert on.stats.shared_prefix_pages == 2 * (n - 1)
        assert on.stats.saved_prefill_tokens == 32 * (n - 1)
        assert (off.stats.peak_pages_used - on.stats.peak_pages_used
                == 2 * (n - 1))
        on.slots.check()


def test_prefix_sharing_rejects_bad_configs(smoke_model):
    cfg, params = smoke_model
    with pytest.raises(ValueError, match="paged"):
        Engine(cfg, params, cache_kind="dense", prefix_sharing=True)
    with pytest.raises(ValueError, match="multiple"):
        Engine(cfg, params, cache_kind="paged", prefix_sharing=True,
               page_size=24, prefill_chunk=16)
