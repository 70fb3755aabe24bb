"""Tiered KV hierarchy: TieredPool store semantics (capacity, LRU spill,
disk round-trip, true eviction), cross-tier PrefixIndex lifecycle
(demoted entries stay matchable, only bottom-tier eviction purges),
session-cache manager dataflow (retain -> reclaim/demote -> promote at
re-admission, swap-threshold truncation), and the engine-level acceptance
bar — greedy outputs bit-identical for resumed-from-demoted vs
re-prefilled vs never-preempted sequences (dense vs paged too), through
host and disk tiers and through the eviction fallback."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models.api import get_model
from repro.models.kvlayout import PagedLayout
from repro.models.layers import LayerCtx
from repro.serving.blockpool import BlockPool, PagedSlotManager
from repro.serving.engine import Engine
from repro.serving.prefix import PrefixIndex
from repro.serving.request import SamplingParams
from repro.serving.tiers import TieredPool


# ---------------------------------------------------------------------------
# TieredPool store semantics (no jax, dummy slabs)
# ---------------------------------------------------------------------------


def _slab(tag):
    """A dummy page slab: content identity matters, structure does not."""
    return (np.full((2, 4), tag, np.float32), np.full((2, 4), -tag, np.int32))


def test_tiered_pool_rejects_bad_capacities(tmp_path):
    with pytest.raises(ValueError, match=">= 0"):
        TieredPool(-1)
    with pytest.raises(ValueError, match="disk_dir"):
        TieredPool(4, disk_pages=2)          # disk capacity without a dir
    # a disk_dir without disk_pages is simply an unused tier
    tp = TieredPool(2, disk_dir=str(tmp_path))
    assert tp.disk_pages == 0


def test_host_tier_lru_spill_evicts_oldest():
    tp = TieredPool(2)
    a, b, c = tp.demote(_slab(1)), tp.demote(_slab(2)), tp.demote(_slab(3))
    # no disk behind the host tier: the LRU slab fell off the bottom
    assert tp.host_used == 2 and len(tp) == 2
    assert tp.ids() == {b, c}
    assert tp.stats.demoted == 3 and tp.stats.evicted == 1
    with pytest.raises(KeyError):
        tp.tier_of(a)
    tp.check()


def test_touch_refreshes_lru_recency():
    tp = TieredPool(2)
    a, _b = tp.demote(_slab(1)), tp.demote(_slab(2))
    tp.touch(a)                              # a becomes most-recently-used
    c = tp.demote(_slab(3))                  # spills b, not a
    assert tp.ids() == {a, c}
    tp.check()


def test_zero_capacity_hierarchy_rejects_demotion():
    tp = TieredPool(0)
    assert tp.demote(_slab(1)) is None       # caller treats as true eviction
    assert tp.stats.demoted == 0
    tp.check()


def test_disk_tier_round_trips_exact_bytes(tmp_path):
    tp = TieredPool(1, disk_dir=str(tmp_path), disk_pages=2)
    a = tp.demote(_slab(7))
    b = tp.demote(_slab(8))                  # spills a host -> disk
    assert tp.tier_of(a) == 2 and tp.tier_of(b) == 1
    assert tp.stats.disk_demotions == 1 and tp.stats.evicted == 0
    tp.check()
    slab = tp.pop(a)                         # promote off disk
    for got, want in zip(slab, _slab(7)):
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(got, want)
    assert tp.disk_used == 0 and not list(tmp_path.iterdir())
    assert tp.stats.promoted == 1
    tp.check()


def test_quantized_slab_disk_round_trip_bitwise(tmp_path):
    """Quantized pages demote as (codes, codes, scales, scales) tuples;
    the disk tier must return every leaf bit-exact with dtypes intact —
    int8 codes may not silently widen, f32 scale rows may not re-round."""
    def qslab(seed):
        r = np.random.default_rng(seed)
        return (r.integers(-127, 128, size=(2, 4, 2, 8)).astype(np.int8),
                r.integers(-127, 128, size=(2, 4, 2, 8)).astype(np.int8),
                r.random((2, 2)).astype(np.float32),
                r.random((2, 2)).astype(np.float32))
    tp = TieredPool(1, disk_dir=str(tmp_path), disk_pages=2)
    a = tp.demote(qslab(7))
    tp.demote(qslab(8))                      # spills a host -> disk
    assert tp.tier_of(a) == 2
    got = tp.pop(a)                          # promote off disk
    for g, w in zip(got, qslab(7)):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(g, w)
    tp.check()


def test_disk_tier_full_evicts_oldest_file(tmp_path):
    tp = TieredPool(1, disk_dir=str(tmp_path), disk_pages=1)
    a = tp.demote(_slab(1))
    b = tp.demote(_slab(2))                  # a -> disk
    c = tp.demote(_slab(3))                  # b -> disk, a falls off
    assert tp.ids() == {b, c}
    assert tp.stats.evicted == 1
    assert len(list(tmp_path.iterdir())) == 1    # one slab file on disk
    tp.check()


def test_pop_and_drop_from_either_tier(tmp_path):
    tp = TieredPool(1, disk_dir=str(tmp_path), disk_pages=4)
    a = tp.demote(_slab(1))
    b = tp.demote(_slab(2))                  # a spilled to disk
    assert tp.pop(b) is not None             # pop from host
    tp.drop(a)                               # drop from disk: file removed
    assert len(tp) == 0 and not list(tmp_path.iterdir())
    assert tp.stats.promoted == 1            # drop is not a promotion
    with pytest.raises(KeyError):
        tp.pop(a)
    tp.check()


# ---------------------------------------------------------------------------
# Cross-tier PrefixIndex lifecycle
# ---------------------------------------------------------------------------


def test_index_entry_survives_demotion_and_promotes_back():
    ix = PrefixIndex(page_size=2)
    ix.register([1, 2, 3, 4], pages=[5, 6])
    ix.commit([1, 2, 3, 4])
    assert ix.demote_page(6, hid=0)          # page freed, slab lives on
    m = ix.match([1, 2, 3, 4])
    assert m.pages == [5, -1]                # demoted placeholder
    assert m.tiers == [0, 1] and m.hids == [None, 0]
    ix.check(live_pages={5}, live_hids={0})
    ix.promote_hid(0, page=9)                # fresh tier-0 page uploaded
    m = ix.match([1, 2, 3, 4])
    assert m.pages == [5, 9] and m.tiers == [0, 0]
    ix.check(live_pages={5, 9})


def test_index_demote_unindexed_page_is_noop():
    ix = PrefixIndex(page_size=2)
    assert not ix.demote_page(3, hid=0)
    assert ix.demoted_ids() == set()


def test_index_set_tier_and_rebind_track_store_moves():
    ix = PrefixIndex(page_size=2)
    ix.register([1, 2], pages=[4])
    ix.commit([1, 2])
    ix.demote_page(4, hid=0)
    ix.set_tier(0, 2)                        # host -> disk spill
    assert ix.match([1, 2]).tiers == [2]
    ix.rebind_hid(0, 5)                      # aborted promotion, new handle
    assert ix.match([1, 2]).hids == [5]
    ix.check(live_pages=set(), live_hids={5})


def test_index_purges_only_on_true_eviction():
    ix = PrefixIndex(page_size=2)
    ix.register([1, 2], pages=[4])
    ix.commit([1, 2])
    ix.demote_page(4, hid=0)
    assert len(ix.match([1, 2])) == 1        # demotion alone keeps the key
    ix.purge_hid(0)                          # slab fell off the bottom
    assert ix.match([1, 2]).pages == []
    assert len(ix) == 0
    ix.check(live_pages=set())


# ---------------------------------------------------------------------------
# Session-cache manager dataflow (dummy gather, no jax)
# ---------------------------------------------------------------------------


def _gather(pages):
    return {p: ("slab", p) for p in pages}


def _tiered_mgr(num_pages=8, page_size=4, host_pages=8):
    pool = BlockPool(num_pages, page_size)
    ix = PrefixIndex(page_size)
    tiers = TieredPool(host_pages, index=ix)
    mgr = PagedSlotManager(3, 32, pool, prefix_index=ix, tiers=tiers)
    return mgr, pool, tiers


def test_tiers_require_prefix_index():
    pool = BlockPool(8, 4)
    with pytest.raises(ValueError, match="prefix index"):
        PagedSlotManager(2, 32, pool, tiers=TieredPool(4))


def test_retain_session_transfers_refs_instead_of_freeing():
    mgr, pool, _ = _tiered_mgr()
    toks = np.arange(100, 109, dtype=np.int32)          # 2 full pages
    idx = mgr.try_assign(0, 9, 4, tokens=toks)
    mgr.commit_prefix(idx, toks)
    full = mgr.slots[idx].pages[:2]
    assert mgr.retain_session(idx, toks) == 2
    assert mgr.slots[idx].free                          # slot released...
    assert all(pool.refcount(p) == 1 for p in full)     # ...pages retained
    assert mgr.session_pages() == 2
    assert mgr.prefix.match(toks).pages == full         # still matchable
    mgr.check()


def test_session_rehit_maps_pages_without_copies():
    mgr, pool, tiers = _tiered_mgr()
    toks = np.arange(100, 109, dtype=np.int32)
    idx = mgr.try_assign(0, 9, 4, tokens=toks)
    mgr.commit_prefix(idx, toks)
    mgr.retain_session(idx, toks)
    idx2 = mgr.try_assign(1, 9, 4, tokens=toks)         # returning session
    s = mgr.slots[idx2]
    assert s.shared_len == 8 and s.session_mapped == 2
    assert not s.pending_promotions                     # tier-0 rehit: no copy
    assert tiers.stats.demoted == 0
    mgr.check()


def test_reclaim_session_demotes_dying_pages_and_keeps_index():
    mgr, pool, tiers = _tiered_mgr()
    toks = np.arange(100, 109, dtype=np.int32)
    idx = mgr.try_assign(0, 9, 4, tokens=toks)
    mgr.commit_prefix(idx, toks)
    mgr.retain_session(idx, toks)
    freed = mgr.reclaim_session(1, _gather)             # LRU-first, 1 page
    assert freed == 1 and mgr.session_pages() == 1
    assert tiers.stats.demoted == 1
    m = mgr.prefix.match(toks)
    assert m.tiers == [1, 0]                            # first chunk demoted
    mgr.check()
    freed = mgr.reclaim_session(10, _gather)            # drain the rest
    assert freed == 1 and mgr.session_pages() == 0
    assert pool.used_pages == 0
    assert mgr.prefix.match(toks).tiers == [1, 1]       # both still matchable
    mgr.check()


def test_reclaim_spares_pages_shared_with_live_slots():
    mgr, pool, _ = _tiered_mgr()
    toks = np.arange(100, 109, dtype=np.int32)
    a = mgr.try_assign(0, 9, 4, tokens=toks)
    mgr.commit_prefix(a, toks)
    b = mgr.try_assign(1, 9, 4, tokens=toks)            # shares both pages
    mgr.retain_session(a, toks)                         # refcount 2 each
    shared = mgr.slots[b].pages[:2]
    mgr.reclaim_session(10, _gather)
    # session refs dropped, but b keeps the pages alive — no demotion
    assert all(pool.refcount(p) == 1 for p in shared)
    assert mgr.prefix.match(toks).tiers == [0, 0]
    mgr.check()


def test_returning_admission_promotes_demoted_span():
    mgr, pool, tiers = _tiered_mgr()
    toks = np.arange(100, 109, dtype=np.int32)
    idx = mgr.try_assign(0, 9, 4, tokens=toks)
    mgr.commit_prefix(idx, toks)
    mgr.retain_session(idx, toks)
    mgr.reclaim_session(10, _gather)                    # both pages host-side
    idx2 = mgr.try_assign(1, 9, 4, tokens=toks)
    s = mgr.slots[idx2]
    assert s.shared_len == 8 and len(s.pending_promotions) == 2
    assert tiers.stats.promoted == 2 and len(tiers) == 0
    # the index is rebound onto the fresh tier-0 destinations
    m = mgr.prefix.match(toks)
    assert m.tiers == [0, 0]
    assert m.pages == [dst for _slab, dst in s.pending_promotions]
    mgr.check()


def test_swap_threshold_truncates_match_at_first_demoted_entry():
    mgr, pool, tiers = _tiered_mgr()
    mgr.swap_threshold = 64                             # promotion never wins
    toks = np.arange(100, 109, dtype=np.int32)
    idx = mgr.try_assign(0, 9, 4, tokens=toks)
    mgr.commit_prefix(idx, toks)
    mgr.retain_session(idx, toks)
    mgr.reclaim_session(1, _gather)                     # first chunk demoted
    idx2 = mgr.try_assign(1, 9, 4, tokens=toks)
    s = mgr.slots[idx2]
    # tier-0 match truncates at the demoted first chunk: nothing shared,
    # nothing promoted — those positions re-prefill
    assert s.shared_len == 0 and not s.pending_promotions
    assert tiers.stats.promoted == 0
    mgr.check()


def test_dry_admission_reclaims_session_via_callback():
    mgr, pool, tiers = _tiered_mgr(num_pages=4)
    mgr.reclaim_cb = lambda need: mgr.reclaim_session(need, _gather) >= need
    toks = np.arange(100, 109, dtype=np.int32)          # 3 pages w/ headroom
    idx = mgr.try_assign(0, 9, 4, tokens=toks)
    mgr.commit_prefix(idx, toks)
    mgr.retain_session(idx, toks)                       # 2 pages cached
    other = np.arange(200, 212, dtype=np.int32)         # needs 4 fresh pages
    idx2 = mgr.try_assign(1, 12, 4, tokens=other)
    assert idx2 is not None                             # cache lost the fight
    assert tiers.stats.demoted == 2                     # demoted, not lost
    assert mgr.session_pages() == 0
    mgr.check()


# ---------------------------------------------------------------------------
# Engine: resume/returning bit-identity through every tier
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def smoke_model():
    cfg = configs.smoke(configs.get("qwen2-0.5b"))
    api = get_model(cfg)
    params = api.init_params(jax.random.PRNGKey(0))
    return cfg, params


def _engine(cfg, params, **kw):
    kw.setdefault("num_slots", 4)
    kw.setdefault("max_seq", 256)
    kw.setdefault("prefill_chunk", 16)
    kw.setdefault("page_size", 16)
    kw.setdefault("cache_kind", "paged")
    kw.setdefault("prefix_sharing", True)
    return Engine(cfg, params, **kw)


def _reqs(cfg, n=3, plen=40, max_new=6, seed=17):
    rng = np.random.default_rng(seed)
    return [(rng.integers(1, cfg.vocab_size, size=plen).astype(np.int32),
             SamplingParams(max_new_tokens=max_new)) for _ in range(n)]


def _rerun(reqs):
    return [(p.copy(), s) for p, s in reqs]


def _toks(out):
    """Outputs in submission order — rids auto-increment across runs on
    one engine, so dicts from different runs never key-compare equal."""
    return [out[k] for k in sorted(out)]


def test_engine_rejects_tiered_misconfig(smoke_model):
    cfg, params = smoke_model
    with pytest.raises(ValueError, match="paged"):
        Engine(cfg, params, cache_kind="dense", host_pages=8)
    with pytest.raises(ValueError, match="prefix_sharing"):
        Engine(cfg, params, cache_kind="paged", host_pages=8,
               prefix_sharing=False)


def test_returning_conversation_promotes_and_matches(smoke_model):
    """The tentpole invariant, host tier: flush the session cache
    host-ward, resubmit the same prompts — the rerun promotes the
    demoted pages and produces bit-identical greedy tokens vs an engine
    that discarded everything (and vs dense)."""
    cfg, params = smoke_model
    reqs = _reqs(cfg)

    base = _engine(cfg, params)
    out_base = _toks(base.run(_rerun(reqs)))

    dense = Engine(cfg, params, cache_kind="dense", num_slots=4,
                   max_seq=256, prefill_chunk=16)
    assert _toks(dense.run(_rerun(reqs))) == out_base

    eng = _engine(cfg, params, host_pages=64)
    assert _toks(eng.run(_rerun(reqs))) == out_base
    eng.evict_finished(flush=True)                      # force off-device
    assert eng.tiers.host_used > 0
    assert eng.pool.used_pages == 0
    assert _toks(eng.run(_rerun(reqs))) == out_base            # returning turn
    assert eng.stats.promoted_pages > 0
    assert eng.stats.demoted_pages > 0
    assert eng.stats.saved_prefill_tokens > 0
    eng.slots.check()


def test_tier0_session_rehit_skips_prefill_without_copies(smoke_model):
    """Retire without flushing: the rerun re-maps resident tier-0 pages
    by refcount bump (session hit) — no promotion traffic at all."""
    cfg, params = smoke_model
    reqs = _reqs(cfg, seed=19)
    base = _engine(cfg, params)
    out = _toks(base.run(_rerun(reqs)))
    eng = _engine(cfg, params, host_pages=64)
    eng.run(_rerun(reqs))
    eng.evict_finished()                                # keep KV on device
    assert eng.slots.session_pages() > 0
    assert _toks(eng.run(_rerun(reqs))) == out
    assert eng.stats.session_hits > 0
    assert eng.stats.promoted_pages == 0
    eng.slots.check()


def test_preempted_resume_identical_through_tiers(smoke_model):
    """Mid-decode preemption under a tight pool with tiers attached:
    victims demote instead of freeing, resumption promotes (or rehits),
    and outputs match a pool that never preempts, a tight pool that
    re-prefills, and the dense engine bit-exactly."""
    cfg, params = smoke_model
    rng = np.random.default_rng(23)
    sp = SamplingParams(max_new_tokens=40)              # forces lazy growth
    reqs = [(rng.integers(1, cfg.vocab_size, size=40).astype(np.int32), sp)
            for _ in range(4)]

    big = _engine(cfg, params, num_pages=64)
    out_big = _toks(big.run(_rerun(reqs), max_ticks=3000))

    tight = _engine(cfg, params, num_pages=9)
    assert _toks(tight.run(_rerun(reqs), max_ticks=3000)) == out_big
    assert tight.stats.preemptions > 0, "pool was never under pressure"

    tiers = _engine(cfg, params, num_pages=9, host_pages=64)
    assert _toks(tiers.run(_rerun(reqs), max_ticks=3000)) == out_big
    assert tiers.stats.preemptions > 0
    assert tiers.stats.demoted_pages > 0, "preemption never demoted"
    tiers.slots.check()


def test_disk_tier_resume_identical(smoke_model, tmp_path):
    """A host tier too small for the flushed sessions spills to disk;
    the returning turn reads the slabs back bit-exactly."""
    cfg, params = smoke_model
    reqs = _reqs(cfg, n=2, seed=29)
    base = _engine(cfg, params)
    out = _toks(base.run(_rerun(reqs)))
    eng = _engine(cfg, params, host_pages=2, disk_dir=str(tmp_path),
                  disk_pages=16)
    eng.run(_rerun(reqs))
    eng.evict_finished(flush=True)
    assert eng.tiers.stats.disk_demotions > 0
    assert _toks(eng.run(_rerun(reqs))) == out
    eng.slots.check()


def test_quantized_pages_demote_promote_bitwise(smoke_model, tmp_path):
    """int8 KV pages flushed through the host+disk tiers come back with
    the exact quantized representation: the tiered store moves the codes
    (int8 slabs) and their f32 scale rows as opaque bytes, so after
    promotion every prefix page is bitwise identical to its pre-demotion
    self — and greedy outputs match a never-demoted int8 engine."""
    cfg, params = smoke_model
    reqs = _reqs(cfg, n=2, seed=43)
    base = _engine(cfg, params, kv_dtype="int8")
    out = _toks(base.run(_rerun(reqs)))

    eng = _engine(cfg, params, kv_dtype="int8", host_pages=2,
                  disk_dir=str(tmp_path), disk_pages=16)
    assert _toks(eng.run(_rerun(reqs))) == out

    def snapshot():
        """Resident prefix pages' slabs keyed by their token chunk."""
        ent = eng.prefix._entries
        keys = sorted(k for k in ent if ent[k].page is not None)
        pages = [ent[k].page for k in keys]
        slabs = eng._gather_pages(pages)
        return {k: slabs[p] for k, p in zip(keys, pages)}

    before = snapshot()
    assert before, "run registered no prefix pages"
    leaves = next(iter(before.values()))
    assert any(a.dtype == np.int8 for a in leaves), "no quantized codes"
    assert any(a.dtype == np.float32 for a in leaves), "no scale rows"

    eng.evict_finished(flush=True)
    assert eng.tiers.stats.disk_demotions > 0

    assert _toks(eng.run(_rerun(reqs))) == out   # promotes the span back
    assert eng.stats.promoted_pages > 0
    after = snapshot()
    assert sorted(after) == sorted(before)
    for k in before:
        for g, w in zip(after[k], before[k]):
            assert g.dtype == w.dtype
            np.testing.assert_array_equal(g, w)
    eng.slots.check()


def _split_storage(spec, device):
    """Stands in for a device on which ``PagedLayout.device_storage``
    splits every pool's page axis in two: ``(L, NP/2, 2, ...)``."""
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        (s.shape[0], s.shape[1] // 2, 2, *s.shape[2:]), s.dtype), spec)


@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_split_pool_storage_serves_identically(smoke_model, monkeypatch,
                                               kv):
    """A pool stored with its page axis split, as a TPU's storage is for
    a head_dim under 128 lanes, serves exactly as the logical pool: the
    same greedy tokens through prefix sharing, COW forks, demotion and
    promotion, the same bytes in every page, and the model's own steps,
    called on the engine's cache, give the same logits and pool."""
    cfg, params = smoke_model
    reqs = _reqs(cfg, seed=47)
    reqs += [(reqs[0][0][:32].copy(), reqs[0][1])]   # page-aligned: forks
    base = _engine(cfg, params, kv_dtype=kv, host_pages=64)
    monkeypatch.setattr(PagedLayout, "device_storage",
                        staticmethod(_split_storage))
    eng = _engine(cfg, params, kv_dtype=kv, host_pages=64)
    for name, a in eng.cache.items():
        assert a.shape == _split_storage(base.cache[name], None).shape
    for e in (base, eng):
        e.outs = [_toks(e.run(_rerun(reqs)))]
        e.evict_finished(flush=True)
        e.outs.append(_toks(e.run(_rerun(reqs))))    # promotes back
    assert eng.outs == base.outs
    assert eng.stats.cow_forks > 0 and eng.stats.promoted_pages > 0
    assert (eng.stats.cow_forks, eng.stats.promoted_pages) == (
        base.stats.cow_forks, base.stats.promoted_pages)

    def logical(cache):
        return {n: np.asarray(a).reshape(base.cache[n].shape)
                for n, a in cache.items()}

    want = logical(base.cache)
    for n, a in logical(eng.cache).items():
        np.testing.assert_array_equal(a, want[n])

    # a caller holding the engine's cache steps the model on it directly
    api, ctx = get_model(cfg), LayerCtx(cfg=cfg, plan=eng.plan)
    table = jnp.arange(16, dtype=jnp.int32).reshape(4, 4)
    lengths = jnp.asarray([20, 33, 5, 47], jnp.int32)
    toks = jnp.arange(1, 5, dtype=jnp.int32)
    outs = [api.decode_step(ctx, params, toks, e.cache, lengths,
                            block_tables=table) for e in (base, eng)]
    np.testing.assert_array_equal(np.asarray(outs[1][0]),
                                  np.asarray(outs[0][0]))
    for n, a in logical(outs[1][1]).items():
        np.testing.assert_array_equal(a, np.asarray(outs[0][1][n]))


def test_eviction_fallback_reprefills_identically(smoke_model):
    """A hierarchy with almost no capacity truly evicts: the purged keys
    stop matching and the rerun silently pays full re-prefill — same
    tokens, just no savings."""
    cfg, params = smoke_model
    reqs = _reqs(cfg, n=2, seed=31)
    base = _engine(cfg, params)
    out = _toks(base.run(_rerun(reqs)))
    eng = _engine(cfg, params, host_pages=1)
    eng.run(_rerun(reqs))
    eng.evict_finished(flush=True)
    assert eng.stats.host_evicted_pages > 0
    assert _toks(eng.run(_rerun(reqs))) == out
    eng.slots.check()


def test_session_cache_off_frees_on_retire(smoke_model):
    """session_cache=False keeps demotion for preemption only: retire
    frees pages as before and the rerun re-prefills from scratch."""
    cfg, params = smoke_model
    reqs = _reqs(cfg, n=2, seed=37)
    base = _engine(cfg, params)
    out = _toks(base.run(_rerun(reqs)))
    eng = _engine(cfg, params, host_pages=64, session_cache=False)
    eng.run(_rerun(reqs))
    eng.evict_finished()
    assert eng.slots.session_pages() == 0
    assert eng.pool.used_pages == 0
    assert _toks(eng.run(_rerun(reqs))) == out
    assert eng.stats.session_hits == 0


def test_flush_sessions_accounts_stats(smoke_model):
    cfg, params = smoke_model
    eng = _engine(cfg, params, host_pages=64)
    eng.run(_rerun(_reqs(cfg, n=2, seed=41)))
    cached = eng.slots.session_pages()
    assert cached > 0
    assert eng.flush_sessions() == cached
    assert eng.stats.demoted_pages == cached
    assert eng.slots.session_pages() == 0
    assert eng.pool.used_pages == 0
    eng.slots.check()


def test_tiers_bench_smoke(tmp_path, monkeypatch):
    """CI wiring: the tiers benchmark runs at smoke sizes, emits a
    well-formed BENCH_tiers.json, and shows the two headline results —
    a warm-session TTFT win and a sane swap-vs-re-prefill crossover."""
    from benchmarks import kv_tiers
    monkeypatch.setattr(kv_tiers, "OUT_PATH",
                        str(tmp_path / "BENCH_tiers.json"))
    result = kv_tiers.run(quick=True)
    assert (tmp_path / "BENCH_tiers.quick.json").exists()
    assert not (tmp_path / "BENCH_tiers.json").exists()
    for row in result["ttft"]:
        assert row["speedup"] > 1.0, "session cache must beat re-prefill"
        assert row["promoted_pages"] > 0
        assert row["saved_prefill_tokens"] > 0
    assert result["identity"]["identical"]
    for arch in result["crossover"]:
        assert arch["swap_threshold"] >= 1
        for pt in arch["curve"]:
            assert pt["swap_s"] > 0 and pt["reprefill_s"] > 0
