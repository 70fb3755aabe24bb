"""Block-paged KV cache: pool/free-list invariants (property-based via the
hypothesis shim), lazy growth, block-table consistency, and paged-vs-dense
engine equivalence through the unified KVLayout path — greedy outputs must
be token-identical, including runs where overcommit forces preemption and
re-admission recycles pages."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro import configs
from repro.core.plan import make_plan
from repro.kernels.group_attention import DecodeGroups
from repro.models import stack
from repro.models.api import get_model
from repro.models.kvlayout import DenseLayout, PagedLayout, pages_for
from repro.models.layers import LayerCtx
from repro.serving.blockpool import BlockPool, PagedSlotManager
from repro.serving.engine import Engine
from repro.serving.request import SamplingParams

settings.register_profile("fast", max_examples=20, deadline=None)
settings.load_profile("fast")


# ---------------------------------------------------------------------------
# KVLayout: the one shape/addressing descriptor both cache kinds share
# ---------------------------------------------------------------------------


def test_kvlayout_shapes_and_operands():
    dense = DenseLayout(num_slots=4, max_seq=256)
    paged = PagedLayout(num_pages=16, page_size=64)
    assert dense.kv_shape(2, 8, 64) == (2, 4, 256, 8, 64)
    assert paged.kv_shape(2, 8, 64) == (2, 16, 64, 8, 64)
    assert not dense.is_paged and paged.is_paged
    assert paged.pages_for(0) == 0
    assert paged.pages_for(64) == 1
    assert paged.pages_for(65) == 2
    cfg = configs.smoke(configs.get("qwen2-0.5b"))
    api = get_model(cfg)
    for layout in (dense, PagedLayout(8, 32)):
        spec = api.cache_spec(layout)
        cache = api.init_cache(layout)
        assert jax.tree.map(lambda s: s.shape, spec) == \
            jax.tree.map(lambda a: a.shape, cache)


def test_recurrent_family_rejects_paged_layout():
    cfg = configs.smoke(configs.get("rwkv6-1.6b"))
    api = get_model(cfg)
    assert not api.supports_paged
    with pytest.raises(ValueError):
        api.init_cache(PagedLayout(8, 32))


# ---------------------------------------------------------------------------
# BlockPool unit behavior
# ---------------------------------------------------------------------------


def test_blockpool_alloc_free_conservation():
    pool = BlockPool(num_pages=8, page_size=16)
    a = pool.alloc(3)
    b = pool.alloc(5)
    assert pool.free_pages == 0 and pool.used_pages == 8
    assert set(a) | set(b) == set(range(8)) and not set(a) & set(b)
    assert pool.alloc(1) is None            # exhausted, not an exception
    pool.free(a)
    assert pool.free_pages == 3
    c = pool.alloc(2)
    assert set(c) <= set(a)                 # freed pages are reused
    pool.check()


def test_blockpool_double_free_raises():
    pool = BlockPool(num_pages=4, page_size=8)
    a = pool.alloc(2)
    pool.free(a)
    with pytest.raises(ValueError):
        pool.free(a)
    with pytest.raises(ValueError):
        pool.free([99])                      # foreign page


def test_blockpool_share_and_refcounted_free():
    """A shared page is freed only when its LAST owner lets go: free()
    decrements, reports exactly the pages that died, and keeps shared
    pages allocated (no page freed while refcount > 0)."""
    pool = BlockPool(num_pages=4, page_size=8)
    a = pool.alloc(2)
    assert [pool.refcount(p) for p in a] == [1, 1]
    pool.share(a)                            # second owner
    assert [pool.refcount(p) for p in a] == [2, 2]
    assert pool.free(a) == []                # nobody died
    assert pool.used_pages == 2 and pool.free_pages == 2
    pool.check()
    dead = pool.free(a)                      # last owner
    assert sorted(dead) == sorted(a)
    assert pool.free_pages == 4
    with pytest.raises(ValueError):
        pool.free(a)                         # refcount 0 = foreign again
    with pytest.raises(ValueError):
        pool.share([a[0]])                   # cannot share a free page
    pool.check()


def test_blockpool_total_refs_counts_sharing():
    pool = BlockPool(num_pages=4, page_size=8)
    a = pool.alloc(3)
    pool.share(a[:2])
    # 3 physical pages stand in for 5 share-less allocations
    assert pool.used_pages == 3 and pool.total_refs == 5
    pool.check()


def test_blockpool_pages_for():
    pool = BlockPool(num_pages=4, page_size=16)
    assert pool.pages_for(0) == 0
    assert pool.pages_for(1) == 1
    assert pool.pages_for(16) == 1
    assert pool.pages_for(17) == 2


# ---------------------------------------------------------------------------
# PagedSlotManager: lazy admission + ensure()-growth; random lifecycles
# keep every cross-structure invariant (no double allocation, free-list
# conservation, block-table <-> pool consistency)
# ---------------------------------------------------------------------------


def test_lazy_admission_reserves_prefill_footprint_plus_headroom():
    pool = BlockPool(num_pages=8, page_size=8)
    mgr = PagedSlotManager(2, max_seq=64, pool=pool)
    idx = mgr.try_assign(0, prompt_len=20, max_new=30)
    assert idx is not None
    # prefill footprint (3 pages) + one decode growth page — NOT the
    # worst-case ceil(50/8) = 7
    assert pool.used_pages == pages_for(20, 8) + 1   # 4
    # growth is page-at-a-time through ensure()
    assert mgr.ensure(idx, 32)                        # inside headroom
    assert pool.used_pages == 4
    assert mgr.ensure(idx, 33)                        # crosses into page 5
    assert pool.used_pages == 5
    mgr.check()


def test_lazy_admission_headroom_capped_at_total_footprint():
    pool = BlockPool(num_pages=8, page_size=8)
    mgr = PagedSlotManager(2, max_seq=64, pool=pool)
    # prompt+max_new fits the prefill pages exactly: no headroom page
    idx = mgr.try_assign(0, prompt_len=14, max_new=2)   # 16 pos = 2 pages
    assert idx is not None
    assert pool.used_pages == 2
    mgr.check()


def test_ensure_reports_dry_pool_without_corrupting_state():
    pool = BlockPool(num_pages=4, page_size=8)
    mgr = PagedSlotManager(2, max_seq=32, pool=pool)
    a = mgr.try_assign(0, prompt_len=16, max_new=8)   # 2 + headroom = 3
    b = mgr.try_assign(1, prompt_len=4, max_new=1)    # 1 page (capped)
    assert a is not None and b is not None
    assert pool.free_pages == 0
    assert not mgr.ensure(a, 25)                      # pool dry
    mgr.check()                                       # nothing leaked
    mgr.release(b)                                    # preemption mechanics
    assert mgr.ensure(a, 25)                          # freed page picked up
    mgr.check()


@given(st.integers(0, 10_000))
def test_paged_manager_random_lifecycle(seed):
    rng = np.random.default_rng(seed)
    page_size = int(rng.choice([4, 8, 16]))
    num_pages = int(rng.integers(4, 40))
    num_slots = int(rng.integers(1, 6))
    max_seq = page_size * max(2, num_pages // max(num_slots, 1))
    pool = BlockPool(num_pages, page_size)
    mgr = PagedSlotManager(num_slots, max_seq, pool)
    live: list[int] = []
    rid = 0
    for _ in range(40):
        op = rng.random()
        if op < 0.4:
            prompt = int(rng.integers(1, max(max_seq // 2, 2)))
            max_new = int(rng.integers(1, max_seq - prompt + 1))
            if pages_for(prompt + max_new, page_size) > num_pages:
                continue                      # would raise by contract
            idx = mgr.try_assign(rid, prompt, max_new)
            if idx is not None:
                assert idx not in live, "slot double-assigned"
                live.append(idx)
                rid += 1
        elif op < 0.6 and live:
            idx = live[rng.integers(len(live))]
            # lazy growth to a random target; failure must be side-effect
            # free (the preempt-and-retry contract)
            mgr.ensure(idx, int(rng.integers(1, max_seq + 1)))
        elif op < 0.8 and live:
            idx = live[rng.integers(len(live))]
            mgr.tick(idx, wrote_kv=bool(rng.random() < 0.9))
        elif live:
            idx = live.pop(rng.integers(len(live)))
            mgr.release(idx)
        mgr.check()                          # invariants after every op
    for idx in live:
        mgr.release(idx)
    mgr.check()
    assert pool.free_pages == num_pages      # everything returned


def test_block_tables_sentinel_and_ownership():
    pool = BlockPool(num_pages=16, page_size=8)
    mgr = PagedSlotManager(3, max_seq=64, pool=pool)
    a = mgr.try_assign(0, prompt_len=20, max_new=4)   # 3 pages (capped)
    b = mgr.try_assign(1, prompt_len=5, max_new=3)    # 1 page (capped)
    assert a is not None and b is not None
    bt = np.asarray(mgr.block_tables())                # cached device array
    assert bt.shape == (3, 8)                          # 64 / 8 logical blocks
    pages_a = set(bt[a][bt[a] < pool.num_pages])
    pages_b = set(bt[b][bt[b] < pool.num_pages])
    assert len(pages_a) == 3 and len(pages_b) == 1
    assert not pages_a & pages_b                       # disjoint ownership
    # unassigned entries (and the whole free slot row) hold the sentinel
    free_row = ({0, 1, 2} - {a, b}).pop()
    assert (bt[free_row] == pool.num_pages).all()
    mgr.release(a)
    assert pool.free_pages == 16 - 1


def test_block_tables_cached_until_invalidated():
    """The dense block-table operand is device-cached: unchanged tables
    return the *same* array object tick after tick (so the jitted decode
    step reuses a device-resident operand instead of re-uploading), and
    every mutation path — lazy growth, release, fresh assignment —
    invalidates it."""
    pool = BlockPool(num_pages=16, page_size=8)
    mgr = PagedSlotManager(2, max_seq=64, pool=pool)
    a = mgr.try_assign(0, prompt_len=9, max_new=20)    # 2 pages + headroom
    bt0 = mgr.block_tables()
    assert mgr.block_tables() is bt0                   # steady state: cached
    mgr.tick(a)                                        # bookkeeping only
    assert mgr.block_tables() is bt0
    assert mgr.ensure(a, 24)                           # inside owned pages
    assert mgr.block_tables() is bt0                   # no table change
    assert mgr.ensure(a, 25)                           # grew one page
    bt1 = mgr.block_tables()
    assert bt1 is not bt0
    assert np.asarray(bt1)[a][3] < pool.num_pages      # new page visible
    mgr.release(a)
    assert mgr.block_tables() is not bt1               # release invalidates
    b = mgr.try_assign(1, prompt_len=5, max_new=3)
    assert b is not None
    assert (np.asarray(mgr.block_tables())[b] < pool.num_pages).sum() == 1


def test_paged_manager_rejects_request_larger_than_pool():
    """A request whose worst-case page footprint exceeds the whole
    (overcommitted) pool must raise at admission, not lazily admit — once
    it ran alone there would be no preemptable victim for its guaranteed
    mid-decode growth failure (livelock)."""
    mgr = PagedSlotManager(1, max_seq=512, pool=BlockPool(2, 64))
    with pytest.raises(ValueError):
        mgr.try_assign(0, prompt_len=200, max_new=100)  # needs 5 > 2 pages


def test_paged_manager_admission_blocks_on_pool_not_slots():
    # plenty of slots, tiny pool: admission must wait on pages
    pool = BlockPool(num_pages=2, page_size=8)
    mgr = PagedSlotManager(4, max_seq=16, pool=pool)
    assert mgr.try_assign(0, prompt_len=15, max_new=1) is not None  # 2 pages
    assert mgr.try_assign(1, prompt_len=1, max_new=1) is None       # no pages
    mgr.release(0)
    assert mgr.try_assign(1, prompt_len=1, max_new=1) is not None


# ---------------------------------------------------------------------------
# Engine equivalence through the unified KVLayout path: paged greedy decode
# is token-identical to dense — with page recycling AND forced preemption
# ---------------------------------------------------------------------------


def _engines(arch, *, page_size=32, num_pages=None, scheduler="fcfs", **kw):
    cfg = configs.smoke(configs.get(arch))
    api = get_model(cfg)
    params = api.init_params(jax.random.PRNGKey(0))
    dense = Engine(cfg, params, cache_kind="dense", **kw)
    paged = Engine(cfg, params, cache_kind="paged", page_size=page_size,
                   num_pages=num_pages, scheduler=scheduler, **kw)
    return cfg, dense, paged


@pytest.mark.parametrize(
    "arch", ["qwen2-0.5b",
             pytest.param("dbrx-132b", marks=pytest.mark.slow)])
def test_paged_engine_token_identical_to_dense(arch):
    """Greedy outputs match across cache kinds, through a workload where
    5 requests share 2 slots — finished sequences release their pages and
    re-admitted requests recycle them mid-run."""
    cfg, dense, paged = _engines(arch, num_slots=2, max_seq=256,
                                 prefill_chunk=32)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, cfg.vocab_size, size=n).astype(np.int32)
               for n in (9, 23, 70, 5)]

    def reqs():
        return [(p, SamplingParams(max_new_tokens=4)) for p in prompts]

    out_dense = dense.run(reqs())
    out_paged = paged.run(reqs())
    assert out_dense == out_paged
    # every page returned to the free list once the run drains
    assert paged.pool.used_pages == 0
    assert paged.pool.free_pages == paged.pool.num_pages


@pytest.mark.parametrize("scheduler", ["fcfs", "sjf", "pagefair"])
def test_overcommitted_paged_engine_preempts_and_matches_dense(scheduler):
    """The acceptance bar: an overcommitted pool (too small for the
    resident batch's total footprint) forces mid-decode preemption —
    pages freed, state re-queued, re-prefilled — and the greedy output
    still matches an un-preempted dense run exactly, under every
    scheduler policy."""
    cfg, dense, paged = _engines(
        "qwen2-0.5b", num_slots=2, max_seq=64, prefill_chunk=16,
        page_size=16, num_pages=4, scheduler=scheduler)
    rng = np.random.default_rng(3)
    # admission footprints (prefill + headroom) are 2 pages each = the
    # whole pool under any admission order; both sequences then need a
    # third page past position 32 mid-decode, so every policy must
    # preempt at least once
    prompts = [rng.integers(1, cfg.vocab_size, size=n).astype(np.int32)
               for n in (9, 10)]
    reqs = [(p, SamplingParams(max_new_tokens=26)) for p in prompts]
    out_dense = dense.run(list(reqs))
    out_paged = paged.run(list(reqs))
    assert paged.stats.preemptions > 0, "pool was never under pressure"
    assert out_dense == out_paged
    assert any(paged.requests[r].preemptions > 0 for r in out_paged)
    assert paged.pool.used_pages == 0          # lazy growth leaked nothing
    assert paged.stats.peak_pages_used <= paged.pool.num_pages


def test_paged_engine_page_recycling_visible():
    """With a pool sized for ~one request, back-to-back requests must reuse
    the same physical pages (recycle through the free list) and still match
    the dense engine."""
    cfg, dense, paged = _engines(
        "qwen2-0.5b", num_slots=1, max_seq=64, prefill_chunk=16,
        page_size=16)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, cfg.vocab_size, size=20).astype(np.int32)
               for _ in range(2)]
    pages_used = []
    outs = {}
    for i, p in enumerate(prompts):
        rid = paged.submit(p, SamplingParams(max_new_tokens=3))
        paged.step()                       # admit + prefill + first tick
        pages_used.append(tuple(paged.slots.slots[0].pages))
        while not paged.requests[rid].finished:
            paged.step()
        outs[rid] = paged.requests[rid].tokens
    out_dense = dense.run([(p, SamplingParams(max_new_tokens=3))
                           for p in prompts])
    assert outs == out_dense
    assert set(pages_used[1]) & set(pages_used[0]), \
        "request 1 did not recycle request 0's freed pages"


def test_paged_engine_rejects_recurrent_families():
    cfg = configs.smoke(configs.get("rwkv6-1.6b"))
    api = get_model(cfg)
    params = api.init_params(jax.random.PRNGKey(0))
    with pytest.raises(ValueError):
        Engine(cfg, params, cache_kind="paged")


# ---------------------------------------------------------------------------
# The stacked pool is carried through the depth loop and written in place
# ---------------------------------------------------------------------------


def _slab_stack(layers_params, x, cache, block_fn, *, unroll=False):
    """Per-layer slab reference for ``stack.run_stack_in_place``: the depth
    loop slices each layer's (NP, ...) slab out and stacks the new slabs
    back, and every layer sees a one-layer pool and one-layer params —
    nothing it writes can reach another layer's pages."""
    def one_layer(tree):
        return jax.tree.map(lambda a: a[None], tree)

    def per_slab(p_i, x, c_i):
        x, c = block_fn(one_layer(p_i), x, one_layer(c_i), 0)
        return x, jax.tree.map(lambda a: a[0], c)

    return stack.run_stack_cached(layers_params, x, cache, per_slab,
                                  unroll=unroll)


def _random_cache(api, layout, rng):
    """A pool that already holds data, so a misplaced or missing write
    shows in the contents."""
    def fill(s):
        if s.dtype == jnp.float32:       # scale pools: positive steps
            return jnp.asarray(rng.uniform(0.01, 0.1, s.shape), s.dtype)
        if s.dtype == jnp.int8:
            return jnp.asarray(rng.integers(-127, 128, s.shape), s.dtype)
        return jnp.asarray(rng.normal(size=s.shape), s.dtype)

    return jax.tree.map(fill, api.cache_spec(layout))


# the two cells' attention shapes at smoke size: qwen's GQA (4/2 heads of
# 64) and phi3's MHA with head_dim 96
SMOKE_MODELS = {
    "qwen": configs.smoke(configs.get("qwen2-0.5b")),
    "phi3": dataclasses.replace(configs.smoke(configs.get("phi3-mini-3.8b")),
                                num_heads=2, num_kv_heads=2, head_dim=96),
}


@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("model", sorted(SMOKE_MODELS))
@pytest.mark.parametrize("step", ["decode", "grouped", "chunk"])
def test_carried_pool_matches_slab_reference(monkeypatch, step, model, kv):
    """The paged decode, grouped-decode and chunk steps write into the
    whole stacked pool in place; pool contents and logits must equal the
    per-layer slab reference bit for bit. Row 1 is an empty slot whose
    table is all sentinel: it writes nothing, and layer 0's sentinel must
    not land on layer 1's page 0 (held by rows 0 and 2). int8 pools bring
    their scale pools along; the Pallas kernels run in interpret mode."""
    cfg = SMOKE_MODELS[model]
    api = get_model(cfg)
    params = api.init_params(jax.random.PRNGKey(0))
    plan = make_plan("pallas", gather_chunk="fused", decode_group="grouped",
                     kv_dtype=kv)
    ctx = LayerCtx(cfg=cfg, plan=plan)
    num_pages, ps = 8, 16
    layout = PagedLayout(num_pages, ps, kv)
    rng = np.random.default_rng(11)
    cache = _random_cache(api, layout, rng)
    bt = jnp.asarray([[0, 5, num_pages, num_pages],
                      [num_pages] * 4,
                      [0, 6, 1, num_pages]], jnp.int32)
    groups = DecodeGroups(*(jnp.asarray(a, jnp.int32) for a in (
        [[0]], [1], [ps], [2], [[0, 2]], [0, 1, 0], [0, 0, 1],
        [ps, 0, ps])))
    if step == "chunk":
        lengths = jnp.asarray([ps, 0, 2 * ps + 8], jnp.int32)
        chunk_lens = jnp.asarray([ps, 0, 5], jnp.int32)
        tokens = jnp.asarray(rng.integers(1, cfg.vocab_size, (3, ps)),
                             jnp.int32)

        def run(c):
            return api.prefill_chunk(ctx, params, tokens, chunk_lens, c,
                                     lengths, block_tables=bt)
    else:
        lengths = jnp.asarray([ps + 4, 0, 2 * ps + 8], jnp.int32)
        tokens = jnp.asarray([3, 0, 7], jnp.int32)
        gr = groups if step == "grouped" else None

        def run(c):
            return api.decode_step(ctx, params, tokens, c, lengths,
                                   block_tables=bt, decode_groups=gr)

    logits, got = jax.jit(run)(cache)
    monkeypatch.setattr(stack, "run_stack_in_place", _slab_stack)
    # a function of its own, so the reference is traced anew
    want_logits, want = jax.jit(lambda c: run(c))(cache)
    assert np.array_equal(np.asarray(logits), np.asarray(want_logits))
    for name in want:
        assert np.array_equal(np.asarray(got[name]), np.asarray(want[name]))
    # the step wrote something, and only into the pages rows 0 and 2 own
    changed = np.asarray(got["k"] != cache["k"]).any(axis=(2, 3, 4))
    assert changed.any()
    assert not changed[:, [2, 3, 4, 7]].any()
