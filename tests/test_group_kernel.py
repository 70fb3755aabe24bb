"""Prefix-shared grouped decode attention: kernel-vs-oracle sweeps
(interpret mode) over group-of-1 / zero tails / partial prefix pages /
mixed group sizes / GQA regrouping, the reconstructed-gather bitwise
identity the XLA grouped path rests on, the group-plan knobs and cost
model, the slot manager's per-tick group plan (cache discipline, COW
fork eviction), and the engine-level greedy bit-identity guard across
{grouped, ungrouped} x {sharing on/off} including the COW-fork and
preemption paths."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import TOL
from repro import configs
from repro.core import dispatch as dsp
from repro.core.plan import PagedPlan, PlanError, make_plan, tune
from repro.kernels import ref
from repro.kernels.decode_attention import paged_decode_attention_unified_max
from repro.kernels.group_attention import (
    DecodeGroups,
    grouped_paged_decode_attention_unified_max,
)
from repro.models.api import get_model
from repro.serving.blockpool import BlockPool, PagedSlotManager
from repro.serving.engine import Engine
from repro.serving.prefix import PrefixIndex, shared_prefix_groups
from repro.serving.request import SamplingParams


def _mk_groups(b, num_pages, specs, num_slots_pad=None):
    """Build a DecodeGroups pytree from ``specs`` =
    [(prefix_pages, prefix_len, member_rows)], padding NG/LP/M to pow2
    exactly like the slot manager does."""
    def pow2(n):
        p = 1
        while p < n:
            p *= 2
        return p

    ng = pow2(len(specs))
    lp = pow2(max(len(pg) for pg, _, _ in specs))
    m = pow2(max(len(ms) for _, _, ms in specs))
    tables = np.full((ng, lp), num_pages, np.int32)
    n_pages = np.zeros(ng, np.int32)
    g_plen = np.zeros(ng, np.int32)
    num_members = np.zeros(ng, np.int32)
    member_rows = np.full((ng, m), b, np.int32)
    gid = np.full(b, ng, np.int32)
    member = np.zeros(b, np.int32)
    prefix_len = np.zeros(b, np.int32)
    for g, (pages, plen, members) in enumerate(specs):
        tables[g, :len(pages)] = pages
        n_pages[g] = len(pages)
        g_plen[g] = plen
        num_members[g] = len(members)
        member_rows[g, :len(members)] = members
        for r, i in enumerate(members):
            gid[i], member[i], prefix_len[i] = g, r, plen
    return DecodeGroups(*(jnp.asarray(a) for a in (
        tables, n_pages, g_plen, num_members, member_rows,
        gid, member, prefix_len)))


def _fixture(dtype, *, b=6, hq=8, hk=2, d=64, ps=16, num_pages=32, nb=6,
             seed=0):
    """Pool + block tables with two shared prefixes: rows {0, 2, 4} share
    pages [3, 4]; rows {1, 5} share page [7]; row 3 is solo. Lengths
    exercise a zero private tail (row 4: length == prefix) and tails that
    end mid-page."""
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(b, hq, d)), dtype)
    kp = jnp.asarray(rng.normal(size=(num_pages, ps, hk, d)), dtype)
    vp = jnp.asarray(rng.normal(size=(num_pages, ps, hk, d)), dtype)
    bt = np.full((b, nb), num_pages, np.int32)
    bt[0, :4] = [3, 4, 10, 11]
    bt[1, :2] = [7, 12]
    bt[2, :3] = [3, 4, 13]
    bt[3, :2] = [15, 16]
    bt[4, :2] = [3, 4]
    bt[5, :3] = [7, 17, 18]
    lengths = np.array(
        [3 * ps + 5, ps + 3, 2 * ps + 7, ps + 9, 2 * ps, 2 * ps + 1],
        np.int32)
    return q, kp, vp, jnp.asarray(bt), jnp.asarray(lengths)


def _default_groups(num_pages, b=6, ps=16):
    return _mk_groups(b, num_pages, [
        ([3, 4], 2 * ps, [0, 2, 4]),
        ([7], ps, [1, 5]),
    ])


# ---------------------------------------------------------------------------
# Oracle: the reconstructed gather is bitwise-neutral
# ---------------------------------------------------------------------------


def test_gather_grouped_kv_is_bitwise_identical():
    """The grouped oracle's KV view — tail gather overwritten with the
    group-table gather over prefix positions — must be elementwise equal
    to the plain per-row gather: the group tables point at the *same
    physical pages* the rows' own tables lead with."""
    _, kp, vp, bt, _ = _fixture("float32")
    groups = _default_groups(kp.shape[0])
    for pool in (kp, vp):
        got = ref.gather_grouped_kv(pool, bt, groups)
        want = ref.gather_paged_kv(pool, bt)
        assert got.shape == want.shape
        assert bool(jnp.all(got == want))


def test_grouped_refs_bitwise_match_ungrouped_refs():
    """Both grouped oracles (sync and unified-max) run the identical
    dense math on the reconstructed view -> bitwise equal to the plain
    paged oracles. This is the XLA-backend grouped path's whole
    correctness argument."""
    q, kp, vp, bt, lengths = _fixture("float32")
    groups = _default_groups(kp.shape[0])
    out_g = ref.attention_decode_grouped_ref(q, kp, vp, bt, lengths, groups)
    out_p = ref.attention_decode_paged_ref(q, kp, vp, bt, lengths)
    assert bool(jnp.all(out_g == out_p))
    ou_g, st_g = ref.attention_decode_grouped_unified_max_ref(
        q, kp, vp, bt, lengths, groups, phi=0.0)
    ou_p, st_p = ref.attention_decode_paged_unified_max_ref(
        q, kp, vp, bt, lengths, phi=0.0)
    assert bool(jnp.all(ou_g == ou_p)) and bool(jnp.all(st_g == st_p))


# ---------------------------------------------------------------------------
# Kernel vs oracle (interpret mode)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "dtype", ["float32",
              pytest.param("bfloat16", marks=pytest.mark.slow)])
def test_grouped_kernel_matches_oracle_mixed_groups(dtype):
    """Mixed group sizes in one batch (3-way, 2-way, solo), GQA head
    regrouping (HQ=8 over HK=2), zero-length private tail (row 4)."""
    q, kp, vp, bt, lengths = _fixture(dtype)
    groups = _default_groups(kp.shape[0])
    out, stat = grouped_paged_decode_attention_unified_max(
        q, kp, vp, bt, lengths, groups, phi=0.0, interpret=True)
    want, _ = ref.attention_decode_grouped_unified_max_ref(
        q, kp, vp, bt, lengths, groups, phi=0.0)
    np.testing.assert_allclose(
        out.astype(np.float32), want.astype(np.float32), **TOL[dtype])
    assert stat.shape == (q.shape[0], kp.shape[2])


def test_grouped_kernel_page_aligned_is_bitwise_vs_ungrouped():
    """With page-aligned prefixes (the only shape the engine emits: a
    group key is whole shared pages) the two-stage kernel accumulates the
    same pages in the same order as the ungrouped kernel — the unified-max
    carry makes the split literally the same fp op sequence, so outputs
    are bitwise equal, not just close."""
    q, kp, vp, bt, lengths = _fixture("float32")
    groups = _default_groups(kp.shape[0])
    out, stat = grouped_paged_decode_attention_unified_max(
        q, kp, vp, bt, lengths, groups, phi=0.0, interpret=True)
    want, wstat = paged_decode_attention_unified_max(
        q, kp, vp, bt, lengths, phi=0.0, interpret=True)
    assert bool(jnp.all(out == want))
    # per-row stats are group-broadcast, but the global overflow decision
    # (any(stat > band)) reduces over the same maxima
    assert float(jnp.max(stat)) == float(jnp.max(wstat))


def test_grouped_kernel_partial_last_prefix_page():
    """A prefix ending mid-page: stage 1 masks past the prefix inside the
    boundary page, stage 2 picks up the rest of that page from the row's
    own table."""
    q, kp, vp, bt, lengths = _fixture("float32", seed=2)
    ps = 16
    groups = _mk_groups(6, kp.shape[0], [
        ([3, 4], 2 * ps - 5, [0, 2]),        # boundary page split mid-page
    ])
    out, _ = grouped_paged_decode_attention_unified_max(
        q, kp, vp, bt, lengths, groups, phi=0.0, interpret=True)
    want, _ = ref.attention_decode_grouped_unified_max_ref(
        q, kp, vp, bt, lengths, groups, phi=0.0)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(want), **TOL["float32"])
    # the reconstructed view stays bitwise-neutral even mid-page
    assert bool(jnp.all(
        ref.gather_grouped_kv(kp, bt, groups) == ref.gather_paged_kv(kp, bt)))


def test_grouped_kernel_group_of_one():
    """A degenerate 1-member group (the manager never emits one, the
    kernel must still be exact): prefix computed 'once' for one row."""
    q, kp, vp, bt, lengths = _fixture("float32", seed=4)
    groups = _mk_groups(6, kp.shape[0], [([3, 4], 32, [0])])
    out, _ = grouped_paged_decode_attention_unified_max(
        q, kp, vp, bt, lengths, groups, phi=0.0, interpret=True)
    want, _ = paged_decode_attention_unified_max(
        q, kp, vp, bt, lengths, phi=0.0, interpret=True)
    assert bool(jnp.all(out == want))


# ---------------------------------------------------------------------------
# Plan knobs + cost model
# ---------------------------------------------------------------------------


def test_paged_plan_group_knobs_validated():
    with pytest.raises(PlanError):
        PagedPlan(decode_group="bogus")
    with pytest.raises(PlanError):
        PagedPlan(group_threshold=0)
    assert PagedPlan().decode_group == "off"


def test_tuned_plan_carries_group_decision_and_roundtrips():
    from repro.core.plan import ExecutionPlan
    cfg = configs.get("qwen2-0.5b")
    p = tune(cfg)
    assert p.paged.decode_group == "grouped"
    assert p.paged.group_threshold >= 1
    assert ExecutionPlan.from_json(p.to_json()) == p
    assert "group>=" in p.describe()


def test_group_cost_model_grouped_wins_with_scale():
    """The decision flow's invariant: once members x prefix pages clears
    the tuned floor, the grouped path's predicted time stays below the
    per-row re-read's, and the gap grows with the dedup factor."""
    kv_dim = 128
    thr = dsp.find_group_threshold(kv_dim)
    assert thr >= 1
    t_off = dsp.predict_group_decode_time("off", 8, 16, 1, kv_dim)
    t_grp = dsp.predict_group_decode_time("grouped", 8, 16, 1, kv_dim)
    assert t_grp < t_off
    # dedup scales with members: doubling members at fixed prefix should
    # roughly double the grouped path's advantage on the prefix bytes
    gain2 = (dsp.predict_group_decode_time("off", 2, 16, 1, kv_dim)
             - dsp.predict_group_decode_time("grouped", 2, 16, 1, kv_dim))
    gain8 = t_off - t_grp
    assert gain8 > 2 * gain2
    with pytest.raises(ValueError):
        dsp.predict_group_decode_time("bogus", 2, 2, 1, kv_dim)


# ---------------------------------------------------------------------------
# Slot-manager group plan
# ---------------------------------------------------------------------------


def _mgr(num_pages=16, page_size=4, num_slots=3, max_seq=32):
    pool = BlockPool(num_pages, page_size)
    return PagedSlotManager(num_slots, max_seq, pool,
                            prefix_index=PrefixIndex(page_size)), pool


def test_shared_prefix_groups_keys_on_leading_refcounted_run():
    mgr, pool = _mgr()
    toks = np.arange(100, 109, dtype=np.int32)          # 2 full pages
    a = mgr.try_assign(0, 9, 4, tokens=toks)
    mgr.commit_prefix(a, toks)
    b = mgr.try_assign(1, 9, 4, tokens=toks)
    groups = shared_prefix_groups(mgr.slots, pool.refcount)
    assert len(groups) == 1
    key, members = groups[0]
    assert sorted(members) == sorted([a, b])
    assert list(key) == mgr.slots[a].pages[:2] == mgr.slots[b].pages[:2]
    assert all(pool.refcount(p) == 2 for p in key)


def test_group_plan_builds_and_caches_until_tables_change():
    mgr, pool = _mgr()
    toks = np.arange(100, 109, dtype=np.int32)
    a = mgr.try_assign(0, 9, 4, tokens=toks)
    mgr.commit_prefix(a, toks)
    b = mgr.try_assign(1, 9, 4, tokens=toks)
    plan = mgr.group_plan(threshold=2)
    assert plan is not None
    assert plan.n_grouped == 2 and plan.pages_deduped == 2
    np.testing.assert_array_equal(
        np.sort(plan.member_rows[0, :2]), np.sort([a, b]))
    assert plan.prefix_len[a] == plan.prefix_len[b] == 8
    # solo slot rows carry the solo sentinel gid == NG
    ng = plan.tables.shape[0]
    free_rows = [i for i in range(len(mgr.slots)) if i not in (a, b)]
    assert all(plan.gid[i] == ng for i in free_rows)
    # steady state: the identical plan object is reused...
    assert mgr.group_plan(threshold=2) is plan
    # ...until some table changes (growth past the admission reservation)
    mgr.ensure(a, 17)
    assert mgr.group_plan(threshold=2) is not plan
    # device operands cache on the plan and mirror the host arrays
    p2 = mgr.group_plan(threshold=2)
    ops = p2.operands()
    assert ops is p2.operands()
    np.testing.assert_array_equal(np.asarray(ops.gid), p2.gid)


def test_group_plan_threshold_and_fork_evict_members():
    mgr, pool = _mgr()
    toks = np.arange(50, 59, dtype=np.int32)
    a = mgr.try_assign(0, 9, 4, tokens=toks)
    mgr.commit_prefix(a, toks)
    b = mgr.try_assign(1, 9, 4, tokens=toks)
    # 2 members x 2 pages = 4 units of deduped work
    assert mgr.group_plan(threshold=4) is not None
    assert mgr.group_plan(threshold=5) is None
    # a COW fork privatizes b's copy -> run shortens -> group dissolves
    forks = mgr.fork_for_write(b, 0, 9)
    assert forks
    assert mgr.group_plan(threshold=2) is None
    assert shared_prefix_groups(mgr.slots, pool.refcount) == []
    # release of the leader likewise invalidates the (empty) plan cleanly
    mgr.release(a)
    assert mgr.group_plan(threshold=2) is None
    mgr.check()


# ---------------------------------------------------------------------------
# Engine: greedy bit-identity across {grouped, ungrouped} x sharing
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def smoke_model():
    cfg = configs.smoke(configs.get("qwen2-0.5b"))
    api = get_model(cfg)
    params = api.init_params(jax.random.PRNGKey(0))
    return cfg, params


GROUPED = make_plan(decode_group="grouped", group_threshold=1)


def test_engine_identity_grouped_vs_ungrouped_vs_dense(smoke_model):
    """The acceptance bar: greedy tokens identical across the dense slot
    cache, paged without sharing, paged sharing ungrouped, and paged
    sharing with grouped decode — and the grouped run actually groups."""
    cfg, params = smoke_model
    rng = np.random.default_rng(29)
    header = rng.integers(1, cfg.vocab_size, size=48).astype(np.int32)
    prompts = [np.concatenate([
        header, rng.integers(1, cfg.vocab_size, size=n).astype(np.int32)])
        for n in (9, 23, 5)] + [
        rng.integers(1, cfg.vocab_size, size=10).astype(np.int32)]

    def reqs():
        return [(p, SamplingParams(max_new_tokens=5)) for p in prompts]

    kw = dict(num_slots=4, max_seq=128, prefill_chunk=16)
    grouped = Engine(cfg, params, cache_kind="paged", page_size=16,
                     prefix_sharing=True, plan=GROUPED, **kw)
    outs = {
        "dense": Engine(cfg, params, cache_kind="dense", **kw).run(reqs()),
        "paged": Engine(cfg, params, cache_kind="paged", page_size=16,
                        **kw).run(reqs()),
        "share": Engine(cfg, params, cache_kind="paged", page_size=16,
                        prefix_sharing=True, **kw).run(reqs()),
        "share+grouped": grouped.run(reqs()),
    }
    base = outs.pop("dense")
    for name, got in outs.items():
        assert got == base, f"{name} diverged from dense"
    assert grouped.stats.grouped_requests > 0, "grouped path never ran"
    assert grouped.stats.prefix_kv_bytes_saved > 0
    grouped.slots.check()


def test_engine_grouped_cow_fork_drops_member_and_matches(smoke_model):
    """COW fork of a group member mid-run: the fully-covered second
    request forks its tail page, whose refcount drop re-keys the group
    plan — outputs still bit-match the ungrouped sharing-off run, and the
    grouped stats only count surviving shared pages."""
    cfg, params = smoke_model
    rng = np.random.default_rng(31)
    prompt = rng.integers(1, cfg.vocab_size, size=32).astype(np.int32)
    outs = {}
    for name, (sharing, plan) in {
        "off": (False, None),
        "grouped": (True, GROUPED),
    }.items():
        eng = Engine(cfg, params, cache_kind="paged", num_slots=2,
                     max_seq=128, prefill_chunk=16, page_size=16,
                     prefix_sharing=sharing, plan=plan)
        ra = eng.submit(prompt, SamplingParams(max_new_tokens=8))
        eng.step()            # a prefills + commits, stays resident
        rb = eng.submit(prompt, SamplingParams(max_new_tokens=8))
        while not (eng.requests[ra].finished and eng.requests[rb].finished):
            eng.step()
        outs[name] = {r: eng.requests[r].tokens for r in (ra, rb)}
        if sharing:
            assert eng.stats.cow_forks == 1
            assert eng.stats.grouped_requests > 0
            eng.slots.check()
    assert outs["grouped"] == outs["off"]


def test_engine_grouped_survives_preemption(smoke_model):
    """Preemption under an overcommitted pool with grouped decode on:
    the victim's release re-keys the plan, re-admission re-maps and
    re-groups, outputs still bit-match an ungrouped non-sharing run."""
    cfg, params = smoke_model
    rng = np.random.default_rng(37)
    header = rng.integers(1, cfg.vocab_size, size=16).astype(np.int32)
    prompts = [np.concatenate([
        header, rng.integers(1, cfg.vocab_size, size=n).astype(np.int32)])
        for n in (9, 10)]

    def reqs():
        return [(p, SamplingParams(max_new_tokens=26)) for p in prompts]

    kw = dict(num_slots=2, max_seq=80, page_size=16, prefill_chunk=16,
              num_pages=5)
    grouped = Engine(cfg, params, cache_kind="paged", prefix_sharing=True,
                     plan=GROUPED, **kw)
    plain = Engine(cfg, params, cache_kind="paged", prefix_sharing=False,
                   **kw)
    out_g = grouped.run(reqs())
    out_p = plain.run(reqs())
    assert grouped.stats.preemptions > 0, "pool was never under pressure"
    assert out_g == out_p
    grouped.slots.check()
    assert grouped.pool.used_pages == 0


def test_engine_grouped_dedup_equals_group_size(smoke_model):
    """N requests decoding behind one shared page-aligned prefix read its
    KV once per grouped tick instead of N times: the engine's saved bytes
    per grouped tick are (N - 1) x prefix pages x page bytes, and greedy
    tokens equal the ungrouped run's."""
    cfg, params = smoke_model
    rng = np.random.default_rng(0)
    header = rng.integers(1, cfg.vocab_size, size=32).astype(np.int32)
    for n in (2, 3):
        prompts = [np.concatenate([header, rng.integers(
            1, cfg.vocab_size, size=8).astype(np.int32)]) for _ in range(n)]
        outs, engines = {}, {}
        for name, plan in (("off", None), ("grouped", GROUPED)):
            eng = Engine(cfg, params, cache_kind="paged", num_slots=n,
                         max_seq=128, page_size=16, prefill_chunk=16,
                         prefix_sharing=True, plan=plan)
            outs[name] = eng.run([(p, SamplingParams(max_new_tokens=12))
                                  for p in prompts])
            engines[name] = eng
        assert outs["grouped"] == outs["off"]
        st = engines["grouped"].stats
        assert st.grouped_requests > 0 and st.grouped_requests % n == 0
        ticks = st.grouped_requests // n
        page_bytes = engines["grouped"]._kv_bytes_per_page
        assert st.prefix_kv_bytes_saved == ticks * (n - 1) * 2 * page_bytes
        assert engines["off"].stats.prefix_kv_bytes_saved == 0
