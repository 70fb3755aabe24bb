"""Fused paged chunk-attention: kernel-vs-oracle sweeps (interpret mode),
the overflow-recompute fallback, the bounded-table bitwise identity the
engine's fused mode rests on, and the engine-level greedy bit-identity
guard across {dense, gather, fused} x {prefix sharing on/off} including a
preemption run."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import TOL
from repro import configs
from repro.core import dispatch as dsp
from repro.core.plan import PagedPlan, PlanError, make_plan, tune
from repro.kernels import ref
from repro.kernels.chunk_attention import (
    paged_chunk_attention_sync,
    paged_chunk_attention_unified_max,
)
from repro.models.api import get_model
from repro.serving.engine import Engine
from repro.serving.request import SamplingParams


def _fixture(dtype, *, b=3, c=16, hq=8, hk=2, d=64, ps=32, num_pages=24,
             nb=6, seed=0):
    """Random pool + disjoint per-row pages, sentinel tails, and lengths
    exercising: a partial last page (37), an empty prefix (0 — the chunk
    is the whole sequence), and a chunk that straddles a page boundary
    mid-page (3*ps - c + 5)."""
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(b, c, hq, d)), dtype)
    kp = jnp.asarray(rng.normal(size=(num_pages, ps, hk, d)), dtype)
    vp = jnp.asarray(rng.normal(size=(num_pages, ps, hk, d)), dtype)
    perm = rng.permutation(num_pages)
    bt = np.full((b, nb), num_pages, np.int32)
    for i in range(b):
        bt[i] = perm[i * nb:(i + 1) * nb]
    bt[2, 4:] = num_pages                       # short row: sentinel tail
    lengths = jnp.asarray([37, 0, 3 * ps - c + 5], jnp.int32)
    return q, kp, vp, jnp.asarray(bt), lengths


@pytest.mark.parametrize(
    "dtype", ["float32",
              pytest.param("bfloat16", marks=pytest.mark.slow)])
def test_fused_chunk_kernel_matches_oracles(dtype):
    """Unified-max kernel == gather oracle (allclose) and == the
    page-blocked fused oracle; the sync kernel likewise."""
    q, kp, vp, bt, lengths = _fixture(dtype)
    out, stat = paged_chunk_attention_unified_max(
        q, kp, vp, bt, lengths, phi=0.0, interpret=True)
    want = ref.attention_chunk_paged_ref(q, kp, vp, bt, lengths, phi=0.0)
    np.testing.assert_allclose(
        out.astype(np.float32), want.astype(np.float32), **TOL[dtype])
    fo, fstat = ref.attention_chunk_paged_fused_ref(
        q, kp, vp, bt, lengths, phi=0.0)
    np.testing.assert_allclose(
        out.astype(np.float32), fo.astype(np.float32), **TOL[dtype])
    assert stat.shape == fstat.shape == (q.shape[0], kp.shape[2])
    np.testing.assert_allclose(stat, fstat, rtol=1e-5, atol=1e-5)

    out_s = paged_chunk_attention_sync(q, kp, vp, bt, lengths,
                                       interpret=True)
    want_s = ref.attention_chunk_paged_ref(q, kp, vp, bt, lengths, phi=None)
    np.testing.assert_allclose(
        out_s.astype(np.float32), want_s.astype(np.float32), **TOL[dtype])


def test_fused_chunk_kernel_causal_at_chunk_boundary():
    """Chunk-local causality: query i of row b sees exactly cache
    positions <= lengths[b] + i. Checked per-row against the dense ref on
    a gathered view, with lengths crossing page boundaries both ways."""
    q, kp, vp, bt, lengths = _fixture("float32", seed=3)
    out, _ = paged_chunk_attention_unified_max(
        q, kp, vp, bt, lengths, phi=0.0, interpret=True)
    k = ref.gather_paged_kv(kp, bt)
    v = ref.gather_paged_kv(vp, bt)
    want = ref.attention_chunk_ref(q, k, v, lengths, phi=0.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    # a key one past the causal frontier must change nothing: perturb the
    # pool at position lengths[0] + c (first invalid key of the last row)
    c = q.shape[1]
    pos = int(lengths[0]) + c          # strictly beyond every valid key
    page, off = pos // kp.shape[1], pos % kp.shape[1]
    kp2 = kp.at[bt[0, page], off].set(1e3)
    out2, _ = paged_chunk_attention_unified_max(
        q, kp2, vp, bt, lengths, phi=0.0, interpret=True)
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(out2[0]))


def test_fused_chunk_partial_last_page_masks_garbage():
    """A partially filled last page: positions past lengths+i hold noise
    that must never leak into the output (write garbage there, compare
    against a pool with zeros there)."""
    q, kp, vp, bt, lengths = _fixture("float32", seed=5)
    ps = kp.shape[1]
    c = q.shape[1]
    # poison everything beyond each row's causal frontier
    kp_p, vp_p = np.array(kp), np.array(vp)
    for row in range(q.shape[0]):
        frontier = int(lengths[row]) + c
        for col in range(bt.shape[1]):
            page = int(bt[row, col])
            if page >= kp.shape[0]:
                continue
            lo = max(frontier - col * ps, 0)
            if lo < ps:
                kp_p[page, lo:] = 7e2
                vp_p[page, lo:] = -7e2
    out_clean, _ = paged_chunk_attention_unified_max(
        q, kp, vp, bt, lengths, phi=0.0, interpret=True)
    out_poison, _ = paged_chunk_attention_unified_max(
        q, jnp.asarray(kp_p), jnp.asarray(vp_p), bt, lengths, phi=0.0,
        interpret=True)
    np.testing.assert_array_equal(np.asarray(out_clean),
                                  np.asarray(out_poison))


def test_fused_chunk_ops_overflow_falls_back_to_safe():
    """ops.attention_chunk_paged in the fused Pallas mode: a band
    overflow must recompute with the sync kernel (finite output close to
    the safe oracle); an in-band run keeps the T1 result."""
    from repro.config import SoftmaxPhiConfig
    from repro.kernels import ops
    rng = np.random.default_rng(7)
    b, c, hq, hk, d, ps, npages, nb = 2, 8, 4, 2, 32, 16, 8, 4
    kp = jnp.asarray(rng.normal(size=(npages, ps, hk, d)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(npages, ps, hk, d)), jnp.float32)
    bt = jnp.asarray(rng.permutation(npages).reshape(b, nb), jnp.int32)
    lens = jnp.asarray([10, 30], jnp.int32)
    plan = make_plan(backend="pallas", gather_chunk="fused")

    q_big = jnp.asarray(rng.normal(size=(b, c, hq, d)), jnp.float32) * 50
    out = ops.attention_chunk_paged(
        q_big, kp, vp, bt, lens,
        phi_cfg=SoftmaxPhiConfig(phi=0.0, band=(-1.0, 1.0)), plan=plan)
    safe = ref.attention_chunk_paged_ref(q_big, kp, vp, bt, lens, phi=None)
    assert bool(jnp.all(jnp.isfinite(out)))
    np.testing.assert_allclose(np.asarray(out), np.asarray(safe),
                               rtol=1e-5, atol=1e-5)

    q_small = jnp.asarray(rng.normal(size=(b, c, hq, d)), jnp.float32) * 0.01
    out2 = ops.attention_chunk_paged(
        q_small, kp, vp, bt, lens,
        phi_cfg=SoftmaxPhiConfig(phi=0.0, band=(-40.0, 40.0)), plan=plan)
    t1 = ref.attention_chunk_paged_ref(q_small, kp, vp, bt, lens, phi=0.0)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(t1),
                               rtol=1e-5, atol=1e-5)


def test_bounded_table_is_bitwise_neutral():
    """The fused mode's XLA realization: slicing trailing table columns
    whose pages carry only causally-masked positions must leave the
    gather-path result bitwise unchanged (Engine._chunk_tables rests on
    this)."""
    q, kp, vp, bt, lengths = _fixture("float32", seed=11)
    c, ps = q.shape[1], kp.shape[1]
    bound = -(-(int(jnp.max(lengths)) + c) // ps)
    assert bound < bt.shape[1]
    for phi in (0.0, None):
        full = ref.attention_chunk_paged_ref(q, kp, vp, bt, lengths, phi=phi)
        cut = ref.attention_chunk_paged_ref(q, kp, vp, bt[:, :bound],
                                            lengths, phi=phi)
        np.testing.assert_array_equal(np.asarray(full), np.asarray(cut))


# ---------------------------------------------------------------------------
# Plan / dispatch decisions
# ---------------------------------------------------------------------------


def test_paged_plan_chunk_knobs_validated():
    with pytest.raises(PlanError):
        PagedPlan(gather_chunk="bogus")
    with pytest.raises(PlanError):
        PagedPlan(fused_threshold=0)
    with pytest.raises(PlanError):
        PagedPlan(chunk_block=-1)


def test_tuned_plan_carries_chunk_decision_and_roundtrips():
    from repro.core.plan import ExecutionPlan
    cfg = configs.get("qwen2-0.5b")
    p = tune(cfg)
    assert p.paged.gather_chunk == "fused"
    assert p.paged.fused_threshold >= 1
    # chunk boundaries must stay on the prefix-sharing page grid
    assert 64 % p.paged.chunk_block == 0
    assert ExecutionPlan.from_json(p.to_json()) == p


def test_chunk_cost_model_fused_wins_while_table_is_sparse():
    """The decision flow's invariant: from the tuned threshold up to
    prompts a quarter of the table width, the fused path's predicted time
    stays below the dense gather's (which pays O(table width) bytes every
    step); the per-page grid bubble only catches up once the prompt
    nearly fills the table — exactly the regime where provisioning is
    dense anyway."""
    kv_dim = 128
    thr = dsp.find_fused_threshold(4096, kv_dim)
    assert thr <= 4096
    for p_len in (thr, 2 * thr, 4096 // 4):
        t_d = dsp.predict_chunk_prefill_time("dense", p_len, 4096, kv_dim)
        t_f = dsp.predict_chunk_prefill_time("fused", p_len, 4096, kv_dim)
        assert t_f < t_d
    assert dsp.find_chunk_block(4096, kv_dim, page_size=64) in (32, 64)
    with pytest.raises(ValueError):
        dsp.predict_chunk_prefill_time("bogus", 64, 4096, kv_dim)


# ---------------------------------------------------------------------------
# Engine: greedy bit-identity across {dense, gather, fused} x sharing
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def smoke_model():
    cfg = configs.smoke(configs.get("qwen2-0.5b"))
    api = get_model(cfg)
    params = api.init_params(jax.random.PRNGKey(0))
    return cfg, params


FUSED = make_plan(gather_chunk="fused", fused_threshold=1)


def test_engine_identity_dense_gather_fused(smoke_model):
    """Greedy tokens are identical across the dense slot cache, the paged
    dense-gather mode, and the fused mode — with prefix sharing on and
    off (shared system-prompt workload)."""
    cfg, params = smoke_model
    rng = np.random.default_rng(13)
    header = rng.integers(1, cfg.vocab_size, size=48).astype(np.int32)
    prompts = [np.concatenate([
        header, rng.integers(1, cfg.vocab_size, size=n).astype(np.int32)])
        for n in (9, 23, 5, 30)]

    def reqs():
        return [(p, SamplingParams(max_new_tokens=5)) for p in prompts]

    kw = dict(num_slots=4, max_seq=128, prefill_chunk=16)
    outs = {
        "dense": Engine(cfg, params, cache_kind="dense", **kw).run(reqs()),
        "gather": Engine(cfg, params, cache_kind="paged", page_size=16,
                         **kw).run(reqs()),
        "fused": Engine(cfg, params, cache_kind="paged", page_size=16,
                        plan=FUSED, **kw).run(reqs()),
        "gather+share": Engine(cfg, params, cache_kind="paged", page_size=16,
                               prefix_sharing=True, **kw).run(reqs()),
        "fused+share": Engine(cfg, params, cache_kind="paged", page_size=16,
                              plan=FUSED, prefix_sharing=True,
                              **kw).run(reqs()),
    }
    base = outs.pop("dense")
    for name, got in outs.items():
        assert got == base, f"{name} diverged from dense"


def test_engine_identity_fused_under_preemption_with_sharing(smoke_model):
    """The hard case: a sharing sequence preempted mid-decode under an
    overcommitted pool, in the fused mode — release drops refs,
    re-admission re-maps the surviving prefix and re-prefills through
    resident-bounded tables, and greedy outputs still match the gather
    mode without sharing."""
    cfg, params = smoke_model
    rng = np.random.default_rng(17)
    header = rng.integers(1, cfg.vocab_size, size=16).astype(np.int32)
    prompts = [np.concatenate([
        header, rng.integers(1, cfg.vocab_size, size=n).astype(np.int32)])
        for n in (9, 10)]

    def reqs():
        return [(p, SamplingParams(max_new_tokens=26)) for p in prompts]

    kw = dict(num_slots=2, max_seq=80, page_size=16, prefill_chunk=16,
              num_pages=5)
    fused = Engine(cfg, params, cache_kind="paged", prefix_sharing=True,
                   plan=FUSED, **kw)
    gather = Engine(cfg, params, cache_kind="paged", prefix_sharing=False,
                    **kw)
    out_f = fused.run(reqs())
    out_g = gather.run(reqs())
    assert fused.stats.preemptions > 0, "pool was never under pressure"
    assert fused.stats.shared_prefix_pages > 0, "nothing was shared"
    assert out_f == out_g
    fused.slots.check()
    assert fused.pool.used_pages == 0


def test_engine_fused_threshold_keeps_short_waves_on_gather(smoke_model):
    """Prompts below paged.fused_threshold keep the one-compile full-width
    table (the tuned inflection), and outputs still match."""
    cfg, params = smoke_model
    rng = np.random.default_rng(19)
    prompts = [rng.integers(1, cfg.vocab_size, size=n).astype(np.int32)
               for n in (7, 12)]

    def reqs():
        return [(p, SamplingParams(max_new_tokens=4)) for p in prompts]

    high = make_plan(gather_chunk="fused", fused_threshold=10_000)
    kw = dict(num_slots=2, max_seq=128, page_size=16, prefill_chunk=16,
              cache_kind="paged")
    a = Engine(cfg, params, plan=high, **kw)
    b = Engine(cfg, params, **kw)
    assert a.run(reqs()) == b.run(reqs())


def test_slot_manager_lengths_device_cache(smoke_model):
    """The lengths operand is device-cached with the block-table cache's
    invalidation discipline: same buffer while nothing changed, fresh
    after assign/tick/release."""
    from repro.serving.blockpool import BlockPool, PagedSlotManager
    pool = BlockPool(16, 16)
    mgr = PagedSlotManager(4, 64, pool)
    l0 = mgr.lengths_device()
    assert mgr.lengths_device() is l0              # cached, no re-upload
    idx = mgr.try_assign(0, 10, 4)
    l1 = mgr.lengths_device()
    assert l1 is not l0 and int(l1[idx]) == 10
    assert mgr.lengths_device() is l1
    mgr.tick(idx)
    l2 = mgr.lengths_device()
    assert l2 is not l1 and int(l2[idx]) == 11
    mgr.tick(idx, wrote_kv=False)                  # no KV written: no change
    assert mgr.lengths_device() is l2
    mgr.release(idx)
    l3 = mgr.lengths_device()
    assert l3 is not l2 and int(l3[idx]) == 0
    np.testing.assert_array_equal(np.asarray(l3), mgr.lengths())


def test_prefill_buckets_are_logarithmic(smoke_model):
    """Batched single-shot prefill pads to power-of-two buckets: distinct
    tail lengths in the same bucket share one compile."""
    cfg, params = smoke_model
    from repro.models import ssm  # noqa: F401  (family without chunked path)
    scfg = configs.smoke(configs.get("rwkv6-1.6b"))
    sapi = get_model(scfg)
    sparams = sapi.init_params(jax.random.PRNGKey(1))
    eng = Engine(scfg, sparams, num_slots=2, max_seq=512)
    assert eng.prefill_chunk == 0                  # batched single-shot path
    rng = np.random.default_rng(23)
    for n in (70, 100, 120):                       # all land in the 128 bucket
        eng.run([(rng.integers(1, scfg.vocab_size, size=n).astype(np.int32),
                  SamplingParams(max_new_tokens=1))])
    assert set(eng._prefill_cache) == {128}


def test_engine_identity_gather_fused_at_page_64(smoke_model):
    """At the cells' page and chunk size (64), prompts of two and four
    chunks decode to the same greedy tokens through the dense slot cache,
    the paged dense-gather mode and the fused mode — also in a second
    wave on the same engine, over pages the first wave freed."""
    cfg, params = smoke_model
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, size=n).astype(np.int32)
               for n in (128, 256)]
    kw = dict(num_slots=2, max_seq=512, prefill_chunk=64)
    engines = {
        "dense": Engine(cfg, params, cache_kind="dense", **kw),
        "gather": Engine(cfg, params, cache_kind="paged", page_size=64,
                         plan=make_plan(gather_chunk="dense"), **kw),
        "fused": Engine(cfg, params, cache_kind="paged", page_size=64,
                        plan=make_plan(gather_chunk="fused",
                                       fused_threshold=64), **kw),
    }
    waves = {}
    for name, eng in engines.items():
        waves[name] = []
        for _ in range(2):
            out = eng.run([(p, SamplingParams(max_new_tokens=2))
                           for p in prompts])
            waves[name].append([out[k] for k in sorted(out)])
            eng.evict_finished()
        assert waves[name][0] == waves[name][1], name
    assert waves["gather"] == waves["dense"] == waves["fused"]
