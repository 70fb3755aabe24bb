"""Quantized KV page subsystem: code math round-trips, write-scatter
algebra, in-kernel dequant parity, COW-fork scale independence, and the
engine-level logits-closeness guard across every paged kernel path.

The plan's contract (mirrors the scheme-swap guard in test_plan.py):
``kv_dtype`` may change the bytes behind every attention read and which
kernel reads them — never correctness beyond the dtype-derived tolerance
of :func:`repro.kernels.quant.logits_guard_tol`.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro import configs
from repro.core.plan import make_plan
from repro.kernels import quant, ref
from repro.serving import kvquant

settings.register_profile("fast", max_examples=20, deadline=None)
settings.load_profile("fast")

SPECS = [quant.INT8] + ([quant.FP8] if quant.fp8_supported() else [])
SPEC_IDS = [s.name for s in SPECS]

TOL = dict(rtol=2e-5, atol=2e-5)


def _roundtrip_ok(x, spec):
    """quantize_pages -> dequantize_pages error within the analytic bound."""
    codes, steps = kvquant.quantize_pages(jnp.asarray(x, jnp.float32), spec)
    y = kvquant.dequantize_pages(codes, steps)
    bound = quant.roundtrip_bound(
        jnp.asarray(x, jnp.float32), steps[..., None, :], spec)
    err = jnp.abs(y - jnp.asarray(x, jnp.float32))
    # small fp slack: the bound itself is computed in f32
    assert bool(jnp.all(err <= bound * (1 + 1e-5) + 1e-30)), (
        spec.name, float(jnp.max(err - bound)))
    return codes, steps, y


# ---------------------------------------------------------------------------
# Round-trip error vs the analytic bound (hypothesis + edge cases)
# ---------------------------------------------------------------------------


@given(st.sampled_from(SPECS),
       st.integers(min_value=1, max_value=3),
       st.integers(min_value=1, max_value=3),
       st.sampled_from([1, 4, 8]),
       st.floats(min_value=-3.0, max_value=3.0),
       st.booleans())
def test_roundtrip_error_bounded(spec, npages, hk, d, log_scale, outlier):
    rng = np.random.default_rng(npages * 100 + hk * 10 + d)
    x = rng.normal(size=(npages, 8, hk, d)) * 10.0 ** log_scale
    if outlier:
        # one huge element per page: the shared step grows, every other
        # element's absolute error grows with it — the bound must track
        x[:, 0, 0, 0] *= 1e4
    _roundtrip_ok(x, spec)


@pytest.mark.parametrize("spec", SPECS, ids=SPEC_IDS)
def test_all_zero_pages_roundtrip_exactly(spec):
    x = np.zeros((3, 8, 2, 4), np.float32)
    codes, steps, y = _roundtrip_ok(x, spec)
    # zero content -> step exactly 0.0 (the "empty page" sentinel), zero
    # codes, and a bitwise-zero decode
    assert bool(jnp.all(steps == 0.0))
    assert bool(jnp.all(codes.astype(jnp.float32) == 0.0))
    assert bool(jnp.all(y == 0.0))


@pytest.mark.parametrize("spec", SPECS, ids=SPEC_IDS)
def test_single_outlier_row_keeps_bound(spec):
    """A single-outlier page stretches the shared step by 1e6: small
    elements collapse to few (or zero) codes but stay within the bound,
    and the outlier itself round-trips at its relative precision."""
    rng = np.random.default_rng(0)
    x = rng.normal(size=(1, 8, 1, 8)).astype(np.float32)
    x[0, 3, 0, 5] = 1e6
    _, _, y = _roundtrip_ok(x, spec)
    rel = float(jnp.abs(y[0, 3, 0, 5] - 1e6) / 1e6)
    assert rel <= (0.5 / spec.qmax if spec.is_int else 2.0 ** -4) * 1.001


@pytest.mark.parametrize("spec", SPECS, ids=SPEC_IDS)
def test_bf16_subnormal_pages_roundtrip(spec):
    """Pages of bf16 subnormals (smallest magnitudes the activation dtype
    can store) must quantize without inf/nan steps or bound violations —
    including when XLA's flush-to-zero collapses the subnormal step
    itself (the decode is then exactly zero, error ~1e-40)."""
    tiny = np.float32(2.0 ** -133)             # bf16 subnormal range
    x = (np.asarray(jnp.asarray(
        np.array([[tiny, -tiny, 2 * tiny, 0.0]] * 8, np.float32)
        .reshape(1, 8, 1, 4), jnp.bfloat16), np.float32))
    codes, steps, y = _roundtrip_ok(x, spec)
    assert np.isfinite(np.asarray(steps)).all()
    assert np.isfinite(np.asarray(y)).all()
    # a page mixing subnormals with one normal value must keep a normal
    # step: the normal element survives, the subnormals round to zero
    # codes within the half-step bound
    x2 = x.copy()
    x2[0, 0, 0, 0] = 1.0
    codes2, steps2, _ = _roundtrip_ok(x2, spec)
    assert float(steps2[0, 0]) > 0.0
    assert bool(jnp.any(codes2.astype(jnp.float32) != 0.0))


# ---------------------------------------------------------------------------
# Write-scatter algebra
# ---------------------------------------------------------------------------

_PS, _HK, _D, _NP, _NB = 4, 2, 4, 6, 4


def _fresh_pools(spec):
    codes = jnp.zeros((_NP, _PS, _HK, _D), spec.code_dtype)
    steps = jnp.zeros((_NP, _HK), jnp.float32)
    return codes, steps


def _scatter_seq(spec, content, bt, chunk_sizes, codes=None, steps=None,
                 start=0):
    """Append ``content`` (T, HK, D) through successive chunks."""
    if codes is None:
        codes, steps = _fresh_pools(spec)
    length = start
    off = 0
    for c in chunk_sizes:
        new = jnp.zeros((1, c, _HK, _D), jnp.float32)
        new = new.at[0, :c].set(content[off:off + c])
        codes, steps = kvquant.scatter_chunk_quantized(
            codes, steps, new, bt, jnp.asarray([length], jnp.int32),
            jnp.asarray([c], jnp.int32), spec)
        length += c
        off += c
    return codes, steps


@pytest.mark.parametrize("spec", SPECS, ids=SPEC_IDS)
def test_scatter_is_chunk_partition_invariant(spec):
    """Steps are a pure function of page content (scatter-max is
    order-free) for *every* partition; codes additionally settle bitwise
    when no page is written by more than one chunk (the partitions the
    chunked-prefill engine emits). A partition that splits a page
    double-rounds its early tokens — still within one extra quantization
    step of the single-shot encoding."""
    rng = np.random.default_rng(1)
    content = jnp.asarray(rng.normal(size=(10, _HK, _D)), jnp.float32)
    bt = jnp.asarray([[2, 0, 5, 3]], jnp.int32)
    a = _scatter_seq(spec, content, bt, [4, 4, 2])     # page-aligned
    c = _scatter_seq(spec, content, bt, [10])          # single shot
    assert bool(jnp.all(a[0] == c[0]))
    assert bool(jnp.all(a[1] == c[1]))

    b = _scatter_seq(spec, content, bt, [3, 3, 3, 1])  # splits pages
    assert bool(jnp.all(b[1] == c[1]))                 # steps still equal
    da = kvquant.dequantize_pages(a[0], a[1])
    db = kvquant.dequantize_pages(b[0], b[1])
    bound = quant.roundtrip_bound(da, a[1][..., None, :], spec)
    assert bool(jnp.all(jnp.abs(da - db) <= 2.0 * bound + 1e-30))


@pytest.mark.parametrize("spec", SPECS, ids=SPEC_IDS)
def test_page_aligned_scatter_equals_one_shot_quantization(spec):
    """A page written by exactly one page-aligned chunk holds the same
    codes as one-shot whole-page quantization — the identity that makes
    prefill-chunked pages comparable to quantize_pages oracles."""
    rng = np.random.default_rng(2)
    content = jnp.asarray(rng.normal(size=(8, _HK, _D)), jnp.float32)
    bt = jnp.asarray([[4, 1, 0, 0]], jnp.int32)
    codes, steps = _scatter_seq(spec, content, bt, [4, 4])
    want_codes, want_steps = kvquant.quantize_pages(
        content.reshape(2, _PS, _HK, _D), spec)
    assert bool(jnp.all(codes[jnp.asarray([4, 1])] == want_codes))
    assert bool(jnp.all(steps[jnp.asarray([4, 1])] == want_steps))


@pytest.mark.parametrize("spec", SPECS, ids=SPEC_IDS)
def test_reused_page_cannot_inherit_stale_step(spec):
    """enters-at-zero: a physical page freed by one sequence and reused by
    another (written again from its position 0) ends bitwise equal to the
    same write into a fresh pool — stale steps and codes are laundered."""
    rng = np.random.default_rng(3)
    big = jnp.asarray(rng.normal(size=(4, _HK, _D)) * 1e3, jnp.float32)
    small = jnp.asarray(rng.normal(size=(4, _HK, _D)), jnp.float32)
    bt = jnp.asarray([[2, 0, 0, 0]], jnp.int32)

    dirty = _scatter_seq(spec, big, bt, [4])              # first tenant
    codes, steps = _scatter_seq(spec, small, bt, [4],
                                codes=dirty[0], steps=dirty[1])
    fresh_codes, fresh_steps = _scatter_seq(spec, small, bt, [4])
    assert bool(jnp.all(codes[2] == fresh_codes[2]))
    assert bool(jnp.all(steps[2] == fresh_steps[2]))


@pytest.mark.parametrize("spec", SPECS, ids=SPEC_IDS)
def test_scatter_drops_invalid_lanes(spec):
    """chunk_lens == 0 rows write nothing — pools stay bitwise."""
    codes, steps = _fresh_pools(spec)
    new = jnp.ones((1, 4, _HK, _D), jnp.float32)
    bt = jnp.asarray([[1, 0, 0, 0]], jnp.int32)
    out_codes, out_steps = kvquant.scatter_chunk_quantized(
        codes, steps, new, bt, jnp.asarray([0], jnp.int32),
        jnp.asarray([0], jnp.int32), spec)
    assert bool(jnp.all(out_codes == codes))
    assert bool(jnp.all(out_steps == steps))


# ---------------------------------------------------------------------------
# In-kernel dequant parity: Pallas kernels vs dequantized-pool oracles
# ---------------------------------------------------------------------------


def _quantized_fixture(spec, seed=0):
    """f32 pools + their quantized twins, disjoint per-row page maps."""
    rng = np.random.default_rng(seed)
    b, hq, hk, d, ps, num_pages, nb = 3, 8, 2, 64, 16, 24, 8
    q = jnp.asarray(rng.normal(size=(b, hq, d)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(num_pages, ps, hk, d)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(num_pages, ps, hk, d)), jnp.float32)
    kc, ks = kvquant.quantize_pages(kp, spec)
    vc, vs = kvquant.quantize_pages(vp, spec)
    perm = rng.permutation(num_pages)
    bt = np.full((b, nb), num_pages, np.int32)
    for i in range(b):
        bt[i] = perm[i * nb:(i + 1) * nb]
    bt[2, 5:] = num_pages
    lengths = jnp.asarray([100, 37, 5 * ps], jnp.int32)
    return q, (kc, ks), (vc, vs), jnp.asarray(bt), lengths


@pytest.mark.parametrize("spec", SPECS, ids=SPEC_IDS)
def test_paged_decode_kernels_dequantize_in_kernel(spec):
    """The decode kernels (unified-max + sync) fed quantized pools match
    the oracle run on the pool-level dequant view — the full-precision
    slab the kernels never materialize."""
    from repro.kernels.decode_attention import (
        paged_decode_attention_sync, paged_decode_attention_unified_max)
    q, (kc, ks), (vc, vs), bt, lengths = _quantized_fixture(spec)
    kd = ref.dequantize_pool_ref(kc, ks)
    vd = ref.dequantize_pool_ref(vc, vs)

    got, _ = paged_decode_attention_unified_max(
        q, kc, vc, bt, lengths, phi=0.0, k_scale=ks, v_scale=vs,
        interpret=True)
    want, _ = ref.attention_decode_paged_unified_max_ref(
        q, kd, vd, bt, lengths, phi=0.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)

    got_s = paged_decode_attention_sync(
        q, kc, vc, bt, lengths, k_scale=ks, v_scale=vs, interpret=True)
    want_s = ref.attention_decode_paged_ref(q, kd, vd, bt, lengths)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s), **TOL)


@pytest.mark.parametrize("spec", SPECS, ids=SPEC_IDS)
def test_paged_chunk_kernels_dequantize_in_kernel(spec):
    from repro.kernels.chunk_attention import (
        paged_chunk_attention_sync, paged_chunk_attention_unified_max)
    spec_fx = _quantized_fixture(spec, seed=4)
    _, (kc, ks), (vc, vs), bt, lengths = spec_fx
    rng = np.random.default_rng(5)
    b, c, hq, d = 3, 8, 8, 64
    q = jnp.asarray(rng.normal(size=(b, c, hq, d)), jnp.float32)
    kd = ref.dequantize_pool_ref(kc, ks)
    vd = ref.dequantize_pool_ref(vc, vs)

    got, _ = paged_chunk_attention_unified_max(
        q, kc, vc, bt, lengths, phi=0.0, k_scale=ks, v_scale=vs,
        interpret=True)
    want, _ = ref.attention_chunk_paged_fused_ref(
        q, kd, vd, bt, lengths, phi=0.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)

    got_s = paged_chunk_attention_sync(
        q, kc, vc, bt, lengths, k_scale=ks, v_scale=vs, interpret=True)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want), **TOL)


@pytest.mark.parametrize("spec", SPECS, ids=SPEC_IDS)
def test_grouped_kernel_dequantizes_in_kernel(spec):
    from repro.kernels.group_attention import (
        DecodeGroups, grouped_paged_decode_attention_unified_max)
    q, (kc, ks), (vc, vs), bt, lengths = _quantized_fixture(spec, seed=6)
    num_pages = kc.shape[0]
    # rows 0 and 2 share row 0's first two pages as a group prefix
    shared = np.asarray(bt)[0, :2]
    bt2 = np.asarray(bt).copy()
    bt2[2, :2] = shared
    bt2 = jnp.asarray(bt2)
    tables = np.full((1, 2), num_pages, np.int32)
    tables[0] = shared
    groups = DecodeGroups(*(jnp.asarray(a) for a in (
        tables, np.asarray([2], np.int32),
        np.asarray([32], np.int32), np.asarray([2], np.int32),
        np.asarray([[0, 2]], np.int32),
        np.asarray([0, 1, 0], np.int32),
        np.asarray([0, 0, 1], np.int32),
        np.asarray([32, 0, 32], np.int32))))
    kd = ref.dequantize_pool_ref(kc, ks)
    vd = ref.dequantize_pool_ref(vc, vs)
    got, _ = grouped_paged_decode_attention_unified_max(
        q, kc, vc, bt2, lengths, groups, phi=0.0, k_scale=ks, v_scale=vs,
        interpret=True)
    want, _ = ref.attention_decode_grouped_unified_max_ref(
        q, kd, vd, bt2, lengths, groups, phi=0.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


@pytest.mark.parametrize("spec", SPECS, ids=SPEC_IDS)
def test_ops_xla_path_equals_gathered_dequant(spec):
    """ops dispatch on the XLA backend: quantized pools route through the
    pool-level dequant view, bitwise-equal to gather-then-dequant."""
    from repro.kernels import ops
    q, (kc, ks), (vc, vs), bt, lengths = _quantized_fixture(spec, seed=7)
    plan = make_plan("xla")
    got = ops.attention_decode_paged(
        q, kc, vc, bt, lengths, plan=plan, k_scale=ks, v_scale=vs)
    kd = ref.dequantize_pool_ref(kc, ks)
    vd = ref.dequantize_pool_ref(vc, vs)
    want, _ = ref.attention_decode_paged_unified_max_ref(
        q, kd, vd, bt, lengths, phi=0.0)
    assert bool(jnp.all(got == want))


# ---------------------------------------------------------------------------
# COW forks copy scale rows
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("spec", SPECS, ids=SPEC_IDS)
def test_cow_fork_copies_scale_rows(spec):
    """The engine's fork is a tree-mapped page copy over *all* cache
    leaves: the forked page must get its own copy of the scale rows, and
    a later write to the fork must leave the source page's step alone."""
    rng = np.random.default_rng(8)
    content = jnp.asarray(rng.normal(size=(4, _HK, _D)), jnp.float32)
    codes, steps = _scatter_seq(spec, content, jnp.asarray([[1, 0, 0, 0]],
                                                           jnp.int32), [4])
    cache = {"k": codes[None], "k_scale": steps[None]}   # (L=1, NP, ...)

    src, dst = 1, 3
    forked = jax.tree.map(lambda a: a.at[:, dst].set(a[:, src]), cache)
    assert bool(jnp.all(forked["k"][:, dst] == cache["k"][:, src]))
    assert bool(jnp.all(forked["k_scale"][:, dst]
                        == cache["k_scale"][:, src]))

    # divergent write into the fork (bigger amax -> new step) must not
    # touch the source page's codes or step
    loud = jnp.asarray(rng.normal(size=(2, _HK, _D)) * 50.0, jnp.float32)
    new_codes, new_steps = _scatter_seq(
        spec, loud, jnp.asarray([[dst, 0, 0, 0]], jnp.int32), [2],
        codes=forked["k"][0], steps=forked["k_scale"][0], start=2)
    assert bool(jnp.all(new_codes[src] == cache["k"][0, src]))
    assert bool(jnp.all(new_steps[src] == cache["k_scale"][0, src]))
    assert not bool(jnp.all(new_steps[dst] == cache["k_scale"][0, src]))


# ---------------------------------------------------------------------------
# Engine-level guard: int8 decode logits vs bf16 across paged kernel paths
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def smoke_model():
    from repro.models.api import get_model
    cfg = configs.smoke(configs.get("qwen2-0.5b"))
    api = get_model(cfg)
    params = api.init_params(jax.random.PRNGKey(0))
    return cfg, api, params


_PAGE = 16


def _mk_engine(cfg, params, kv_dtype, *, prefill_mode="gather",
               sharing=False, host_pages=None, decode_group="off"):
    from repro.serving.engine import Engine
    plan = make_plan(
        "xla",
        gather_chunk="fused" if prefill_mode == "fused" else "dense",
        fused_threshold=1,
        decode_group=decode_group, group_threshold=2,
        kv_dtype=kv_dtype or "bf16")
    return Engine(cfg, params, num_slots=3, max_seq=128,
                  cache_kind="paged", page_size=_PAGE,
                  prefill_chunk=_PAGE, plan=plan, kv_dtype=kv_dtype,
                  # the tiered store rides on the prefix index
                  prefix_sharing=sharing or bool(host_pages),
                  host_pages=host_pages,
                  session_cache=bool(host_pages) or None, seed=0)


def _prompts(cfg, sharing):
    rng = np.random.default_rng(11)
    if sharing:
        head = rng.integers(1, cfg.vocab_size, size=32).astype(np.int32)
        return [np.concatenate([head, rng.integers(
            1, cfg.vocab_size, size=_PAGE).astype(np.int32)])
            for _ in range(3)]
    return [rng.integers(1, cfg.vocab_size, size=48).astype(np.int32)
            for _ in range(3)]


def _prefill_and_probe(eng, api, prompts, *, tier_roundtrip=False):
    """Admit+prefill only (no free-running decode, so the written KV is
    exactly the prompts — dense-equivalent across kv_dtypes), then probe
    one decode step's logits through the engine's own plan."""
    from repro.models.layers import LayerCtx
    from repro.serving.request import SamplingParams
    sp = SamplingParams(max_new_tokens=4, temperature=0.0)
    if tier_roundtrip:
        eng.run([(p.copy(), sp) for p in prompts])
        eng.evict_finished(flush=True)
        assert eng.tiers.host_used > 0
    for p in prompts:
        eng.submit(p.copy(), sp)
    eng._admit()
    assert len(eng.by_slot) == len(prompts)
    if tier_roundtrip:
        assert eng.stats.promoted_pages > 0, "rerun did not promote"
    rows = sorted(eng.by_slot)
    ctx = LayerCtx(cfg=eng.cfg, plan=eng.plan)
    toks = jnp.arange(1, eng.num_slots + 1, dtype=jnp.int32)
    logits, _ = api.decode_step(
        ctx, eng.params, toks, eng.cache,
        jnp.asarray(eng.slots.lengths(), jnp.int32),
        block_tables=eng.slots.block_tables())
    return np.asarray(logits, np.float32)[rows]


@pytest.mark.parametrize("tiers", [False, True], ids=["", "tiers"])
@pytest.mark.parametrize("sharing", [False, True], ids=["", "shared"])
@pytest.mark.parametrize("prefill_mode", ["gather", "fused"])
def test_int8_decode_logits_within_guard(smoke_model, prefill_mode,
                                         sharing, tiers):
    """Greedy-decode logits under kv_dtype=int8 stay within the
    dtype-derived guard vs the bf16 baseline, for prompts whose written
    KV is identical across precisions — covering {gather, fused} prefill
    x {sharing on/off} x {cold, tier round-trip}."""
    cfg, api, params = smoke_model
    prompts = _prompts(cfg, sharing)
    out = {}
    for kd in ("bf16", "int8"):
        eng = _mk_engine(cfg, params, kd, prefill_mode=prefill_mode,
                         sharing=sharing,
                         host_pages=64 if tiers else None)
        out[kd] = _prefill_and_probe(eng, api, prompts,
                                     tier_roundtrip=tiers)
    scale = float(np.abs(out["bf16"]).max())
    atol = quant.logits_guard_tol(quant.INT8) * max(scale, 1.0)
    np.testing.assert_allclose(out["int8"], out["bf16"], atol=atol, rtol=0)


def test_int8_grouped_probe_matches_ungrouped(smoke_model):
    """The grouped-decode path under int8: a full greedy run with
    decode_group=grouped produces bitwise-identical tokens to the same
    int8 run ungrouped (the grouped XLA path reconstructs the identical
    dense view through the group plan), and the sharing run actually
    forked pages — scale rows forked with them."""
    from repro.serving.request import SamplingParams
    cfg, api, params = smoke_model
    # fully identical page-aligned prompt, staged: the leader prefills and
    # commits its pages first, then the fully-covered followers arrive and
    # their final-chunk re-run must COW-fork the shared tail page
    rng = np.random.default_rng(12)
    prompt = rng.integers(1, cfg.vocab_size, size=2 * _PAGE).astype(np.int32)
    sp = SamplingParams(max_new_tokens=6, temperature=0.0)
    outs, forks = {}, {}
    for mode in ("off", "grouped"):
        eng = _mk_engine(cfg, params, "int8", sharing=True,
                         decode_group=mode)
        ra = eng.submit(prompt.copy(), sp)
        eng.step()            # leader prefills + commits, stays resident
        rb = eng.submit(prompt.copy(), sp)
        rc = eng.submit(prompt.copy(), sp)
        while not all(eng.requests[r].finished for r in (ra, rb, rc)):
            eng.step()
        outs[mode] = [eng.requests[r].tokens for r in (ra, rb, rc)]
        forks[mode] = eng.stats.cow_forks
        if mode == "grouped":
            assert eng.stats.grouped_requests > 0, \
                "grouped path never engaged"
    assert outs["grouped"] == outs["off"]
    assert min(forks.values()) > 0, "workload produced no COW forks"


def test_int8_greedy_identical_across_paged_modes(smoke_model):
    """At a fixed write history the quantized representation is a pure
    function of page content, so int8 greedy tokens are bitwise identical
    across {gather, fused} x {sharing on/off} x tier round-trip."""
    from repro.serving.request import SamplingParams
    cfg, api, params = smoke_model
    prompts = _prompts(cfg, sharing=True)
    sp = SamplingParams(max_new_tokens=5, temperature=0.0)

    def run(**kw):
        eng = _mk_engine(cfg, params, "int8", **kw)
        rounds = 2 if kw.get("host_pages") else 1
        for r in range(rounds):
            out = eng.run([(p.copy(), sp) for p in prompts])
            if r + 1 < rounds:
                eng.evict_finished(flush=True)
        if kw.get("host_pages"):
            assert eng.stats.promoted_pages > 0
        # key by submission order, not request id (the tier round-trip's
        # second round gets fresh ids)
        return [out[k] for k in sorted(out)]

    base = run(prefill_mode="gather", sharing=False)
    assert run(prefill_mode="fused", sharing=False) == base
    assert run(prefill_mode="gather", sharing=True) == base
    assert run(prefill_mode="fused", sharing=True) == base
    assert run(prefill_mode="gather", sharing=True, host_pages=64) == base


# ---------------------------------------------------------------------------
# Engine plumbing: knob validation + byte counters
# ---------------------------------------------------------------------------


def test_engine_rejects_bad_kv_dtype_combos(smoke_model):
    from repro.serving.engine import Engine
    cfg, _, params = smoke_model
    with pytest.raises(ValueError, match="paged"):
        Engine(cfg, params, num_slots=2, max_seq=64, cache_kind="dense",
               kv_dtype="int8")
    with pytest.raises(ValueError, match="kv_dtype"):
        Engine(cfg, params, num_slots=2, max_seq=64, cache_kind="paged",
               page_size=_PAGE, kv_dtype="int4")


def test_engine_adopts_plan_kv_dtype_and_counts_bytes(smoke_model):
    """kv_dtype=None adopts the plan's paged.kv_dtype; the stats counters
    report the true (scale-row-inclusive) per-page bytes and accumulate
    decode reads."""
    from repro.serving.request import SamplingParams
    cfg, api, params = smoke_model
    rng = np.random.default_rng(13)
    prompts = [rng.integers(1, cfg.vocab_size, size=20).astype(np.int32)
               for _ in range(2)]
    sp = SamplingParams(max_new_tokens=3, temperature=0.0)
    got = {}
    for kd in ("bf16", "int8"):
        eng = _mk_engine(cfg, params, None if kd == "bf16" else kd)
        if kd == "bf16":
            assert eng.kv_dtype == "bf16"     # adopted from the plan
        eng.run([(p.copy(), sp) for p in prompts])
        assert eng.stats.kv_bytes_decode_read > 0
        got[kd] = eng.stats
        # quantized leaves exist iff quantized
        assert kvquant.cache_is_quantized(eng.cache) == (kd == "int8")
    ratio = got["bf16"].kv_page_bytes / got["int8"].kv_page_bytes
    assert ratio >= 1.9
    assert (got["bf16"].kv_bytes_decode_read
            > got["int8"].kv_bytes_decode_read)


def test_int8_kv_capacity_and_decode_within_guard(smoke_model):
    """int8 pages hold >= 1.9x the tokens of bf16 pages in one HBM budget
    at qwen2-0.5b's and llama2-7b's published widths (scale rows
    included), and twelve teacher-forced decode steps from an empty
    page pool stay within the dtype-derived guard of the bf16 steps."""
    from repro.core import dispatch
    from repro.models.kvlayout import PagedLayout
    from repro.models.layers import LayerCtx
    for arch in ("qwen2-0.5b", "llama2-7b"):
        full = configs.get(arch)
        bf16, int8 = (dispatch.kv_page_bytes(full, page_size=64, kv_dtype=k)
                      for k in ("bf16", "int8"))
        assert bf16 / int8 >= 1.9, (arch, bf16, int8)

    cfg, api, params = smoke_model
    slots, steps = 2, 12
    per_seq = -(-(steps + 1) // _PAGE)
    table = jnp.arange(slots * per_seq, dtype=jnp.int32).reshape(
        slots, per_seq)
    toks = np.random.default_rng(5).integers(
        1, cfg.vocab_size, size=(steps, slots)).astype(np.int32)
    ctx = LayerCtx(cfg=cfg, plan=make_plan("xla"))
    step = jax.jit(lambda t, c, le: api.decode_step(
        ctx, params, t, c, le, block_tables=table))
    trace = {}
    for kd in ("bf16", "int8"):
        cache = api.init_cache(PagedLayout(slots * per_seq, _PAGE, kd))
        got = []
        for t in range(steps):
            logits, cache = step(jnp.asarray(toks[t]), cache,
                                 jnp.full((slots,), t, jnp.int32))
            got.append(np.asarray(logits, np.float32))
        trace[kd] = np.stack(got)
    atol = quant.logits_guard_tol(quant.INT8) * max(
        float(np.abs(trace["bf16"]).max()), 1.0)
    np.testing.assert_allclose(trace["int8"], trace["bf16"], atol=atol,
                               rtol=0)
