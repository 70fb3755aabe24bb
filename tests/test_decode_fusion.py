"""DecodeFusionPlan: plan-selected decode-layer stage granularity.

Contract under test (ISSUE 9):
  * the ``decode_fusion`` knob validates, serializes, and survives a
    backend override; pre-fusion plan documents load with the split
    default;
  * the ``ref.py`` stage oracles are expression-for-expression copies of
    the split chain (``rmsnorm``/``rope`` bitwise), so on the XLA
    backend the fused stage dispatch is bit-identical to split;
  * ``split`` and ``looped`` produce **bitwise-identical** decode logits
    (same depth scan, same per-stage jaxpr). ``fused`` python-unrolls
    the L layer bodies, which lets XLA place bf16 rounding at different
    fusion boundaries than the scan body — the one documented
    reassociated seam, held to the scheme-swap dtype-eps bound instead;
  * the Pallas stage kernels match their oracles in interpret mode to
    rounding-unit tolerance (K-stream f32 accumulation);
  * ``stack.unstack``/``restack`` round-trip stacked params bitwise (the
    unrolled path must see exactly the scanned values);
  * the engine threads granularity through dense, paged, prefix-shared,
    preempting, and quantized-KV decode ticks with greedy-identical
    tokens, and caches the positions operand under the lengths-device
    dirty discipline.
"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro import configs
from repro.core import dispatch as dsp
from repro.core.plan import (
    DEFAULT_PLAN, FUSION_MODES, DecodeFusionPlan, ExecutionPlan, PlanError,
    make_plan, tune,
)
from repro.kernels import ref
from repro.kernels.decode_fuse import (
    decode_ingest_fused, ffn_norm_fused, oproj_residual_fused,
)
from repro.models import layers as L
from repro.models import stack
from repro.models.api import get_model
from repro.models.kvlayout import DenseLayout
from repro.models.layers import LayerCtx

CFG = configs.get("qwen2-0.5b")


@pytest.fixture(scope="module")
def smoke_model():
    cfg = configs.smoke(CFG)
    api = get_model(cfg)
    params = api.init_params(jax.random.PRNGKey(0))
    return cfg, api, params


# ---------------------------------------------------------------------------
# Plan knob
# ---------------------------------------------------------------------------


def test_fusion_knob_validates():
    for g in FUSION_MODES:
        assert DecodeFusionPlan(granularity=g).granularity == g
    with pytest.raises(PlanError, match="granularity"):
        DecodeFusionPlan(granularity="megakernel")
    with pytest.raises(PlanError, match="backend"):
        DecodeFusionPlan(backend="cuda")


def test_fusion_knob_round_trips_json():
    p = make_plan(decode_fusion="looped")
    q = ExecutionPlan.from_json(p.to_json())
    assert q.decode_fusion == p.decode_fusion
    assert "fusion[looped]" in p.describe()


def test_legacy_plan_without_fusion_key_loads_split():
    """Pre-fusion plan documents must keep loading (backward compat) and
    land on the split default — the semantics they were tuned under."""
    doc = json.loads(make_plan().to_json())
    del doc["ops"]["decode_fusion"]
    p = ExecutionPlan.from_json(json.dumps(doc))
    assert p.decode_fusion.granularity == "split"


def test_backend_override_keeps_granularity():
    """with_overrides(backend=...) maps the backend but never the tuned
    granularity: on XLA the fused stages dispatch their bit-identical
    jnp oracles, so the decision stays meaningful."""
    p = make_plan(backend="pallas", decode_fusion="looped")
    q = p.with_overrides(backend="xla")
    assert q.decode_fusion.backend == "xla"
    assert q.decode_fusion.granularity == "looped"


def test_tune_covers_fusion_knob():
    p = tune(CFG)
    assert p.decode_fusion.granularity in FUSION_MODES
    # full-depth llama-class config: the stage-dispatch roofline has the
    # looped dispatch strictly cheapest (fewest stages, one loop setup)
    assert p.decode_fusion.granularity == "looped"


def test_predict_fusion_time_roofline():
    t = {g: dsp.predict_fusion_time(CFG, g) for g in FUSION_MODES}
    assert all(v > 0 for v in t.values())
    assert t["fused"] < t["split"]      # fewer stage boundaries per layer
    assert t["looped"] < t["split"]
    with pytest.raises(ValueError, match="granularity"):
        dsp.predict_fusion_time(CFG, "megakernel")
    assert dsp.find_decode_fusion(CFG) in FUSION_MODES


# ---------------------------------------------------------------------------
# Oracles == split chain (bitwise)
# ---------------------------------------------------------------------------


def test_ref_norm_and_rope_are_bitwise_copies():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, 1, 96)), jnp.bfloat16)
    scale = jnp.asarray(rng.standard_normal(96), jnp.bfloat16)
    for eps in (1e-6, 1e-5):
        assert np.array_equal(np.asarray(L.rmsnorm(x, scale, eps)),
                              np.asarray(ref.rmsnorm_ref(x, scale, eps)))
    h = x.reshape(2, 1, 3, 32)
    pos = jnp.array([[5], [170]], jnp.int32)
    assert np.array_equal(np.asarray(L.rope(h, pos, 1e4)),
                          np.asarray(ref.rope_ref(h, pos, 1e4)))


@pytest.mark.parametrize("granularity", ["fused", "looped"])
def test_stage_dispatch_bitwise_on_xla(smoke_model, granularity):
    """layers.decode_ingest / decode_epilogue on the XLA backend compose
    the exact split-chain expressions: per-stage outputs are bitwise."""
    cfg, _, _ = smoke_model
    key = jax.random.PRNGKey(3)
    p = L.attention_params(cfg, key)
    np_ = L.norm_params(cfg, cfg.d_model)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 1, cfg.d_model),
                          jnp.dtype(cfg.activation_dtype))
    pos = jnp.array([4, 9], jnp.int32)
    ctx_s = LayerCtx(cfg=cfg, plan=make_plan(decode_fusion="split"))
    ctx_g = LayerCtx(cfg=cfg, plan=make_plan(decode_fusion=granularity))
    for a, b in zip(L.decode_ingest(ctx_s, np_, p, x, pos),
                    L.decode_ingest(ctx_g, np_, p, x, pos)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    o = jax.random.normal(jax.random.PRNGKey(2), (2, 1, cfg.q_dim),
                          jnp.dtype(cfg.activation_dtype))
    res = jax.random.normal(jax.random.PRNGKey(4), (2, 1, cfg.d_model),
                            jnp.dtype(cfg.activation_dtype))
    assert np.array_equal(
        np.asarray(L.decode_epilogue(ctx_s, p, o, res)),
        np.asarray(L.decode_epilogue(ctx_g, p, o, res)))


def test_split_and_looped_decode_logits_bitwise(smoke_model):
    """Same depth scan + bitwise stages -> bitwise logits and cache."""
    cfg, api, params = smoke_model
    cache = api.init_cache(DenseLayout(2, 64))
    toks = jnp.array([3, 5], jnp.int32)
    lens = jnp.array([4, 9], jnp.int32)
    outs = {}
    for g in ("split", "looped"):
        ctx = LayerCtx(cfg=cfg, plan=make_plan(decode_fusion=g))
        logits, nc = api.decode_step(ctx, params, toks, cache, lens)
        outs[g] = (np.asarray(logits), nc)
    assert np.array_equal(outs["split"][0], outs["looped"][0])
    for a, b in zip(jax.tree.leaves(outs["split"][1]),
                    jax.tree.leaves(outs["looped"][1])):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_fused_decode_logits_value_close(smoke_model):
    """The documented exclusion: ``fused`` unrolls the L layer bodies, so
    XLA may fuse (and round) across different boundaries than the scan
    body compiles to — same expressions, different bf16 rounding
    placement. Bound it with the scheme-swap dtype-eps pattern."""
    cfg, api, params = smoke_model
    cache = api.init_cache(DenseLayout(2, 64))
    toks = jnp.array([3, 5], jnp.int32)
    lens = jnp.array([4, 9], jnp.int32)
    outs = {}
    for g in ("split", "fused"):
        ctx = LayerCtx(cfg=cfg, plan=make_plan(decode_fusion=g))
        logits, _ = api.decode_step(ctx, params, toks, cache, lens)
        outs[g] = np.asarray(logits, np.float32)
    eps = float(jnp.finfo(jnp.dtype(cfg.activation_dtype)).eps)
    scale = float(np.abs(outs["split"]).max())
    atol = 32 * eps * max(scale, 1.0)
    np.testing.assert_allclose(outs["fused"], outs["split"],
                               rtol=32 * eps, atol=atol)


# ---------------------------------------------------------------------------
# Pallas kernels vs oracles (interpret mode)
# ---------------------------------------------------------------------------


INGEST_CASES = [
    # (num_heads, num_kv_heads, head_dim, d_model, bias, rope)
    (4, 2, 32, 128, False, True),      # GQA
    (8, 8, 64, 256, True, True),       # MHA + qkv bias
    (4, 1, 64, 192, True, False),      # MQA, no rope, K not 128-multiple
    (12, 4, 64, 384, False, True),     # wider GQA, K streams in blocks
]


@pytest.mark.parametrize("hq,hk,dh,d,bias,use_rope", INGEST_CASES)
def test_ingest_kernel_matches_oracle(hq, hk, dh, d, bias, use_rope):
    rng = np.random.default_rng(hq * 1000 + d)
    m = 3
    x = jnp.asarray(rng.standard_normal((m, 1, d)), jnp.bfloat16)
    scale = jnp.asarray(rng.standard_normal(d), jnp.bfloat16)
    wq = jnp.asarray(rng.standard_normal((d, hq * dh)), jnp.bfloat16)
    wk = jnp.asarray(rng.standard_normal((d, hk * dh)), jnp.bfloat16)
    wv = jnp.asarray(rng.standard_normal((d, hk * dh)), jnp.bfloat16)
    bq = jnp.asarray(rng.standard_normal(hq * dh), jnp.bfloat16) \
        if bias else None
    bk = jnp.asarray(rng.standard_normal(hk * dh), jnp.bfloat16) \
        if bias else None
    bv = jnp.asarray(rng.standard_normal(hk * dh), jnp.bfloat16) \
        if bias else None
    pos = jnp.array([4, 9, 170], jnp.int32)
    qo, ko, vo = ref.decode_ingest_ref(
        x, scale, wq, wk, wv, pos, num_heads=hq, num_kv_heads=hk,
        head_dim=dh, eps=1e-6, use_rope=use_rope, bq=bq, bk=bk, bv=bv)
    qf, kf, vf = decode_ingest_fused(
        x.reshape(m, d), scale, wq, wk, wv, pos, num_heads=hq,
        num_kv_heads=hk, head_dim=dh, eps=1e-6, use_rope=use_rope,
        bq=bq, bk_bias=bk, bv=bv, interpret=True)
    eps = float(jnp.finfo(jnp.bfloat16).eps)
    for a, b in ((qo.reshape(m, -1), qf), (ko.reshape(m, -1), kf),
                 (vo.reshape(m, -1), vf)):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        atol = 32 * eps * max(float(np.abs(a).max()), 1.0)
        np.testing.assert_allclose(b, a, rtol=32 * eps, atol=atol)


@pytest.mark.parametrize("m,q_dim,d", [(1, 128, 128), (3, 256, 192),
                                       (8, 384, 512)])
def test_oproj_kernel_matches_oracle(m, q_dim, d):
    rng = np.random.default_rng(m * 100 + d)
    o = jnp.asarray(rng.standard_normal((m, 1, q_dim)), jnp.bfloat16)
    wo = jnp.asarray(rng.standard_normal((q_dim, d)), jnp.bfloat16)
    res = jnp.asarray(rng.standard_normal((m, 1, d)), jnp.bfloat16)
    want = ref.oproj_residual_ref(o, wo, res)
    got = oproj_residual_fused(
        o.reshape(m, q_dim), wo, res.reshape(m, d),
        interpret=True).reshape(want.shape)
    a = np.asarray(want, np.float32)
    b = np.asarray(got, np.float32)
    eps = float(jnp.finfo(jnp.bfloat16).eps)
    atol = 32 * eps * max(float(np.abs(a).max()), 1.0)
    np.testing.assert_allclose(b, a, rtol=32 * eps, atol=atol)


@pytest.mark.parametrize("m,d,f,act", [(1, 128, 256, "swiglu"),
                                       (4, 192, 384, "swiglu"),
                                       (8, 256, 512, "geglu"),
                                       (3, 384, 640, "swiglu")])
def test_ffn_norm_kernel_matches_oracle(m, d, f, act):
    rng = np.random.default_rng(m * 100 + f)
    x = jnp.asarray(rng.standard_normal((m, 1, d)), jnp.bfloat16)
    scale = jnp.asarray(rng.standard_normal(d), jnp.bfloat16)
    wg = jnp.asarray(rng.standard_normal((d, f)) / 8, jnp.bfloat16)
    wu = jnp.asarray(rng.standard_normal((d, f)) / 8, jnp.bfloat16)
    want = ref.ffn_norm_ref(x, scale, wg, wu, activation=act, eps=1e-6,
                            fused=True)
    got = ffn_norm_fused(x.reshape(m, d), scale, wg, wu, activation=act,
                         eps=1e-6, interpret=True).reshape(want.shape)
    a = np.asarray(want, np.float32)
    b = np.asarray(got, np.float32)
    eps = float(jnp.finfo(jnp.bfloat16).eps)
    atol = 32 * eps * max(float(np.abs(a).max()), 1.0)
    np.testing.assert_allclose(b, a, rtol=32 * eps, atol=atol)


@pytest.mark.parametrize("fused_ffn", [False, True])
def test_decode_mlp_stage_bitwise_on_xla(smoke_model, fused_ffn):
    """layers.decode_mlp's fused seam composes whichever split chain the
    plan's fused_ffn knob selects — bitwise either way on XLA."""
    cfg, _, _ = smoke_model
    p = L.mlp_params(cfg, jax.random.PRNGKey(5))
    np_ = L.norm_params(cfg, cfg.d_model)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 1, cfg.d_model),
                          jnp.dtype(cfg.activation_dtype))
    ctx_s = LayerCtx(cfg=cfg, plan=make_plan(decode_fusion="split",
                                             fused_ffn=fused_ffn))
    ctx_g = LayerCtx(cfg=cfg, plan=make_plan(decode_fusion="looped",
                                             fused_ffn=fused_ffn))
    assert np.array_equal(
        np.asarray(L.decode_mlp(ctx_s, np_, p, x)),
        np.asarray(L.decode_mlp(ctx_g, np_, p, x)))


def test_ops_dispatch_routes_by_plan(smoke_model):
    """ops.decode_ingest/oproj_residual: pallas backend runs the fused
    kernels (interpret on CPU), xla backend runs the oracles — and the
    two agree to dtype-eps."""
    from repro.kernels import ops
    cfg, _, _ = smoke_model
    rng = np.random.default_rng(5)
    d, hq, hk, dh = (cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
                     cfg.head_dim)
    x = jnp.asarray(rng.standard_normal((2, 1, d)), jnp.bfloat16)
    scale = jnp.asarray(rng.standard_normal(d), jnp.bfloat16)
    wq = jnp.asarray(rng.standard_normal((d, hq * dh)), jnp.bfloat16)
    wk = jnp.asarray(rng.standard_normal((d, hk * dh)), jnp.bfloat16)
    wv = jnp.asarray(rng.standard_normal((d, hk * dh)), jnp.bfloat16)
    pos = jnp.array([4, 9], jnp.int32)
    kw = dict(num_heads=hq, num_kv_heads=hk, head_dim=dh, eps=1e-6)
    eps = float(jnp.finfo(jnp.bfloat16).eps)
    ref_out = ops.decode_ingest(
        x, scale, wq, wk, wv, pos,
        plan=make_plan(backend="xla", decode_fusion="fused"), **kw)
    pal_out = ops.decode_ingest(
        x, scale, wq, wk, wv, pos,
        plan=make_plan(backend="pallas", decode_fusion="fused"), **kw)
    for a, b in zip(ref_out, pal_out):
        assert a.shape == b.shape
        a = np.asarray(a, np.float32)
        atol = 32 * eps * max(float(np.abs(a).max()), 1.0)
        np.testing.assert_allclose(np.asarray(b, np.float32), a,
                                   rtol=32 * eps, atol=atol)


# ---------------------------------------------------------------------------
# stack restacking round-trip (the unrolled path's foundation)
# ---------------------------------------------------------------------------


@given(st.integers(min_value=1, max_value=5),
       st.integers(min_value=1, max_value=4),
       st.sampled_from([jnp.float32, jnp.bfloat16, jnp.int32]))
@settings(max_examples=20, deadline=None)
def test_unstack_restack_round_trip_bitwise(layers, width, dtype):
    rng = np.random.default_rng(layers * 10 + width)
    tree = {
        "w": jnp.asarray(rng.standard_normal((layers, width, 8)), dtype),
        "sub": {"b": jnp.asarray(
            rng.standard_normal((layers, width)), dtype)},
    }
    per_layer = stack.unstack(tree)
    assert len(per_layer) == layers
    back = stack.restack(per_layer)
    assert jax.tree_util.tree_structure(back) == \
        jax.tree_util.tree_structure(tree)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        assert a.dtype == b.dtype
        assert np.array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# Engine: greedy identity across granularities
# ---------------------------------------------------------------------------


def _run_engine(cfg, params, prompts, max_new=5, **kw):
    from repro.serving.engine import Engine
    from repro.serving.request import SamplingParams
    eng = Engine(cfg, params, num_slots=2, max_seq=64, **kw)
    sp = SamplingParams(max_new_tokens=max_new, temperature=0.0)
    out = eng.run([(p, sp) for p in prompts])
    return eng, [out[k] for k in sorted(out)]


@pytest.fixture(scope="module")
def engine_prompts(smoke_model):
    cfg, _, _ = smoke_model
    rng = np.random.default_rng(7)
    return [rng.integers(1, cfg.vocab_size, size=n).astype(np.int32)
            for n in (11, 26)]


def test_engine_greedy_identity_dense(smoke_model, engine_prompts):
    cfg, _, params = smoke_model
    outs = {g: _run_engine(cfg, params, engine_prompts,
                           decode_fusion=g)[1]
            for g in FUSION_MODES}
    assert outs["split"] == outs["fused"] == outs["looped"]


@pytest.mark.parametrize("sharing", [False, True])
def test_engine_greedy_identity_paged(smoke_model, engine_prompts,
                                      sharing):
    cfg, _, params = smoke_model
    kw = dict(cache_kind="paged", page_size=16, prefill_chunk=16,
              prefix_sharing=sharing)
    outs = {g: _run_engine(cfg, params, engine_prompts,
                           decode_fusion=g, **kw)[1]
            for g in FUSION_MODES}
    assert outs["split"] == outs["fused"] == outs["looped"]


def test_engine_greedy_identity_quantized_kv(smoke_model, engine_prompts):
    cfg, _, params = smoke_model
    kw = dict(cache_kind="paged", page_size=16, prefill_chunk=16,
              kv_dtype="int8")
    outs = {g: _run_engine(cfg, params, engine_prompts,
                           decode_fusion=g, **kw)[1]
            for g in FUSION_MODES}
    assert outs["split"] == outs["fused"] == outs["looped"]


def test_engine_greedy_identity_under_preemption(smoke_model):
    """Overcommitted pool forces mid-decode preemption (partial pages,
    re-prefill); granularity must not change a single token."""
    cfg, _, params = smoke_model
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, cfg.vocab_size, size=n).astype(np.int32)
               for n in (9, 10)]
    kw = dict(cache_kind="paged", page_size=16, prefill_chunk=16,
              num_pages=4)
    outs = {}
    for g in FUSION_MODES:
        eng, toks = _run_engine(cfg, params, prompts, max_new=26,
                                decode_fusion=g, **kw)
        outs[g] = toks
        assert eng.stats.preemptions > 0, "pool was never under pressure"
    assert outs["split"] == outs["fused"] == outs["looped"]


def test_engine_fusion_arg_wins_over_plan(smoke_model):
    from repro.serving.engine import Engine
    cfg, _, params = smoke_model
    plan = make_plan(decode_fusion="split")
    eng = Engine(cfg, params, num_slots=2, max_seq=64, plan=plan,
                 decode_fusion="looped")
    assert eng.decode_fusion == "looped"
    assert eng.ctx.plan.decode_fusion.granularity == "looped"
    # plan knob adopted when the arg is absent
    eng2 = Engine(cfg, params, num_slots=2, max_seq=64,
                  plan=make_plan(decode_fusion="fused"))
    assert eng2.decode_fusion == "fused"
    with pytest.raises(ValueError, match="decode_fusion"):
        Engine(cfg, params, num_slots=2, max_seq=64,
               decode_fusion="megakernel")


# ---------------------------------------------------------------------------
# positions operand: device cache under the lengths dirty discipline
# ---------------------------------------------------------------------------


def test_positions_device_cached_and_dirty_tracked():
    from repro.serving.kvcache import SlotManager
    mgr = SlotManager(3, 64)
    p0 = mgr.positions_device()
    assert p0.dtype == jnp.int32 and p0.shape == (3,)
    assert mgr.positions_device() is p0          # clean -> same buffer
    idx = mgr.try_assign(0, 5, 4)
    assert idx is not None
    p1 = mgr.positions_device()
    assert p1 is not p0                          # assign dirtied it
    assert int(p1[idx]) == 5
    assert mgr.positions_device() is p1
    mgr.tick(idx, wrote_kv=False)                # prefill token: no KV
    assert mgr.positions_device() is p1          # ... so still clean
    mgr.tick(idx, wrote_kv=True)
    p2 = mgr.positions_device()
    assert p2 is not p1 and int(p2[idx]) == 6
    mgr.release(idx)
    p3 = mgr.positions_device()
    assert p3 is not p2 and int(p3[idx]) == 0
    # positions mirror lengths for every family today
    assert np.array_equal(np.asarray(p3), np.asarray(mgr.lengths_device()))


def test_decode_step_accepts_positions_operand(smoke_model):
    """positions=None defaults to lengths; passing the explicit operand
    with the same values is bitwise identical (the engine path)."""
    cfg, api, params = smoke_model
    cache = api.init_cache(DenseLayout(2, 64))
    toks = jnp.array([3, 5], jnp.int32)
    lens = jnp.array([4, 9], jnp.int32)
    ctx = LayerCtx(cfg=cfg, plan=make_plan(decode_fusion="looped"))
    a, _ = api.decode_step(ctx, params, toks, cache, lens)
    b, _ = api.decode_step(ctx, params, toks, cache, lens,
                           positions=jnp.asarray(lens))
    assert np.array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# Bench artifact smoke (fast lane)
# ---------------------------------------------------------------------------


def test_fusion_bench_smoke(tmp_path, monkeypatch):
    """benchmarks.decode_fusion --quick emits a well-formed
    BENCH_fusion.json sidecar showing the headline result: the fused
    granularities cut the batch-1 decode-tick dispatch count >= 2x."""
    from benchmarks import decode_fusion
    monkeypatch.setattr(decode_fusion, "OUT_PATH",
                        str(tmp_path / "BENCH_fusion.json"))
    result = decode_fusion.run(quick=True)
    assert (tmp_path / "BENCH_fusion.quick.json").exists()
    assert not (tmp_path / "BENCH_fusion.json").exists()
    assert result["mode"] == "quick"
    counts = result["dispatches_per_tick"]
    assert set(counts) == {"split", "fused", "looped"}
    # the acceptance bar: >= 2x fewer dispatches per tick at batch 1
    assert counts["split"] >= 2 * counts["looped"]
    assert counts["split"] >= 2 * counts["fused"]
    # wall clock is noise-bounded on CPU (split and looped compile the
    # same XLA program) — just require sane, same-ballpark numbers
    for row in result["latency"]:
        assert row["split_us"] > 0
        assert row["looped_over_split"] < 1.5
