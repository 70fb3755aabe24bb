"""Quantized GEMM weight subsystem: per-output-channel round-trips,
in-kernel dequant parity for every GEMM path, the engine-level logits
guard across {split, fused, looped} x {dense, paged+sharing}, and the
bf16 bitwise-identity regression.

The plan's contract (the weight-side twin of test_kvquant.py):
``weight_dtype`` may change the bytes behind every GEMM weight read and
which kernel epilogue runs — never correctness beyond the dtype-derived
tolerance of :func:`repro.kernels.quant.logits_guard_tol`, and the bf16
path must stay bitwise untouched.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro import configs
from repro.core import dispatch
from repro.core.plan import (DEFAULT_PLAN, WEIGHT_DTYPES, ExecutionPlan,
                             PlanError, make_plan)
from repro.kernels import quant, ref
from repro.kernels.flat_gemm import flat_gemm
from repro.kernels.fused_ffn import fused_ffn_up
from repro.kernels.gemv import gemv
from repro.models import wquant

settings.register_profile("fast", max_examples=20, deadline=None)
settings.load_profile("fast")

SPECS = [quant.INT8] + ([quant.FP8] if quant.fp8_supported() else [])
SPEC_IDS = [s.name for s in SPECS]


# ---------------------------------------------------------------------------
# quantize-at-load round-trips: per-output-channel algebra
# ---------------------------------------------------------------------------


def _roundtrip_ok(w, spec):
    """quantize_weight -> dequantize_weight within the analytic bound,
    checked per output channel (the step axis)."""
    w = jnp.asarray(w, jnp.float32)
    wq = wquant.quantize_weight(w, spec)
    y = wquant.dequantize_weight(wq)
    # roundtrip_bound works on the step-last layout the encode ran in
    wt = jnp.swapaxes(w, -1, -2)
    bound = quant.roundtrip_bound(wt, wq["scale"], spec)
    err = jnp.abs(jnp.swapaxes(y, -1, -2) - wt)
    assert bool(jnp.all(err <= bound * (1 + 1e-5) + 1e-30)), (
        spec.name, float(jnp.max(err - bound)))
    return y


@given(st.sampled_from(SPECS), st.integers(0, 2 ** 31 - 1))
def test_roundtrip_within_bound(spec, seed):
    rng = np.random.default_rng(seed)
    w = rng.normal(size=(24, 16)) * rng.uniform(0.01, 10.0)
    _roundtrip_ok(w, spec)


@pytest.mark.parametrize("spec", SPECS, ids=SPEC_IDS)
def test_outlier_channel_does_not_poison_neighbors(spec):
    """One loud output channel must not inflate the error of the quiet
    ones — that is what per-channel (vs per-tensor) steps buy."""
    rng = np.random.default_rng(0)
    w = rng.normal(size=(32, 8)).astype(np.float32) * 0.1
    w[:, 3] *= 1000.0                       # outlier output channel
    deq = np.asarray(_roundtrip_ok(w, spec))
    quiet = [n for n in range(8) if n != 3]
    err_quiet = np.abs(deq[:, quiet] - w[:, quiet]).max()
    # a per-tensor step would be ~1000x coarser on the quiet channels
    per_tensor_step = np.abs(w).max() / spec.qmax
    assert err_quiet < per_tensor_step / 10


@pytest.mark.parametrize("spec", SPECS, ids=SPEC_IDS)
def test_all_zero_channel_roundtrips_exactly(spec):
    rng = np.random.default_rng(1)
    w = rng.normal(size=(16, 6)).astype(np.float32)
    w[:, 2] = 0.0
    wq = wquant.quantize_weight(jnp.asarray(w), spec)
    assert bool(jnp.all(wq["codes"][:, 2].astype(jnp.float32) == 0.0))
    deq = np.asarray(wquant.dequantize_weight(wq))
    assert np.all(deq[:, 2] == 0.0)
    assert np.all(np.isfinite(deq))


@pytest.mark.parametrize("spec", SPECS, ids=SPEC_IDS)
def test_stacked_leaves_quantize_per_layer(spec):
    """(L, K, N) leaves get independent per-(layer, channel) steps."""
    rng = np.random.default_rng(2)
    w = rng.normal(size=(3, 8, 4)).astype(np.float32)
    w[1] *= 100.0
    wq = wquant.quantize_weight(jnp.asarray(w), spec)
    assert wq["codes"].shape == (3, 8, 4)
    assert wq["scale"].shape == (3, 4)
    per_layer = [wquant.quantize_weight(jnp.asarray(w[i]), spec)
                 for i in range(3)]
    for i in range(3):
        assert bool(jnp.all(wq["codes"][i] == per_layer[i]["codes"]))
        assert bool(jnp.all(wq["scale"][i] == per_layer[i]["scale"]))


def test_quantize_params_touches_only_weight_keys():
    rng = np.random.default_rng(3)
    mk = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    params = {
        "embedding": mk(32, 8),
        "layers": {"wq": mk(2, 8, 8), "bq": mk(2, 8),
                   "w_up": mk(2, 8, 16), "norm1": mk(2, 8)},
        "lm_head": mk(8, 32),
    }
    out = wquant.quantize_params(params, quant.INT8)
    assert wquant.is_quantized_leaf(out["layers"]["wq"])
    assert wquant.is_quantized_leaf(out["layers"]["w_up"])
    # everything else rides through by identity
    for key in ("embedding", "lm_head"):
        assert out[key] is params[key]
    for key in ("bq", "norm1"):
        assert out["layers"][key] is params["layers"][key]
    # byte accounting: codes + scales, weight keys only
    got = wquant.gemm_weight_bytes(out)
    want = sum(out["layers"][k]["codes"].nbytes
               + out["layers"][k]["scale"].nbytes for k in ("wq", "w_up"))
    assert got == want
    bf16_bytes = wquant.gemm_weight_bytes(params)
    assert bf16_bytes / got > 1.9           # f32 leaves vs int8 codes


# ---------------------------------------------------------------------------
# kernel-vs-oracle parity for every quantized GEMM path
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def gemm_case():
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 4)
    M, K, N = 4, 256, 384
    x = jax.random.normal(ks[0], (M, K), jnp.bfloat16)
    mkw = lambda k: (jax.random.normal(k, (K, N)) * 0.05).astype(jnp.bfloat16)
    return x, mkw(ks[1]), mkw(ks[2]), mkw(ks[3])


@pytest.mark.parametrize("spec", SPECS, ids=SPEC_IDS)
def test_flat_gemm_quant_matches_oracle(gemm_case, spec):
    x, w, _, _ = gemm_case
    wq = wquant.quantize_weight(w, spec)
    want = ref.flat_gemm_ref(x, wq["codes"], w_scale=wq["scale"])
    got = flat_gemm(x, wq["codes"], w_scale=wq["scale"], interpret=True)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


@pytest.mark.parametrize("spec", SPECS, ids=SPEC_IDS)
def test_gemv_quant_matches_oracle(gemm_case, spec):
    x, w, _, _ = gemm_case
    wq = wquant.quantize_weight(w, spec)
    want = ref.gemv_ref(x[:1], wq["codes"], w_scale=wq["scale"])
    got = gemv(x[:1], wq["codes"], w_scale=wq["scale"], interpret=True)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


@pytest.mark.parametrize("spec", SPECS, ids=SPEC_IDS)
def test_fused_ffn_quant_matches_oracle(gemm_case, spec):
    x, _, wg, wu = gemm_case
    gq = wquant.quantize_weight(wg, spec)
    uq = wquant.quantize_weight(wu, spec)
    want = ref.fused_ffn_up_ref(x, gq["codes"], uq["codes"],
                                wg_scale=gq["scale"], wu_scale=uq["scale"])
    got = fused_ffn_up(x, gq["codes"], uq["codes"], wg_scale=gq["scale"],
                       wu_scale=uq["scale"], interpret=True)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


@pytest.fixture(scope="module")
def seam_case():
    key = jax.random.PRNGKey(1)
    ks = jax.random.split(key, 8)
    B, D, HQ, HK, Dh, F = 2, 128, 4, 2, 32, 256
    mkw = lambda k, *s: (jax.random.normal(k, s) * 0.05).astype(jnp.bfloat16)
    return dict(
        B=B, D=D, HQ=HQ, HK=HK, Dh=Dh, F=F,
        x=jax.random.normal(ks[0], (B, 1, D), jnp.bfloat16),
        ns=(1 + 0.1 * jax.random.normal(ks[1], (D,))).astype(jnp.bfloat16),
        wq=mkw(ks[2], D, HQ * Dh), wk=mkw(ks[3], D, HK * Dh),
        wv=mkw(ks[4], D, HK * Dh), wo=mkw(ks[5], HQ * Dh, D),
        wg=mkw(ks[6], D, F), wu=mkw(ks[7], D, F),
        o=jax.random.normal(ks[5], (B, 1, HQ * Dh), jnp.bfloat16),
        pos=jnp.arange(2, dtype=jnp.int32) + 3,
    )


def _plans():
    mk = lambda be: dataclasses.replace(
        DEFAULT_PLAN, decode_fusion=dataclasses.replace(
            DEFAULT_PLAN.decode_fusion, backend=be))
    return [("pallas", mk("pallas")), ("xla", mk("xla"))]


@pytest.mark.parametrize("spec", SPECS, ids=SPEC_IDS)
def test_decode_ingest_quant_matches_oracle(seam_case, spec):
    from repro.kernels import ops
    c = seam_case
    Q = lambda w: wquant.quantize_weight(w, spec)
    qq, qk, qv = Q(c["wq"]), Q(c["wk"]), Q(c["wv"])
    want = ref.decode_ingest_ref(
        c["x"], c["ns"], qq["codes"], qk["codes"], qv["codes"], c["pos"],
        num_heads=c["HQ"], num_kv_heads=c["HK"], head_dim=c["Dh"], eps=1e-6,
        wq_scale=qq["scale"], wk_scale=qk["scale"], wv_scale=qv["scale"])
    for name, plan in _plans():
        got = ops.decode_ingest(
            c["x"], c["ns"], {"codes": qq["codes"], "scale": qq["scale"]},
            {"codes": qk["codes"], "scale": qk["scale"]},
            {"codes": qv["codes"], "scale": qv["scale"]}, c["pos"],
            num_heads=c["HQ"], num_kv_heads=c["HK"], head_dim=c["Dh"],
            eps=1e-6, plan=plan)
        for a, b in zip(got, want):
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32),
                                          err_msg=name)


@pytest.mark.parametrize("spec", SPECS, ids=SPEC_IDS)
def test_oproj_residual_quant_matches_oracle(seam_case, spec):
    from repro.kernels import ops
    c = seam_case
    oq = wquant.quantize_weight(c["wo"], spec)
    want = ref.oproj_residual_ref(c["o"], oq["codes"], c["x"],
                                  w_scale=oq["scale"])
    for name, plan in _plans():
        got = ops.oproj_residual(c["o"], oq, c["x"], plan=plan)
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32),
                                      err_msg=name)


@pytest.mark.parametrize("spec", SPECS, ids=SPEC_IDS)
def test_ffn_norm_quant_matches_oracle(seam_case, spec):
    from repro.kernels import ops
    c = seam_case
    gq = wquant.quantize_weight(c["wg"], spec)
    uq = wquant.quantize_weight(c["wu"], spec)
    # the xla path composes the plan's fused_ffn knob; compare per-plan
    for name, plan in _plans():
        want = ref.ffn_norm_ref(c["x"], c["ns"], gq["codes"], uq["codes"],
                                eps=1e-6, fused=plan.fused_ffn.fused,
                                wg_scale=gq["scale"], wu_scale=uq["scale"])
        got = ops.ffn_norm(c["x"], c["ns"], gq, uq, eps=1e-6, plan=plan)
        if name == "pallas":
            # fused kernel == fused oracle composition bitwise
            want = ref.ffn_norm_ref(
                c["x"], c["ns"], gq["codes"], uq["codes"], eps=1e-6,
                fused=True,
                wg_scale=gq["scale"], wu_scale=uq["scale"])
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32),
                                      err_msg=name)


@pytest.mark.parametrize("spec", SPECS, ids=SPEC_IDS)
def test_quant_gemm_error_vs_bf16_within_bound(gemm_case, spec):
    """The quantized GEMM vs the full-precision GEMM: error bounded by
    the K-summed per-channel round-trip bound (the algebra the epilogue
    scale distributes over the reduction)."""
    x, w, _, _ = gemm_case
    wq = wquant.quantize_weight(w, spec)
    got = np.asarray(
        ref.flat_gemm_ref(x, wq["codes"], w_scale=wq["scale"]), np.float32)
    want = np.asarray(
        jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32)), np.float32)
    wt = jnp.swapaxes(w.astype(jnp.float32), -1, -2)
    per_elt = quant.roundtrip_bound(wt, wq["scale"], spec)  # (N, K)
    bound = np.asarray(jnp.abs(x.astype(jnp.float32)) @ per_elt.T)
    # bf16 output rounding of the quantized path adds half-ulp slack
    slack = np.abs(got) * 2.0 ** -8 + 1e-6
    assert np.all(np.abs(got - want) <= bound + slack)


# ---------------------------------------------------------------------------
# plan knob, decision flow, and byte model
# ---------------------------------------------------------------------------


def test_weight_dtype_knob_validates():
    with pytest.raises(PlanError, match="weight_dtype"):
        make_plan(weight_dtype="int3")
    for wd in WEIGHT_DTYPES:
        assert make_plan(weight_dtype=wd).matmul.weight_dtype == wd


def test_plan_json_roundtrip_and_backcompat():
    import json
    p = make_plan(weight_dtype="int8")
    doc = json.loads(p.to_json())
    assert doc["ops"]["matmul"]["weight_dtype"] == "int8"
    assert ExecutionPlan.from_json(p.to_json()).matmul.weight_dtype == "int8"
    # pre-wquant documents load with the bf16 default
    del doc["ops"]["matmul"]["weight_dtype"]
    assert (ExecutionPlan.from_json(json.dumps(doc)).matmul.weight_dtype
            == "bf16")


def test_guard_tol_mirror_matches_quant():
    """dispatch.py is jax-free, so it mirrors logits_guard_tol as plain
    numbers — the mirror must never drift from the kernel-side truth."""
    assert dispatch.WEIGHT_GUARD_TOL["bf16"] == 0.0
    assert (dispatch.WEIGHT_GUARD_TOL["int8"]
            == pytest.approx(quant.logits_guard_tol(quant.INT8)))
    assert (dispatch.WEIGHT_GUARD_TOL["fp8"]
            == pytest.approx(quant.logits_guard_tol(quant.FP8)))
    assert set(dispatch.WEIGHT_DTYPE_BYTES) == set(WEIGHT_DTYPES)


def test_param_bytes_model():
    cfg = configs.get("qwen2-0.5b")
    b = dispatch.param_bytes(cfg, "bf16")
    i = dispatch.param_bytes(cfg, "int8")
    assert b / i >= 1.9                     # codes halve, scales are +4/K
    assert dispatch.param_bytes(cfg, "fp8") == i
    with pytest.raises(KeyError):
        dispatch.param_bytes(cfg, "int3")


def test_find_weight_dtype_decision_flow():
    cfg = configs.get("qwen2-0.5b")
    # unconstrained: the smaller stream wins, int8 ahead of fp8 on ties
    assert dispatch.find_weight_dtype(cfg) == "int8"
    # a zero tolerance budget admits only the bitwise path
    assert dispatch.find_weight_dtype(cfg, tol_budget=0.0) == "bf16"
    # budget between fp8's and int8's guard picks the admissible one
    int8_tol = dispatch.WEIGHT_GUARD_TOL["int8"]
    fp8_tol = dispatch.WEIGHT_GUARD_TOL["fp8"]
    assert fp8_tol > int8_tol
    mid = (int8_tol + fp8_tol) / 2
    assert dispatch.find_weight_dtype(cfg, tol_budget=mid) == "int8"
    with pytest.raises(ValueError):
        dispatch.find_weight_dtype(cfg, candidates=("int3",))


def test_flat_gemm_roofline_shrinks_with_weight_dtype():
    t_bf = dispatch.predict_flat_gemm_time(1, 4096, 4096)
    t_i8 = dispatch.predict_flat_gemm_time(1, 4096, 4096,
                                           weight_dtype="int8")
    assert t_i8 < t_bf
    # bf16 path must equal the existing FLAT_GEMM roofline exactly
    assert t_bf == dispatch.predict_time(dispatch.Impl.FLAT_GEMM,
                                         1, 4096, 4096)


def test_tune_threads_weight_dtype():
    cfg = configs.smoke(configs.get("qwen2-0.5b"))
    from repro.core import plan as plan_mod
    assert plan_mod.tune(cfg).matmul.weight_dtype == "bf16"  # default
    assert (plan_mod.tune(cfg, weight_dtype="int8").matmul.weight_dtype
            == "int8")
    assert (plan_mod.tune(cfg, weight_dtype=None).matmul.weight_dtype
            == dispatch.find_weight_dtype(cfg))


# ---------------------------------------------------------------------------
# engine-level guard + bf16 bitwise regression
# ---------------------------------------------------------------------------


_PAGE = 16


@pytest.fixture(scope="module")
def smoke_model():
    from repro.models.api import get_model
    cfg = configs.smoke(configs.get("qwen2-0.5b"))
    api = get_model(cfg)
    params = api.init_params(jax.random.PRNGKey(0))
    return cfg, api, params


def _mk_engine(cfg, params, weight_dtype, *, fusion="split", kind="dense",
               sharing=False):
    from repro.serving.engine import Engine
    kw = {}
    if kind == "paged":
        kw.update(page_size=_PAGE, prefill_chunk=_PAGE,
                  prefix_sharing=sharing)
    return Engine(cfg, params, num_slots=3, max_seq=128, cache_kind=kind,
                  weight_dtype=weight_dtype, decode_fusion=fusion, seed=0,
                  **kw)


def _prompts(cfg, sharing):
    rng = np.random.default_rng(11)
    if sharing:
        head = rng.integers(1, cfg.vocab_size, size=2 * _PAGE).astype(
            np.int32)
        return [np.concatenate([head, rng.integers(
            1, cfg.vocab_size, size=_PAGE).astype(np.int32)])
            for _ in range(3)]
    return [rng.integers(1, cfg.vocab_size, size=24).astype(np.int32)
            for _ in range(3)]


def _probe_logits(eng, api, prompts):
    """Admit + prefill only, then one teacher-forced decode step through
    the engine's own plan — identical token stream across precisions."""
    from repro.models.layers import LayerCtx
    from repro.serving.request import SamplingParams
    sp = SamplingParams(max_new_tokens=4, temperature=0.0)
    for p in prompts:
        eng.submit(p.copy(), sp)
    eng._admit()
    assert len(eng.by_slot) == len(prompts)
    rows = sorted(eng.by_slot)
    ctx = LayerCtx(cfg=eng.cfg, plan=eng.plan)
    toks = jnp.arange(1, eng.num_slots + 1, dtype=jnp.int32)
    logits, _ = api.decode_step(
        ctx, eng.params, toks, eng.cache,
        jnp.asarray(eng.slots.lengths(), jnp.int32),
        block_tables=(eng.slots.block_tables() if eng.pool is not None
                      else None))
    return np.asarray(logits, np.float32)[rows]


@pytest.mark.parametrize("kind,sharing",
                         [("dense", False), ("paged", True)],
                         ids=["dense", "paged+shared"])
@pytest.mark.parametrize("fusion", ["split", "fused", "looped"])
def test_quant_logits_within_guard(smoke_model, fusion, kind, sharing):
    """Teacher-forced decode logits under weight_dtype=int8 (and fp8
    where supported) stay within the dtype-derived guard vs the bf16
    baseline, across the full granularity x cache matrix."""
    cfg, api, params = smoke_model
    prompts = _prompts(cfg, sharing)
    out = {}
    for wd in ["bf16"] + SPEC_IDS:
        eng = _mk_engine(cfg, params, wd, fusion=fusion, kind=kind,
                         sharing=sharing)
        out[wd] = _probe_logits(eng, api, prompts)
    scale = max(float(np.abs(out["bf16"]).max()), 1.0)
    for s in SPECS:
        atol = quant.logits_guard_tol(s) * scale
        np.testing.assert_allclose(out[s.name], out["bf16"], atol=atol,
                                   rtol=0)


@pytest.mark.parametrize("kind", ["dense", "paged"])
@pytest.mark.parametrize("fusion", ["split", "fused", "looped"])
def test_bf16_greedy_bitwise_unchanged(smoke_model, fusion, kind):
    """weight_dtype='bf16' must be a no-op: greedy tokens identical to
    an engine that never heard of the knob (weight_dtype=None with a
    default-plan bf16 knob) for every granularity and cache kind."""
    from repro.serving.request import SamplingParams
    cfg, api, params = smoke_model
    prompts = _prompts(cfg, False)
    sp = SamplingParams(max_new_tokens=5, temperature=0.0)
    reqs = [(p.copy(), sp) for p in prompts]
    explicit = _mk_engine(cfg, params, "bf16", fusion=fusion, kind=kind)
    implicit = _mk_engine(cfg, params, None, fusion=fusion, kind=kind)
    assert implicit.weight_dtype == "bf16"
    assert explicit.run(reqs) == implicit.run(reqs)


def test_quant_greedy_runs_to_length(smoke_model):
    """int8 engines decode to full length on every granularity (the
    looped scan-body traces over (codes, scale) dict leaves). Bitwise
    identity across granularities is a bf16-only contract — quantized
    granularities are only held to the shared logits guard, which
    test_quant_logits_within_guard covers."""
    from repro.serving.request import SamplingParams
    cfg, api, params = smoke_model
    sp = SamplingParams(max_new_tokens=5, temperature=0.0)
    reqs = [(p.copy(), sp) for p in _prompts(cfg, False)]
    for fusion in ("split", "fused", "looped"):
        eng = _mk_engine(cfg, params, "int8", fusion=fusion)
        outs = eng.run(reqs)
        assert all(len(v) == 5 for v in outs.values()), fusion


def test_engine_weight_byte_accounting(smoke_model):
    """weight_bytes_decode_read counts true scale-inclusive stored bytes
    per tick; int8 shrinks the stream >= 1.9x vs bf16."""
    from repro.serving.request import SamplingParams
    cfg, api, params = smoke_model
    sp = SamplingParams(max_new_tokens=4, temperature=0.0)
    reqs = [(p.copy(), sp) for p in _prompts(cfg, False)]
    per_tick, read = {}, {}
    for wd in ("bf16", "int8"):
        eng = _mk_engine(cfg, params, wd)
        eng.run(reqs)
        per_tick[wd] = eng._weight_bytes_per_tick
        read[wd] = eng.stats.weight_bytes_decode_read
        assert wquant.gemm_weight_bytes(eng.params) == per_tick[wd]
        assert read[wd] == per_tick[wd] * eng.ticks
    assert per_tick["bf16"] / per_tick["int8"] >= 1.9
    assert read["bf16"] / read["int8"] >= 1.9


def test_engine_rejects_bad_weight_dtype(smoke_model):
    cfg, api, params = smoke_model
    with pytest.raises(ValueError, match="weight_dtype"):
        _mk_engine(cfg, params, "int3")


def test_engine_fp8_gate(smoke_model, monkeypatch):
    cfg, api, params = smoke_model
    monkeypatch.setattr(quant, "fp8_supported", lambda: False)
    with pytest.raises(ValueError, match="fp8"):
        _mk_engine(cfg, params, "fp8")


def test_engine_adopts_plan_weight_dtype(smoke_model):
    """No explicit arg: the plan's tuned matmul.weight_dtype rides in,
    and the resolved value lands back in eng.plan."""
    from repro.serving.engine import Engine
    cfg, api, params = smoke_model
    plan = make_plan(weight_dtype="int8")
    eng = Engine(cfg, params, num_slots=2, max_seq=64, plan=plan)
    assert eng.weight_dtype == "int8"
    assert eng.plan.matmul.weight_dtype == "int8"
    assert wquant.is_quantized_leaf(eng.params["layers"]["attn"]["wq"])
    assert wquant.is_quantized_leaf(eng.params["layers"]["mlp"]["w_up"])
    # explicit override beats the plan
    eng2 = Engine(cfg, params, num_slots=2, max_seq=64, plan=plan,
                  weight_dtype="bf16")
    assert eng2.weight_dtype == "bf16"
    assert eng2.plan.matmul.weight_dtype == "bf16"


def test_describe_mentions_weight_dtype():
    assert "w=int8" in make_plan(weight_dtype="int8").describe()
    assert "w=" not in make_plan().describe()


# ---------------------------------------------------------------------------
# benchmark smoke
# ---------------------------------------------------------------------------


def test_weight_quant_bench_smoke(tmp_path, monkeypatch):
    from benchmarks import weight_quant
    monkeypatch.setattr(weight_quant, "OUT_PATH",
                        str(tmp_path / "BENCH_wquant.json"))
    result = weight_quant.run(quick=True)
    assert result["weight_bytes_per_tick"]["bf16"] > 0
    assert result["byte_reduction"]["int8"] >= 1.9
    assert result["footprint_reduction"]["int8"] >= 1.9
    assert (result["max_abs_dlogits"]["int8"]
            <= result["guard_atol"]["int8"])
