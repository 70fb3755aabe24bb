"""The decode layer's seams: one composition, under one layer scan.

Contract under test:
  * plan documents load whether or not they still carry the retired
    fusion op; the seams route on the plan's matmul backend,
    so a backend override to XLA sends them to the ``ref.py`` oracles;
  * the ``ref.py`` seam oracles are expression-for-expression copies of
    the op chain (``rmsnorm``/``rope`` bitwise), so on the XLA backend
    each seam, and a whole decode step, is bit-identical to the op-chain
    functions called directly (``norm``, ``attention_qkv``, ``matmul``,
    ``mlp_block``);
  * the explicitly unrolled depth (the dry-run's cost probes) lets XLA
    place bf16 rounding at other fusion boundaries than the scan body,
    and is held to a dtype-eps bound against the scanned step;
  * the Pallas seam kernels match their oracles in interpret mode to
    rounding-unit tolerance (K-stream f32 accumulation), including the
    MHA head_dim-96 shape at phi3's epsilon;
  * ``stack.unstack``/``restack`` round-trip stacked params bitwise (the
    unrolled path must see exactly the scanned values);
  * the engine's greedy tokens through dense, paged, prefix-shared,
    quantized-KV and preempting decode match teacher forcing, at qwen's
    and phi3's attention shapes, and the positions operand is cached
    under the lengths-device dirty discipline.
"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro import configs
from repro.core.plan import ExecutionPlan, make_plan
from repro.kernels import decode_fuse, quant, ref
from repro.kernels.decode_fuse import (
    decode_ingest_fused, ffn_norm_fused, oproj_residual_fused,
)
from repro.models import layers as L
from repro.models import stack
from repro.models import transformer as tfm
from repro.models.api import get_model
from repro.models.kvlayout import DenseLayout
from repro.models.layers import LayerCtx

CFG = configs.get("qwen2-0.5b")
# the two cells' attention shapes at smoke size: qwen's GQA (4/2 heads of
# 64, qkv biases) and phi3's MHA with head_dim 96 (no biases, eps 1e-5)
SMOKE_MODELS = {
    "qwen": configs.smoke(CFG),
    "phi3": dataclasses.replace(configs.smoke(configs.get("phi3-mini-3.8b")),
                                num_heads=2, num_kv_heads=2, head_dim=96),
}


@pytest.fixture(scope="module")
def smoke_models():
    out = {}
    for name, cfg in SMOKE_MODELS.items():
        api = get_model(cfg)
        out[name] = (cfg, api, api.init_params(jax.random.PRNGKey(0)))
    return out


@pytest.fixture(scope="module")
def smoke_model(smoke_models):
    return smoke_models["qwen"]


# ---------------------------------------------------------------------------
# Plan documents and seam routing
# ---------------------------------------------------------------------------


def test_plan_documents_load_with_or_without_stale_fusion_op():
    """A plan document written before the decode composition was fixed
    still carries the fusion op; the loader reads ops by name, so it
    loads like one without it."""
    stale_op = "decode_fusion"
    doc = json.loads(make_plan("pallas").to_json())
    assert stale_op not in doc["ops"]
    plain = ExecutionPlan.from_json(json.dumps(doc))
    doc["ops"][stale_op] = {"backend": "pallas", "granularity": "looped"}
    stale = ExecutionPlan.from_json(json.dumps(doc))
    assert stale == plain == make_plan("pallas")


def test_backend_override_routes_seams_to_oracles(monkeypatch):
    """The seams are GEMM seams: they follow the matmul backend, so
    ``with_overrides(backend="xla")`` of a Pallas plan runs the ``ref.py``
    oracles (the Pallas kernels are never called) and returns their
    values bit for bit."""
    from repro.kernels import ops

    def refuse(*a, **k):
        raise AssertionError("Pallas seam kernel called on the XLA backend")

    for name in ("decode_ingest_fused", "oproj_residual_fused",
                 "ffn_norm_fused"):
        monkeypatch.setattr(decode_fuse, name, refuse)
    plan = make_plan("pallas").with_overrides(backend="xla")
    assert plan.matmul.backend == "xla"
    rng = np.random.default_rng(9)
    d, h, dh, f = 128, 4, 32, 256
    bf = lambda *s: jnp.asarray(rng.standard_normal(s) / 8, jnp.bfloat16)
    x, scale, o = bf(2, 1, d), bf(d), bf(2, 1, h * dh)
    wq, wk, wv, wo, wg, wu = (bf(d, h * dh), bf(d, h * dh), bf(d, h * dh),
                              bf(h * dh, d), bf(d, f), bf(d, f))
    pos = jnp.array([3, 11], jnp.int32)
    kw = dict(num_heads=h, num_kv_heads=h, head_dim=dh, eps=1e-6)
    pairs = [
        (ops.decode_ingest(x, scale, wq, wk, wv, pos, plan=plan, **kw),
         ref.decode_ingest_ref(x, scale, wq, wk, wv, pos, **kw)),
        (ops.oproj_residual(o, wo, x, plan=plan),
         ref.oproj_residual_ref(o, wo, x)),
        (ops.ffn_norm(x, scale, wg, wu, eps=1e-6, plan=plan),
         ref.ffn_norm_ref(x, scale, wg, wu, eps=1e-6,
                          fused=plan.fused_ffn.fused)),
    ]
    for got, want in pairs:
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            assert np.array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# Seams == op chain on XLA (bitwise)
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


@pytest.mark.parametrize("qkv_bias", [False, True])
def test_stage_dispatch_bitwise_on_xla(smoke_model, qkv_bias):
    """layers.decode_ingest / decode_epilogue on the XLA backend compose
    the op chain's expressions: per-stage outputs are bitwise equal to
    ``norm`` → ``attention_qkv`` and ``resid + matmul`` called directly,
    with the q/k/v biases qwen carries and without them, as phi3."""
    cfg, _, _ = smoke_model
    cfg = dataclasses.replace(cfg, qkv_bias=qkv_bias)
    p = L.attention_params(cfg, jax.random.PRNGKey(3))
    if qkv_bias:      # drawn, not the zeros init_params starts from
        for i, name in enumerate(("bq", "bk", "bv")):
            p[name] = jax.random.normal(jax.random.PRNGKey(10 + i),
                                        p[name].shape, p[name].dtype)
    np_ = L.norm_params(cfg, cfg.d_model)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 1, cfg.d_model),
                          jnp.dtype(cfg.activation_dtype))
    pos = jnp.array([4, 9], jnp.int32)
    ctx = LayerCtx(cfg=cfg, plan=make_plan())
    chain = L.attention_qkv(ctx, p, L.norm(cfg, np_, x), pos[:, None])
    for a, b in zip(chain, L.decode_ingest(ctx, np_, p, x, pos)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    o = jax.random.normal(jax.random.PRNGKey(2), (2, 1, cfg.q_dim),
                          jnp.dtype(cfg.activation_dtype))
    res = jax.random.normal(jax.random.PRNGKey(4), (2, 1, cfg.d_model),
                            jnp.dtype(cfg.activation_dtype))
    assert np.array_equal(
        np.asarray(res + ctx.matmul(o, p["wo"])),
        np.asarray(L.decode_epilogue(ctx, p, o, res)))


def _op_chain_block(ctx, p, x, position, cache, lengths, block_tables=None,
                    decode_groups=None, layer=None):
    """One dense-layout decode layer composed from the op-chain functions
    alone — the oracle the seams are held to."""
    cfg = ctx.cfg
    q, k, v = L.attention_qkv(ctx, p["attn"], L.norm(cfg, p["attn_norm"], x),
                              position[:, None])
    o, ck, cv = L.decode_attend(ctx, q, k, v, cache["k"], cache["v"],
                                lengths)
    x = x + ctx.matmul(o, p["attn"]["wo"])
    x = x + L.mlp_block(ctx, p["mlp"], L.norm(cfg, p["mlp_norm"], x))
    return ctx.shard(x, "act_resid"), {"k": ck, "v": cv}


def test_seam_and_op_chain_decode_logits_bitwise(smoke_model):
    """Same depth scan + bitwise stages -> bitwise logits and cache."""
    cfg, api, params = smoke_model
    cache = api.init_cache(DenseLayout(2, 64))
    toks = jnp.array([3, 5], jnp.int32)
    lens = jnp.array([4, 9], jnp.int32)
    ctx = LayerCtx(cfg=cfg, plan=make_plan())
    seams = api.decode_step(ctx, params, toks, cache, lens)
    chain = tfm.decode_step(ctx, params, toks, cache, lens,
                            decode_block_fn=_op_chain_block)
    for a, b in zip(jax.tree.leaves(seams), jax.tree.leaves(chain)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_unrolled_decode_logits_value_close(smoke_model):
    """The dry-run's probes unroll the L layer bodies, so XLA may fuse
    (and round) across different boundaries than the scan body compiles
    to — same expressions, different bf16 rounding placement. Bound it
    with the scheme-swap dtype-eps pattern."""
    cfg, api, params = smoke_model
    cache = api.init_cache(DenseLayout(2, 64))
    toks = jnp.array([3, 5], jnp.int32)
    lens = jnp.array([4, 9], jnp.int32)
    ctx = LayerCtx(cfg=cfg, plan=make_plan())
    scanned, _ = api.decode_step(ctx, params, toks, cache, lens)
    unrolled, _ = api.decode_step(ctx, params, toks, cache, lens,
                                  unroll=True)
    scanned = np.asarray(scanned, np.float32)
    eps = float(jnp.finfo(jnp.dtype(cfg.activation_dtype)).eps)
    atol = 32 * eps * max(float(np.abs(scanned).max()), 1.0)
    np.testing.assert_allclose(np.asarray(unrolled, np.float32), scanned,
                               rtol=32 * eps, atol=atol)


# ---------------------------------------------------------------------------
# Pallas kernels vs oracles (interpret mode)
# ---------------------------------------------------------------------------


INGEST_CASES = [
    # (num_heads, num_kv_heads, head_dim, d_model, bias, rope, eps)
    (4, 2, 32, 128, False, True, 1e-6),      # GQA
    (8, 8, 64, 256, True, True, 1e-6),       # MHA + qkv bias
    (4, 1, 64, 192, True, False, 1e-6),      # MQA, no rope, K not 128-mult
    (12, 4, 64, 384, False, True, 1e-6),     # wider GQA, K streams in blocks
    (4, 4, 96, 3072, False, True, 1e-5),     # phi3: MHA of 96, its d_model
]


@pytest.mark.parametrize("hq,hk,dh,d,bias,use_rope,eps", INGEST_CASES)
def test_ingest_kernel_matches_oracle(hq, hk, dh, d, bias, use_rope, eps):
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
        head_dim=dh, eps=eps, use_rope=use_rope, bq=bq, bk=bk, bv=bv)
    qf, kf, vf = decode_ingest_fused(
        x.reshape(m, d), scale, wq, wk, wv, pos, num_heads=hq,
        num_kv_heads=hk, head_dim=dh, eps=eps, use_rope=use_rope,
        bq=bq, bk_bias=bk, bv=bv, interpret=True)
    eps = float(jnp.finfo(jnp.bfloat16).eps)
    for a, b in ((qo.reshape(m, -1), qf), (ko.reshape(m, -1), kf),
                 (vo.reshape(m, -1), vf)):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        atol = 32 * eps * max(float(np.abs(a).max()), 1.0)
        np.testing.assert_allclose(b, a, rtol=32 * eps, atol=atol)


@pytest.mark.parametrize("m,q_dim,d", [(1, 128, 128), (3, 256, 192),
                                       (8, 384, 512),
                                       (8, 3072, 3072)])  # phi3: 32 x 96
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


@pytest.mark.parametrize("m,d,f,act,eps", [(1, 128, 256, "swiglu", 1e-6),
                                           (4, 192, 384, "swiglu", 1e-6),
                                           (8, 256, 512, "geglu", 1e-6),
                                           (3, 384, 640, "swiglu", 1e-6),
                                           (8, 3072, 1024, "swiglu", 1e-5)])
def test_ffn_norm_kernel_matches_oracle(m, d, f, act, eps):
    rng = np.random.default_rng(m * 100 + f)
    x = jnp.asarray(rng.standard_normal((m, 1, d)), jnp.bfloat16)
    scale = jnp.asarray(rng.standard_normal(d), jnp.bfloat16)
    wg = jnp.asarray(rng.standard_normal((d, f)) / 8, jnp.bfloat16)
    wu = jnp.asarray(rng.standard_normal((d, f)) / 8, jnp.bfloat16)
    want = ref.ffn_norm_ref(x, scale, wg, wu, activation=act, eps=eps,
                            fused=True)
    got = ffn_norm_fused(x.reshape(m, d), scale, wg, wu, activation=act,
                         eps=eps, interpret=True).reshape(want.shape)
    a = np.asarray(want, np.float32)
    b = np.asarray(got, np.float32)
    eps = float(jnp.finfo(jnp.bfloat16).eps)
    atol = 32 * eps * max(float(np.abs(a).max()), 1.0)
    np.testing.assert_allclose(b, a, rtol=32 * eps, atol=atol)


@pytest.mark.parametrize("fused_ffn", [False, True])
def test_decode_mlp_stage_bitwise_on_xla(smoke_model, fused_ffn):
    """layers.decode_mlp's seam composes whichever op chain the plan's
    fused_ffn knob selects — bitwise equal to ``norm`` → ``mlp_block``
    either way on XLA."""
    cfg, _, _ = smoke_model
    p = L.mlp_params(cfg, jax.random.PRNGKey(5))
    np_ = L.norm_params(cfg, cfg.d_model)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 1, cfg.d_model),
                          jnp.dtype(cfg.activation_dtype))
    ctx = LayerCtx(cfg=cfg, plan=make_plan(fused_ffn=fused_ffn))
    assert np.array_equal(
        np.asarray(x + L.mlp_block(ctx, p, L.norm(cfg, np_, x))),
        np.asarray(L.decode_mlp(ctx, np_, p, x)))


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
    ref_out = ops.decode_ingest(x, scale, wq, wk, wv, pos,
                                plan=make_plan(backend="xla"), **kw)
    pal_out = ops.decode_ingest(x, scale, wq, wk, wv, pos,
                                plan=make_plan(backend="pallas"), **kw)
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
# Engine: greedy tokens against teacher forcing
# ---------------------------------------------------------------------------

TEACHER_LEN = 64     # one padded teacher shape -> one compile per model


def _run_engine(cfg, params, prompts, max_new=5, **kw):
    from repro.serving.engine import Engine
    from repro.serving.request import SamplingParams
    eng = Engine(cfg, params, num_slots=2, max_seq=64, **kw)
    sp = SamplingParams(max_new_tokens=max_new, temperature=0.0)
    out = eng.run([(p, sp) for p in prompts])
    return eng, [out[k] for k in sorted(out)]


def _assert_teacher_forced(cfg, api, params, prompts, outs, tol=0.0):
    """Each served token is the argmax of single-shot ``api.prefill`` of
    the prompt plus the tokens served before it (the oracle of
    ``test_serving.test_engine_matches_teacher_forcing``), wherever the
    teacher's best logit leads its second by more than twice ``tol``
    times the largest logit (with ``tol=0``, everywhere but exact ties:
    bf16 logits of a smoke model tie often, and a tie has no argmax).
    At least half of the served tokens must be held."""
    ctx = LayerCtx(cfg=cfg)
    prefill = jax.jit(lambda t, n: api.prefill(
        ctx, params, t, n, api.init_cache(DenseLayout(1, TEACHER_LEN)))[0])
    held = 0
    for prompt, toks in zip(prompts, outs):
        for k, tok in enumerate(toks):
            seq = np.concatenate([prompt, np.asarray(toks[:k], np.int32)])
            padded = np.zeros((TEACHER_LEN,), np.int32)
            padded[:len(seq)] = seq
            logits = np.asarray(prefill(
                jnp.asarray(padded)[None],
                jnp.array([len(seq)], jnp.int32))[0, :cfg.vocab_size],
                np.float32)
            top2 = np.sort(logits)[-2:]
            if top2[1] - top2[0] > 2 * tol * np.abs(logits).max():
                assert int(logits.argmax()) == tok, (k, tok)
                held += 1
    assert held >= sum(map(len, outs)) // 2, "too few decisive positions"


@pytest.fixture(scope="module")
def engine_prompts():
    """Two prompts behind one shared 16-token page (a page at the tests'
    page size), with private tails of 5 and 10 tokens."""
    rng = np.random.default_rng(7)
    header = rng.integers(1, 512, size=16).astype(np.int32)
    return [np.concatenate([header, rng.integers(1, 512, size=n).astype(
        np.int32)]) for n in (5, 10)]


@pytest.mark.parametrize("model", sorted(SMOKE_MODELS))
def test_engine_matches_teacher_forcing_dense(smoke_models, engine_prompts,
                                              model):
    cfg, api, params = smoke_models[model]
    _, outs = _run_engine(cfg, params, engine_prompts)
    _assert_teacher_forced(cfg, api, params, engine_prompts, outs)


@pytest.mark.parametrize("sharing", [False, True])
@pytest.mark.parametrize("model", sorted(SMOKE_MODELS))
def test_engine_matches_teacher_forcing_paged(smoke_models, engine_prompts,
                                              model, sharing):
    cfg, api, params = smoke_models[model]
    eng, outs = _run_engine(cfg, params, engine_prompts, cache_kind="paged",
                            page_size=16, prefill_chunk=16,
                            prefix_sharing=sharing)
    if sharing:
        assert eng.stats.shared_prefix_pages > 0, "nothing was shared"
    _assert_teacher_forced(cfg, api, params, engine_prompts, outs)


@pytest.mark.parametrize("model", sorted(SMOKE_MODELS))
def test_engine_matches_teacher_forcing_quantized_kv(smoke_models,
                                                     engine_prompts, model):
    """int8 pages store each key and value to within half a code step,
    relative; a token is held to the teacher where the teacher's best
    logit leads by more than that relative error on both logits."""
    cfg, api, params = smoke_models[model]
    _, outs = _run_engine(cfg, params, engine_prompts, cache_kind="paged",
                          page_size=16, prefill_chunk=16, kv_dtype="int8")
    _assert_teacher_forced(cfg, api, params, engine_prompts, outs,
                           tol=0.5 / quant.INT8.qmax)


@pytest.mark.parametrize("model", sorted(SMOKE_MODELS))
def test_engine_matches_teacher_forcing_under_preemption(smoke_models,
                                                         model):
    """Overcommitted pool forces mid-decode preemption (partial pages,
    re-prefill); the served tokens still follow teacher forcing."""
    cfg, api, params = smoke_models[model]
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, cfg.vocab_size, size=n).astype(np.int32)
               for n in (9, 10)]
    eng, outs = _run_engine(cfg, params, prompts, max_new=26,
                            cache_kind="paged", page_size=16,
                            prefill_chunk=16, num_pages=4)
    assert eng.stats.preemptions > 0, "pool was never under pressure"
    _assert_teacher_forced(cfg, api, params, prompts, outs)


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
    ctx = LayerCtx(cfg=cfg, plan=make_plan())
    a, _ = api.decode_step(ctx, params, toks, cache, lens)
    b, _ = api.decode_step(ctx, params, toks, cache, lens,
                           positions=jnp.asarray(lens))
    assert np.array_equal(np.asarray(a), np.asarray(b))
