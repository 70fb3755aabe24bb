"""ExecutionPlan tests: serialization identity, provenance staleness,
tuned-decision invariants, and the greedy-identity guard (plans choose
which kernel runs, never the tokens)."""
import dataclasses
import json

import jax
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro import configs, hardware
from repro.core import dispatch as dsp
from repro.core import plan as plan_mod
from repro.core.plan import (
    DEFAULT_PLAN, ExecutionPlan, PlanError, StalePlanError, make_plan, tune,
)

settings.register_profile("fast", max_examples=20, deadline=None)
settings.load_profile("fast")

CFG = configs.get("qwen2-0.5b")
TUNED = tune(CFG)


# ---------------------------------------------------------------------------
# Round-trip + staleness
# ---------------------------------------------------------------------------


def test_json_roundtrip_is_identity(tmp_path):
    assert ExecutionPlan.from_json(TUNED.to_json()) == TUNED
    path = TUNED.save(str(tmp_path / "p.json"))
    assert ExecutionPlan.load(path, cfg=CFG) == TUNED
    # the default artifact location is versioned per (arch, hardware)
    assert plan_mod.default_plan_path(CFG).endswith(
        f"{CFG.name}-{hardware.TPU_V5E.name}.json")


def test_load_rejects_wrong_hardware(tmp_path):
    path = TUNED.save(str(tmp_path / "p.json"))
    other = dataclasses.replace(hardware.TPU_V5E, name="tpu-v9",
                                hbm_bw=5e12)
    with pytest.raises(StalePlanError, match="hardware"):
        ExecutionPlan.load(path, cfg=CFG, spec=other)


def test_load_rejects_wrong_config(tmp_path):
    path = TUNED.save(str(tmp_path / "p.json"))
    with pytest.raises(StalePlanError, match="config"):
        ExecutionPlan.load(path, cfg=configs.smoke(CFG))


def test_load_rejects_wrong_version(tmp_path):
    doc = json.loads(TUNED.to_json())
    doc["version"] = plan_mod.PLAN_VERSION + 1
    p = tmp_path / "p.json"
    p.write_text(json.dumps(doc))
    with pytest.raises(StalePlanError, match="version"):
        ExecutionPlan.load(str(p), cfg=CFG)


def test_load_rejects_unprovenanced_unless_lax(tmp_path):
    path = make_plan().save(str(tmp_path / "hand.json"))
    with pytest.raises(StalePlanError, match="provenance"):
        ExecutionPlan.load(path, cfg=CFG)
    assert ExecutionPlan.load(path, strict=False) == make_plan()


def test_bad_knob_values_rejected():
    with pytest.raises(PlanError):
        plan_mod.AttentionDecodePlan(scheme="bogus")
    with pytest.raises(PlanError):
        plan_mod.MatmulPlan(backend="cuda")
    with pytest.raises(PlanError):
        plan_mod.AttentionDecodePlan(block_k=0)
    with pytest.raises(PlanError):
        plan_mod.AttentionPrefillPlan(chunk_threshold=-1)
    with pytest.raises(PlanError):
        ExecutionPlan.from_json("{not json")


def test_malformed_document_stays_inside_plan_error_contract():
    """Every malformed-document path — ops registry, knob values, and
    provenance — must surface as PlanError, never a raw TypeError."""
    doc = json.loads(TUNED.to_json())
    doc["ops"]["attention_decode"]["block_k"] = 0
    with pytest.raises(PlanError):
        ExecutionPlan.from_json(json.dumps(doc))
    doc = json.loads(TUNED.to_json())
    doc["provenance"] = {"backend": "xla", "hw": "typo"}
    with pytest.raises(PlanError, match="provenance"):
        ExecutionPlan.from_json(json.dumps(doc))


def test_with_overrides_maps_shared_knobs():
    p = TUNED.with_overrides(backend="xla", fallback=False, scheme="sync")
    assert p.attention_decode.fallback is False
    assert p.attention_prefill.scheme == "sync"
    assert p.paged.scheme == "sync"
    assert p.fused_ffn.fused is False          # pallas-only fusion dropped
    assert p.matmul.entries == TUNED.matmul.entries   # decisions survive
    # None keeps everything
    assert TUNED.with_overrides() == TUNED


# ---------------------------------------------------------------------------
# Tuned-decision invariants (hypothesis)
# ---------------------------------------------------------------------------

_ORDER = {dsp.Impl.GEMV: 0, dsp.Impl.FLAT_GEMM: 1, dsp.Impl.XLA_DOT: 2}
_KNS = sorted(TUNED.matmul.entries) + [(17, 23)]   # incl. an unseen shape


@given(st.integers(min_value=1, max_value=2047),
       st.integers(min_value=1, max_value=1024),
       st.sampled_from(_KNS))
def test_tuned_pick_piecewise_monotone_in_m(m, dm, kn):
    """Across the widened op space (every tuned [K, N] and the default
    policy) the decision is piecewise-monotone: growing M never routes
    *down* the ImplA -> ImplB -> ImplC ladder."""
    k, n = kn
    a = TUNED.matmul.pick(m, k, n)
    b = TUNED.matmul.pick(m + dm, k, n)
    assert _ORDER[a] <= _ORDER[b]


@given(st.sampled_from([64, 256, 1024, 4096, 32768]),
       st.integers(min_value=1, max_value=8))
def test_tuned_block_k_monotone_in_seq(s, mult):
    """Decode block_k decision is monotone in the representative KV
    length (the beyond-GEMM inflection analogue)."""
    bk1 = dsp.find_block_k(s, CFG.kv_dim)
    bk2 = dsp.find_block_k(s * mult, CFG.kv_dim)
    assert bk1 <= bk2


# ---------------------------------------------------------------------------
# Greedy-identity guard: plans pick kernels, not tokens
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def smoke_model():
    from repro.models.api import get_model
    cfg = configs.smoke(CFG)
    api = get_model(cfg)
    params = api.init_params(jax.random.PRNGKey(0))
    return cfg, params


@pytest.mark.parametrize("cache_kind", ["dense", "paged"])
def test_greedy_identity_across_plans(smoke_model, cache_kind):
    """Token-identical greedy outputs across plans for the same config:
    a plan may change which kernel runs (GEMM routing, block_k, the
    fallback cond, chunk threshold) but never the math. Scheme/backend
    swaps are excluded from the *bitwise* guard — sync vs. unified-max
    is value-close but not bitwise, and near-uniform random-init logits
    amplify fp ties into argmax flips — so scheme variants get their own
    check: ``test_scheme_swap_decode_logits_value_close`` below bounds
    the decode-logit deviation with an atol tied to the activation
    dtype's epsilon (not just "some other test somewhere")."""
    from repro.serving.engine import Engine
    from repro.serving.request import SamplingParams
    cfg, params = smoke_model
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, cfg.vocab_size, size=n).astype(np.int32)
               for n in (11, 26)]
    sp = SamplingParams(max_new_tokens=5, temperature=0.0)
    plans = [
        None,                                   # untuned default
        tune(cfg),                              # tuned decisions
        make_plan(fallback=False, block_k=128, chunk_threshold=1024),
    ]
    outs = []
    for p in plans:
        eng = Engine(cfg, params, num_slots=2, max_seq=64,
                     cache_kind=cache_kind, page_size=16, plan=p)
        outs.append(eng.run([(pr, sp) for pr in prompts]))
    assert outs[0] == outs[1] == outs[2]


@pytest.mark.parametrize("cache_kind", ["dense", "paged"])
def test_scheme_swap_decode_logits_value_close(smoke_model, cache_kind):
    """The real check behind the identity guard's scheme exclusion:
    swapping the softmax scheme (sync <-> unified-max) may change the
    *rounding* of decode logits, never their value. Bounds the deviation
    on a warmed cache with an atol tied to the activation dtype — the
    unified-max rescale is one extra multiply per element, so the two
    schemes must agree to a small multiple of eps at logit scale."""
    import jax.numpy as jnp

    from repro.models.api import get_model
    from repro.models.kvlayout import DenseLayout, PagedLayout
    from repro.models.layers import LayerCtx
    from repro.serving.blockpool import BlockPool, PagedSlotManager

    cfg, params = smoke_model
    api = get_model(cfg)
    num_slots, max_seq, page_size = 4, 64, 16
    lengths = jnp.array([7, 33, 60, 13], jnp.int32)
    toks = jnp.array([3, 1, 4, 1], jnp.int32)

    if cache_kind == "dense":
        layout, bt = DenseLayout(num_slots, max_seq), None
    else:
        pool = BlockPool(num_slots * 4, page_size)
        mgr = PagedSlotManager(num_slots, max_seq, pool)
        for i, ln in enumerate(np.asarray(lengths)):
            assert mgr.try_assign(i, int(ln), 1) is not None
            assert mgr.ensure(i, int(ln) + 1)
        layout, bt = PagedLayout(pool.num_pages, page_size), \
            mgr.block_tables()
    # warm the cache with noise so attention reduces over real values
    cache = jax.tree.map(
        lambda c: c + 0.05 * jax.random.normal(
            jax.random.PRNGKey(9), c.shape, c.dtype),
        api.init_cache(layout))

    outs = {}
    for scheme in ("sync", "unified_max"):
        ctx = LayerCtx(cfg=cfg, plan=make_plan(scheme=scheme))
        logits, _ = api.decode_step(ctx, params, toks, cache, lengths,
                                    block_tables=bt)
        outs[scheme] = np.asarray(logits, np.float32)

    eps = float(jnp.finfo(jnp.dtype(cfg.activation_dtype)).eps)
    scale = float(np.abs(outs["sync"]).max())
    atol = 32 * eps * max(scale, 1.0)
    np.testing.assert_allclose(outs["unified_max"], outs["sync"],
                               rtol=32 * eps, atol=atol)


# ---------------------------------------------------------------------------
# Bench artifact smoke (fast lane)
# ---------------------------------------------------------------------------


def test_dispatch_bench_smoke(tmp_path, monkeypatch):
    """benchmarks.dispatch_table --quick tunes, round-trips, and emits a
    well-formed BENCH_dispatch.json."""
    from benchmarks import dispatch_table
    monkeypatch.setattr(dispatch_table, "OUT_PATH",
                        str(tmp_path / "BENCH_dispatch.json"))
    result = dispatch_table.run(quick=True)
    # quick mode lands in the .quick.json sidecar and never clobbers the
    # committed full-mode artifact
    assert (tmp_path / "BENCH_dispatch.quick.json").exists()
    assert not (tmp_path / "BENCH_dispatch.json").exists()
    assert result["mode"] == "quick"
    assert result["config"]["measure"] == "analytical"
    assert result["rows"], "inflection rows must be emitted"
    for row in result["rows"]:
        assert {"arch", "name", "k", "n", "m1", "m2"} <= set(row)
        assert row["m1"] <= row["m2"]
    assert "llama2-7b" in result["plans"]
