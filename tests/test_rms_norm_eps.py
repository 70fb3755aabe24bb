"""The RMSNorm epsilon is the configuration's, on every served path.

Phi-3-mini publishes ``rms_norm_eps`` 1e-5, Qwen2 1e-6. The program's
norms (the XLA blocks, ``ops.decode_ingest``/``ops.ffn_norm`` and the
two ``decode_fuse`` Pallas kernels) read ``ModelConfig.rms_norm_eps``.
Here the plain float32 reference (``bench/reference/dense.py``) judges
the served path at a phi3-shaped small size: chunked prefill into a page
pool, then decode through the cache, teacher-forced on the reference's
own greedy tokens, under each decode-fusion granularity. The same
program with Qwen's epsilon must fall outside the tolerance.

d_model stays at Phi-3's 3072, so the weights drawn at the benchmark's
scales (``bench/weights.py``: the embedding at ``d_model ** -0.5``) put
the first norm's input at the served model's mean square, about 3.3e-4,
where 1e-5 against 1e-6 moves the first layer's normed activations by
about 1.4 %. The prompt is short and the decode long, so that most of
the keys and values attended to were written through the decode seams.
"""
import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import weights  # noqa: E402
from bench.reference import dense  # noqa: E402
from repro import configs, hardware  # noqa: E402
from repro.core import plan as plan_mod  # noqa: E402
from repro.models.api import get_model  # noqa: E402
from repro.models.kvlayout import PagedLayout  # noqa: E402
from repro.models.layers import LayerCtx  # noqa: E402

BENCH = os.path.join(ROOT, "bench")
MODEL = {                      # Phi-3-mini with 2 layers of 2/2 heads
    "hidden_size": 3072, "intermediate_size": 1024,
    "num_hidden_layers": 2, "num_attention_heads": 2,
    "num_key_value_heads": 2, "head_dim": 96, "vocab_size": 512,
    "rms_norm_eps": 1e-5, "rope_theta": 1e4,
    "tie_word_embeddings": False, "qkv_bias": False,
}
PAGE, CHUNK, MAX_SEQ = 16, 16, 128
SEED, PROMPT, NEW = 2**31 + 5, 20, 40
GRANULARITIES = ("split", "fused", "looped")


def _program(eps: float):
    return dataclasses.replace(
        configs.get("phi3-mini-3.8b"), name="phi3-mini-3.8b-small",
        num_layers=2, num_heads=2, num_kv_heads=2, d_ff=1024,
        vocab_size=512, rms_norm_eps=eps)


@pytest.fixture(scope="module")
def reference():
    """(served sequence, reference logits at each served position)."""
    w = weights.draw(MODEL, SEED)
    seq = np.random.default_rng(SEED).integers(
        1, MODEL["vocab_size"], PROMPT).astype(np.int32)
    for _ in range(NEW):
        lg = dense.logits(MODEL, w, seq, len(seq) - 1, MAX_SEQ, 256)
        seq = np.append(seq, np.int32(lg[0].argmax()))
    return seq, dense.logits(MODEL, w, seq[:-1], PROMPT - 1, MAX_SEQ, 256)


def _served(cfg, granularity: str, seq: np.ndarray) -> np.ndarray:
    """The program's logits at the reference's positions: the prompt in
    chunks into a page pool, then one decode step per served token."""
    plan = plan_mod.tune(cfg, hardware.TPU_V5E, backend="pallas",
                         page_size=PAGE)
    plan = dataclasses.replace(plan, decode_fusion=plan_mod.DecodeFusionPlan(
        backend="pallas", granularity=granularity))
    api, ctx = get_model(cfg), LayerCtx(cfg=cfg, plan=plan)
    params = weights.for_program(MODEL, SEED, jax.eval_shape(
        api.init_params, jax.random.PRNGKey(0)))
    pages = MAX_SEQ // PAGE
    cache = api.init_cache(PagedLayout(pages, PAGE, "bf16"))
    table = jnp.arange(pages, dtype=jnp.int32)[None]
    chunk = jax.jit(lambda p, t, cl, c, le: api.prefill_chunk(
        ctx, p, t, cl, c, le, block_tables=table))
    step = jax.jit(lambda p, t, c, le: api.decode_step(
        ctx, p, t, c, le, block_tables=table))
    for start in range(0, PROMPT, CHUNK):
        toks = np.zeros((1, CHUNK), np.int32)
        part = seq[start:min(start + CHUNK, PROMPT)]
        toks[0, :len(part)] = part
        logits, cache = chunk(params, jnp.asarray(toks),
                              jnp.array([len(part)], jnp.int32), cache,
                              jnp.array([start], jnp.int32))
    got = [logits[0]]
    for i in range(NEW - 1):
        logits, cache = step(params, jnp.asarray(seq[PROMPT + i])[None],
                             cache, jnp.array([PROMPT + i], jnp.int32))
        got.append(logits[0])
    return np.asarray(jnp.stack(got), np.float32)[:, :MODEL["vocab_size"]]


def _tolerance(want: np.ndarray) -> float:
    """Bound on the rms of served minus reference logits.

    The program keeps the residual stream, keys, values and logits in
    bfloat16: each of the L layers' two residual updates and the logits
    round once, 2 L + 1 roundings, each at most half a bfloat16 epsilon
    relative to the value rounded. The rms over positions and vocabulary
    takes that relative bound to (2 L + 1) eps_bf16 / 2 times the rms
    logit. The 1e-5 program reads about half of it (rms 0.0093 to
    0.0098 against 0.0198 at this seed); Qwen's epsilon in its place
    reads 0.036, and so does a decode-ingest kernel that drops the
    epsilon it is given (0.033)."""
    eps = float(jnp.finfo(jnp.bfloat16).eps)
    return ((2 * MODEL["num_hidden_layers"] + 1) * eps / 2
            * float(np.sqrt(np.mean(want ** 2))))


def _rms(a: np.ndarray) -> float:
    return float(np.sqrt(np.mean(a ** 2)))


@pytest.mark.parametrize("granularity", GRANULARITIES)
def test_served_logits_match_reference(reference, granularity):
    seq, want = reference
    got = _served(_program(MODEL["rms_norm_eps"]), granularity, seq)
    tol = _tolerance(want)
    assert _rms(got - want) <= tol, (_rms(got - want), tol)
    # greedy agrees wherever the reference's best logit leads its second
    # by more than the largest error the rms bound allows one logit
    # (2 L + 1 half-epsilons of max|logit|, doubled for both logits)
    top2 = np.sort(want, axis=1)[:, -2:]
    edge = (2 * MODEL["num_hidden_layers"] + 1) * float(
        jnp.finfo(jnp.bfloat16).eps) * np.abs(want).max()
    clear = top2[:, 1] - top2[:, 0] > edge
    assert clear.any()
    np.testing.assert_array_equal(got.argmax(1)[clear],
                                  want.argmax(1)[clear])


@pytest.mark.parametrize("granularity", GRANULARITIES)
def test_qwen_epsilon_falls_outside_tolerance(reference, granularity):
    """The negative control: the same program with 1e-6 in place of the
    published 1e-5 is refused by the tolerance the served path meets."""
    seq, want = reference
    got = _served(_program(1e-6), granularity, seq)
    assert _rms(got - want) > _tolerance(want), _rms(got - want)


@pytest.mark.parametrize("backend", ("xla", "pallas"))
def test_fused_seams_take_the_epsilon(backend):
    """``ops.decode_ingest`` and ``ops.ffn_norm`` (the two ``decode_fuse``
    kernels on Pallas, their oracles on XLA) norm with the epsilon they
    are given, on rows whose mean square (about 1e-5) makes 1e-5 and
    1e-6 differ by far more than rounding."""
    from repro.kernels import ops, ref
    rng = np.random.default_rng(7)
    d, h, dh, f = 256, 2, 64, 256
    x = jnp.asarray(rng.standard_normal((2, 1, d)) * 3e-3, jnp.bfloat16)
    scale = jnp.ones((d,), jnp.bfloat16)
    wq, wk, wv = (jnp.asarray(rng.standard_normal((d, h * dh)) / 16,
                              jnp.bfloat16) for _ in range(3))
    wg, wu = (jnp.asarray(rng.standard_normal((d, f)) / 16, jnp.bfloat16)
              for _ in range(2))
    pos = jnp.array([3, 7], jnp.int32)
    plan = plan_mod.make_plan(backend=backend, decode_fusion="fused")
    kw = dict(num_heads=h, num_kv_heads=h, head_dim=dh)
    bf = float(jnp.finfo(jnp.bfloat16).eps)
    for eps in (1e-5, 1e-6):
        other = 1e-6 if eps == 1e-5 else 1e-5
        cases = (
            (ops.decode_ingest(x, scale, wq, wk, wv, pos, eps=eps,
                               plan=plan, **kw)[0],
             ref.decode_ingest_ref(x, scale, wq, wk, wv, pos, eps=eps,
                                   **kw)[0],
             ref.decode_ingest_ref(x, scale, wq, wk, wv, pos, eps=other,
                                   **kw)[0]),
            (ops.ffn_norm(x, scale, wg, wu, eps=eps, plan=plan),
             ref.ffn_norm_ref(x, scale, wg, wu, eps=eps),
             ref.ffn_norm_ref(x, scale, wg, wu, eps=other)),
        )
        for got, want, wrong in cases:
            got, want, wrong = (np.asarray(a, np.float32)
                                for a in (got, want, wrong))
            # the kernels reassociate the norm and GEMM sums: a few
            # bfloat16 epsilons of the output's scale; the other epsilon
            # moves it by about 20 % of that scale
            tol = 8 * bf * np.abs(want).max()
            assert np.abs(got - want).max() <= tol
            assert np.abs(got - wrong).max() > 4 * tol


# -- guards on the benchmark's configurations ----------------------------------


def _bench_configs():
    d = os.path.join(BENCH, "configs")
    return sorted(f for f in os.listdir(d) if f.endswith(".json"))


def _json(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


@pytest.mark.parametrize("name", _bench_configs())
def test_registry_epsilon_is_published(name):
    """Each benchmark configuration's program config takes the epsilon
    its published ``rms_norm_eps`` states."""
    c = _json("configs", name)
    assert (configs.get(c["repo_config"]).rms_norm_eps
            == c["model"]["rms_norm_eps"])


def test_long_decode_contexts_stay_under_phi3_window():
    """The paged dense path applies no sliding window; the long-decode
    mix's longest context (longest prompt plus longest output) stays
    under Phi-3-mini's published 2047, so the window never binds."""
    mix = _json("traffic", "long-decode.json")
    window = _json("configs", "phi3-mini-3.8b.json")["model"][
        "sliding_window"]
    longest = mix["prompt"]["max"] + mix["output"]["max"]
    assert longest == 1920 < window == 2047
    assert configs.get("phi3-mini-3.8b").sliding_window == 0
