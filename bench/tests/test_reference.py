"""The plain reference against the program's served path at a small
size: chunked prefill into a page pool, then decode through the cache,
teacher-forced on the reference's own greedy tokens."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from bench import harness, weights
from bench.reference import dense

DATA = os.path.join(os.path.dirname(__file__), "data")


def test_reference_matches_paged_prefill_and_decode():
    from repro import hardware
    from repro.models.api import get_model
    from repro.models.kvlayout import PagedLayout
    from repro.models.layers import LayerCtx

    with open(os.path.join(DATA, "smoke-config.json")) as f:
        config = json.load(f)
    model, e = config["model"], config["engine"]
    cfg = harness.program_config(config)
    plan, _ = harness.plan_for(cfg, hardware.TPU_V5E, e["page_size"])
    api, ctx = get_model(cfg), LayerCtx(cfg=cfg, plan=plan)
    seed, prompt_len, new = 2**31 + 5, 45, 12
    params = weights.for_program(model, seed, jax.eval_shape(
        api.init_params, jax.random.PRNGKey(0)))
    prompt = np.random.default_rng(seed).integers(
        1, model["vocab_size"], prompt_len).astype(np.int32)

    # reference first: its greedy tokens are the ones fed to the program
    w = weights.draw(model, seed)
    seq = prompt
    for _ in range(new):
        lg = dense.logits(model, w, seq, len(seq) - 1, e["max_seq"], 256)
        seq = np.append(seq, np.int32(lg[0].argmax()))
    want = dense.logits(model, w, seq[:-1], prompt_len - 1, e["max_seq"],
                        256)

    pages = e["max_seq"] // e["page_size"]
    cache = api.init_cache(PagedLayout(pages, e["page_size"], "bf16"))
    table = jnp.arange(pages, dtype=jnp.int32)[None]
    chunk = jax.jit(lambda p, t, cl, c, le: api.prefill_chunk(
        ctx, p, t, cl, c, le, block_tables=table))
    step = jax.jit(lambda p, t, c, le: api.decode_step(
        ctx, p, t, c, le, block_tables=table))
    c = e["prefill_chunk"]
    for start in range(0, prompt_len, c):
        toks = np.zeros((1, c), np.int32)
        part = prompt[start:start + c]
        toks[0, :len(part)] = part
        logits, cache = chunk(params, jnp.asarray(toks),
                              jnp.array([len(part)], jnp.int32), cache,
                              jnp.array([start], jnp.int32))
    got = [logits[0]]
    for i in range(new - 1):
        logits, cache = step(params, jnp.asarray(seq[prompt_len + i])[None],
                             cache, jnp.array([prompt_len + i], jnp.int32))
        got.append(logits[0])
    got = np.asarray(jnp.stack(got), np.float32)[:, :model["vocab_size"]]

    # Tolerance: the program keeps the residual stream, keys, values and
    # logits in bfloat16. Each of the L layers has two residual updates
    # that round at one bfloat16 epsilon relative, and the logits round
    # once more: (2 L + 1) eps_bf16 max|logit|. A wrong position, page or
    # head moves logits by O(max|logit|), far beyond it.
    eps = float(jnp.finfo(jnp.bfloat16).eps)
    tol = (2 * model["num_hidden_layers"] + 1) * eps * np.abs(want).max()
    gap = np.abs(got - want).max()
    assert gap <= tol, (gap, tol)
    # and the served path agrees on what greedy decoding picks wherever
    # the reference's best logit leads its second by more than 2 tol
    top2 = np.sort(want, axis=1)[:, -2:]
    clear = top2[:, 1] - top2[:, 0] > 2 * tol
    assert clear.any()
    np.testing.assert_array_equal(got.argmax(1)[clear],
                                  want.argmax(1)[clear])


def test_lower_precision_reference_departs_by_rounding_alone():
    """The control's pass (int8 weights and pages, bfloat16 products)
    moves the logits by more than nothing and far less than a wrong
    model would; the correctness check reads both passes' gaps."""
    from bench import correct
    with open(os.path.join(DATA, "smoke-config.json")) as f:
        model = json.load(f)["model"]
    seed = 2**31 + 21
    w = weights.draw(model, seed)
    toks = np.random.default_rng(seed).integers(
        1, model["vocab_size"], 100).astype(np.int32)
    exact = dense.logits(model, w, toks, 40, 128, 64)
    low = dense.logits(model, w, toks, 40, 128, 64, lower=True)
    d = np.abs(low - exact).max()
    assert 0 < d < 0.1 * np.abs(exact).max(), d
    # served tokens that continue the prompt as ``toks`` does
    served, lower = correct.gaps(model, seed,
                                 [(toks[:41], np.append(toks[41:], 0))],
                                 128, 64, control=True)
    at = np.arange(60)
    best = exact.max(1)
    np.testing.assert_allclose(served[:59], (best - exact[at, np.append(
        toks[41:], 0)])[:59], rtol=0, atol=0)
    np.testing.assert_allclose(lower, best - exact[at, low.argmax(1)],
                               rtol=0, atol=0)
    assert lower.max() > 0.0
