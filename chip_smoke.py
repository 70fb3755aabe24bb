"""Serve qwen2-0.5b at its published widths on one TPU through the Pallas
kernels, and check what comes out.

    python chip_smoke.py [--seed N]

One process, no children. It

  1. refuses to run unless JAX's first device is a TPU whose kind the
     hardware table knows (no CPU fallback, no interpret mode);
  2. builds qwen2-0.5b (24 layers, d_model 896, 14/2 heads, head_dim 64,
     d_ff 4864, vocab 151936) with random weights from ``--seed`` and an
     ``Engine`` under the committed Pallas plan
     (``plans/qwen2-0.5b-tpu-v5e.json``): paged cache, page size 64, 8
     slots, max_seq 1024, chunked prefill, prefix sharing, and the decode
     seams under one layer scan;
  3. serves 8 requests — a 128-token shared prefix plus 384 unshared
     tokens each — for 32 greedy tokens apiece, once to compile and once
     timed, and checks that every request finished;
  4. compiles the engine's decode step and checks that its HLO holds the
     Mosaic kernels (``tpu_custom_call``);
  5. compares the last-position prefill logits of one prompt between the
     Pallas plan and the same plan on the XLA backend (same weights, both
     on the chip) within a tolerance derived from bf16's epsilon.

Earlier lines report compile and wall seconds, tokens served, the logit
gap against its tolerance, the device kind and peak HBM bytes. The last
line is the JSON result, printed only when every check passed.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "qwen2-0.5b"
SLOTS, PAGE, MAX_SEQ = 8, 64, 1024
SHARED, UNSHARED, NEW_TOKENS = 128, 384, 32


def _require(ok: bool, what: str) -> None:
    """A failed check ends the run with a non-zero exit and no result."""
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def _workload(rng, vocab):
    """8 prompts: one shared 128-token header + 384 tokens of their own."""
    import numpy as np
    header = rng.integers(1, vocab, size=SHARED).astype(np.int32)
    return [np.concatenate([header, rng.integers(
        1, vocab, size=UNSHARED).astype(np.int32)]) for _ in range(SLOTS)]


def _serve(eng, prompts, sp):
    """Submit every prompt, step the engine to completion. Returns
    (rids, prefill_s, decode_s): prefill runs from submission to the last
    first token (each first token is sampled on the host from prefill
    logits, so the device has finished by then), decode from there to the
    last token, with the cache synced by ``block_until_ready``."""
    import jax
    t0 = time.perf_counter()
    rids = [eng.submit(p, sp) for p in prompts]
    while any(not eng.requests[r].finished for r in rids):
        eng.step()
    jax.block_until_ready(eng.cache)
    t_end = time.perf_counter()
    t_first = max(eng.requests[r].first_token_time for r in rids)
    return rids, t_first - t0, t_end - t_first


def _prefill_logits(api, ctx, params, prompt):
    """Last-position logits of ``prompt`` through the served chunked,
    paged prefill path (one sequence, pages 0..n-1)."""
    import jax
    import jax.numpy as jnp
    from repro.models.kvlayout import PagedLayout, pages_for

    n_pages = pages_for(len(prompt), PAGE)
    cache = api.init_cache(PagedLayout(n_pages, PAGE, "bf16"))
    table = jnp.arange(n_pages, dtype=jnp.int32)[None]
    step = jax.jit(lambda p, t, cl, c, le: api.prefill_chunk(
        ctx, p, t, cl, c, le, block_tables=table))
    logits = None
    for start in range(0, len(prompt), PAGE):
        chunk = prompt[start:start + PAGE]
        logits, cache = step(params, jnp.asarray(chunk)[None],
                             jnp.array([len(chunk)], jnp.int32), cache,
                             jnp.array([start], jnp.int32))
    return jax.block_until_ready(logits)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found "
                         f"{device.platform!r} ({device.device_kind})")

    from repro import configs, hardware
    from repro.core import plan as plan_mod
    from repro.kernels import ops
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models.api import get_model
    from repro.models.layers import LayerCtx
    from repro.serving.engine import Engine
    from repro.serving.request import FinishReason, SamplingParams

    cache_dir = enable_compile_cache()
    spec = hardware.for_device(device)      # unknown kinds raise here
    interpret, _ = ops.kernel_target()
    _require(not interpret, "kernels would run in interpret mode")
    print(f"device: {device.platform} {device.device_kind!r} "
          f"x{len(jax.devices())} -> {spec.name}; compile cache {cache_dir}")

    cfg = configs.get(ARCH)
    root = os.path.dirname(os.path.abspath(__file__))
    plan = plan_mod.ExecutionPlan.load(
        plan_mod.default_plan_path(cfg, spec, os.path.join(root, "plans")),
        cfg=cfg, spec=spec)
    backends = {plan.matmul.backend, plan.attention_decode.backend,
                plan.attention_prefill.backend, plan.fused_ffn.backend,
                plan.paged.backend}
    _require(backends == {"pallas"}, f"plan is not all-Pallas: {backends}")
    print(f"plan: {plan.describe()}")

    api = get_model(cfg)
    t = time.perf_counter()
    params = jax.block_until_ready(
        jax.jit(api.init_params)(jax.random.PRNGKey(args.seed)))
    print(f"params: {sum(x.size for x in jax.tree.leaves(params))} "
          f"random weights from seed {args.seed} in "
          f"{time.perf_counter() - t:.3f} s")

    eng = Engine(cfg, params, num_slots=SLOTS, max_seq=MAX_SEQ,
                 cache_kind="paged", page_size=PAGE, plan=plan,
                 prefix_sharing=True, seed=args.seed)
    sp = SamplingParams(max_new_tokens=NEW_TOKENS)      # greedy
    rng = np.random.default_rng(args.seed)

    # pass 1 compiles every shape the workload reaches; pass 2 is timed
    warm, timed = _workload(rng, cfg.vocab_size), _workload(rng,
                                                            cfg.vocab_size)
    t = time.perf_counter()
    _serve(eng, warm, sp)
    warm_s = time.perf_counter() - t
    rids, prefill_s, decode_s = _serve(eng, timed, sp)
    tokens = sum(len(eng.requests[r].tokens) for r in rids)
    for r in rids:
        st = eng.requests[r]
        _require(st.finished and st.finish_reason is FinishReason.LENGTH
                 and len(st.tokens) == NEW_TOKENS,
                 f"request {r}: {st.finish_reason}, {len(st.tokens)} tokens")
    print(f"first pass {warm_s:.3f} s, compiles included: compile "
          f"~{warm_s - prefill_s - decode_s:.3f} s (first pass minus timed "
          f"pass)")
    print(f"timed pass: served {len(rids)} requests, {tokens} tokens: "
          f"prefill {prefill_s:.3f} s, decode {decode_s:.3f} s "
          f"({eng.stats.shared_prefix_pages} shared-prefix page mappings, "
          f"{eng.stats.grouped_requests} grouped decode rows)")

    # the engine's decode step, compiled ahead of time at its own shapes
    lengths = jnp.asarray(eng.slots.lengths())
    decode = jax.jit(lambda p, tk, c, bt, le: api.decode_step(
        eng.ctx, p, tk, c, le, block_tables=bt))
    t = time.perf_counter()
    compiled = decode.lower(params, jnp.zeros((SLOTS,), jnp.int32),
                            eng.cache, eng.slots.block_tables(),
                            lengths).compile()
    compile_s = time.perf_counter() - t
    n_kernels = compiled.as_text().count("tpu_custom_call")
    _require(n_kernels > 0, "decode step holds no Mosaic kernel")
    print(f"decode step lower+compile: {compile_s:.3f} s (persistent "
          f"compile cache on), {n_kernels} tpu_custom_call sites in its HLO")

    # Pallas vs XLA prefill logits, same weights, both on the chip.
    # Tolerance: each of the L layers has two seams (attention, FFN) where
    # the kernel and the XLA reference round or associate differently;
    # each moves the residual stream by at most one bf16 epsilon relative.
    # Summed linearly over the depth: 2 * L * eps * max(|logits|, 1).
    xla_plan = plan.with_overrides(backend="xla")
    prompt = timed[0]
    got = _prefill_logits(api, LayerCtx(cfg=cfg, plan=plan), params, prompt)
    want = _prefill_logits(api, LayerCtx(cfg=cfg, plan=xla_plan), params,
                           prompt)
    got = np.asarray(got[0, :cfg.vocab_size], np.float32)
    want = np.asarray(want[0, :cfg.vocab_size], np.float32)
    _require(bool(np.isfinite(got).all() and np.isfinite(want).all()),
             "non-finite prefill logits")
    eps = float(jnp.finfo(jnp.bfloat16).eps)
    tol = 2 * cfg.num_layers * eps * max(float(np.abs(want).max()), 1.0)
    gap = float(np.abs(got - want).max())
    print(f"prefill logits, Pallas vs XLA: max |dlogit| {gap:.6f} "
          f"<= tol {tol:.6f} (2*L*eps_bf16*max|logit|, max|logit| "
          f"{float(np.abs(want).max()):.4f}); argmax "
          f"{int(got.argmax())} vs {int(want.argmax())}")
    _require(gap <= tol, f"Pallas/XLA logits differ: {gap} > {tol}")

    stats = device.memory_stats() or {}
    print(f"peak HBM bytes: {stats.get('peak_bytes_in_use', 'not reported')}")
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
