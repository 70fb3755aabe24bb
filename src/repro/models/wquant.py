"""Quantize-at-load pass for GEMM weights — the second precision knob.

The weight-side twin of the kv_dtype subsystem
(:mod:`repro.serving.kvquant`): after PR 8 halved the KV stream, the
decode tick's dominant HBM traffic is the layer weight slab, read once
per tick at M = batch ≤ ~8 (the paper's flat-GEMM regime, where every
GEMM is memory-bound on weight bytes). This module converts each GEMM
weight leaf of a params pytree into int8/fp8 *codes* plus one f32 step
per **output channel**, reusing ``kernels/quant.py``'s
QuantSpec/encode/decode algebra so ``codes * step`` remains THE dequant
expression everywhere:

  * per-output-channel steps: a weight ``(…, K, N)`` is quantized along
    K with one step per N column — ``step[n] = max_k |w[k, n]| / qmax``.
    The step factors out of the GEMM's K sum, so the kernels multiply it
    onto the f32 accumulator once in the epilogue (exactly
    ``decode(codes, step)`` distributed over the reduction) and the bf16
    weight slab never materializes in HBM.
  * a quantized leaf is the dict ``{"codes": (…, K, N) code_dtype,
    "scale": (…, N) f32}`` — a plain pytree node, so the stacked-L
    layer params stack/slice/scan through :mod:`repro.models.stack`'s
    generic ``tree.map`` plumbing unchanged, and the decode scan body
    keeps its structure.
  * only the GEMM weight leaves named in :data:`WEIGHT_KEYS` quantize;
    bias, norm, embedding and lm-head leaves stay full precision (they
    are tiny or accuracy-critical — the ``kv_dtype`` design's scale-row
    exemption, applied to the weight side).

``ops.*`` detect the dict form structurally and thread the scales into
the kernels; model call sites never change. The accuracy contract is the
same dtype-derived logits-closeness guard as the KV axis
(``quant.logits_guard_tol``); the bf16 path never sees this module.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import quant

# GEMM weight leaves of the dense-transformer families (attention + glu
# mlp projections). Leaves with other names — biases, norm scales,
# embedding/lm_head, recurrent/ssm mixers — stay full precision.
WEIGHT_KEYS = frozenset(
    {"wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"})


def is_quantized_leaf(w) -> bool:
    """True for the ``{"codes", "scale"}`` dict a quantized leaf becomes."""
    return isinstance(w, dict) and "codes" in w and "scale" in w


def quantize_weight(w: jax.Array, spec: quant.QuantSpec) -> dict:
    """One leaf ``(…, K, N)`` -> ``{"codes": (…, K, N), "scale": (…, N)}``.

    The reduction axis is K (the contraction dim), one step per output
    channel: transpose to (…, N, K), reuse the last-axis
    ``compute_step``/``encode`` algebra, transpose the codes back.
    """
    wt = jnp.swapaxes(w, -1, -2)                      # (…, N, K)
    step = quant.compute_step(wt, spec, axes=-1)      # (…, N)
    codes = jnp.swapaxes(quant.encode(wt, step, spec), -1, -2)
    return {"codes": codes, "scale": step.astype(jnp.float32)}


def dequantize_weight(wq: dict) -> jax.Array:
    """``codes * step`` back to a full (…, K, N) f32 weight (tests and
    error-bound probes; the serving path never materializes this)."""
    wt = quant.decode(jnp.swapaxes(wq["codes"], -1, -2), wq["scale"])
    return jnp.swapaxes(wt, -1, -2)


def quantize_params(params: dict, spec: quant.QuantSpec) -> dict:
    """Quantize every :data:`WEIGHT_KEYS` leaf under ``params["layers"]``.

    Returns a new pytree; non-weight leaves (and everything outside the
    layer stack — embedding, lm_head, final_norm) are passed through
    untouched. Stacked leaves ``(L, K, N)`` quantize per (layer, output
    channel) — the leading axes broadcast through the same algebra.
    """
    def walk(tree):
        if isinstance(tree, dict):
            return {
                key: (quantize_weight(v, spec)
                      if key in WEIGHT_KEYS and not isinstance(v, dict)
                      else walk(v))
                for key, v in tree.items()
            }
        return tree

    out = dict(params)
    if "layers" in out:
        out["layers"] = walk(out["layers"])
    return out


def gemm_weight_bytes(params: dict) -> int:
    """True stored bytes of the decode tick's GEMM weight stream: every
    :data:`WEIGHT_KEYS` leaf under ``params["layers"]``, codes *and*
    scales as stored (bf16 leaves at full width). Embedding/lm_head are
    excluded — they are not per-layer streams and never quantize."""
    total = 0

    def walk(tree):
        nonlocal total
        if not isinstance(tree, dict):
            return
        for key, v in tree.items():
            if key in WEIGHT_KEYS:
                if is_quantized_leaf(v):
                    total += v["codes"].nbytes + v["scale"].nbytes
                else:
                    total += v.nbytes
            elif isinstance(v, dict):
                walk(v)

    walk(params.get("layers", {}))
    return total
