"""The GEMM seam kernels read their weights out of the stacked params in
place.

Contract under test (interpret mode):
  * ``decode_ingest_fused``, ``oproj_residual_fused``, ``ffn_norm_fused``
    and ``fused_ffn_up`` handed ``(L, K, N)`` weight stacks (steps
    ``(L, N)``) and a layer index give, bit for bit, what the same
    kernel gives on that layer's ``(K, N)`` slice: the first, middle and
    last layer, bf16, int8 and (where the stack stores it) fp8 weights,
    at qwen2-0.5b's and phi3-mini-3.8b's published widths;
  * a width no block divides is padded one layer at a time: the layer is
    taken out of the stack first, and no pad ever copies the stack.

The compiled-program side (no weight-sized copy left in the served step
programs) is ``tests/test_tpu_compile.py::test_step_reads_weights_in_place``.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.kernels import quant
from repro.kernels.decode_fuse import (
    decode_ingest_fused, ffn_norm_fused, oproj_residual_fused,
)
from repro.kernels.fused_ffn import fused_ffn_up
from repro.models import wquant

WIDTHS = {"phi3": configs.get("phi3-mini-3.8b"),
          "qwen": configs.get("qwen2-0.5b")}
WDTYPES = ["bf16", "int8"] + (["fp8"] if quant.fp8_supported() else [])
KERNELS = ("decode_ingest", "oproj_residual", "ffn_norm", "fused_ffn_up")
M = 8            # decode rows: the phi3 cell's slots
LAYERS = 3       # read at 0, LAYERS // 2 and LAYERS - 1


def _weight_shapes(kernel, cfg):
    d, q, kv, f = cfg.d_model, cfg.q_dim, cfg.kv_dim, cfg.d_ff
    return {"decode_ingest": [(d, q), (d, kv), (d, kv)],
            "oproj_residual": [(q, d)],
            "ffn_norm": [(d, f), (d, f)],
            "fused_ffn_up": [(d, f), (d, f)]}[kernel]


def _stacks(kernel, cfg, wdtype):
    """``LAYERS``-deep weight stacks and their steps (``None`` for bf16)."""
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    ws, ss = [], []
    for key, (k, n) in zip(keys, _weight_shapes(kernel, cfg)):
        w = (jax.random.normal(key, (LAYERS, k, n), jnp.float32)
             * k ** -0.5).astype(jnp.bfloat16)
        if wdtype == "bf16":
            ws.append(w)
            ss.append(None)
        else:
            wq = wquant.quantize_weight(w, quant.spec_for(wdtype))
            ws.append(wq["codes"])
            ss.append(wq["scale"])
    return ws, ss


def _call(kernel, cfg, ws, ss, layer):
    """One kernel call on fixed activations (``layer`` None: per-layer
    weights)."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(3), 3)
    d, q, kv = cfg.d_model, cfg.q_dim, cfg.kv_dim
    bf16 = jnp.bfloat16
    x = jax.random.normal(k1, (M, d), jnp.float32).astype(bf16)
    norm = (1.0 + 0.1 * jax.random.normal(k2, (d,))).astype(bf16)
    if kernel == "decode_ingest":
        bias = ({"bq": jnp.full((q,), 0.01, bf16),
                 "bk_bias": jnp.full((kv,), -0.02, bf16),
                 "bv": jnp.full((kv,), 0.03, bf16)} if cfg.qkv_bias else {})
        return decode_ingest_fused(
            x, norm, *ws, jnp.arange(M, dtype=jnp.int32) * 37,
            num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
            head_dim=cfg.head_dim, rope_theta=cfg.rope_theta,
            eps=cfg.rms_norm_eps, wq_scale=ss[0], wk_scale=ss[1],
            wv_scale=ss[2], layer=layer, interpret=True, **bias)
    if kernel == "oproj_residual":
        o = jax.random.normal(k3, (M, ws[0].shape[-2]),
                              jnp.float32).astype(bf16)
        return oproj_residual_fused(o, ws[0], x, w_scale=ss[0], layer=layer,
                                    interpret=True)
    if kernel == "ffn_norm":
        return ffn_norm_fused(x, norm, *ws, eps=cfg.rms_norm_eps,
                              wg_scale=ss[0], wu_scale=ss[1], layer=layer,
                              interpret=True)
    return fused_ffn_up(x, *ws, wg_scale=ss[0], wu_scale=ss[1], layer=layer,
                        interpret=True)


def _at(leaves, layer):
    return [None if a is None else a[layer] for a in leaves]


@pytest.mark.parametrize("wdtype", WDTYPES)
@pytest.mark.parametrize("model", sorted(WIDTHS))
@pytest.mark.parametrize("kernel", KERNELS)
def test_stacked_weights_match_layer_slice(kernel, model, wdtype):
    """Stack + layer index == the same kernel on the layer's slice, bit
    for bit, at the first, middle and last layer."""
    cfg = WIDTHS[model]
    ws, ss = _stacks(kernel, cfg, wdtype)
    stacked = jax.jit(lambda w, s, l: _call(kernel, cfg, w, s, l))
    sliced = jax.jit(lambda w, s: _call(kernel, cfg, w, s, None))
    for layer in (0, LAYERS // 2, LAYERS - 1):
        got = stacked(ws, ss, jnp.int32(layer))
        want = sliced(_at(ws, layer), _at(ss, layer))
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            assert g.shape == w.shape
            assert np.array_equal(np.asarray(g), np.asarray(w)), layer


# widths no 128-lane block divides: a q width of 3 x 64 heads, and an N of
# 200 (d_ff for the FFN kernels, d_model for the output projection)
ODD = dataclasses.replace(WIDTHS["qwen"], d_model=256, num_heads=3,
                          num_kv_heads=1, head_dim=64, d_ff=200)


@pytest.mark.parametrize("kernel", KERNELS)
def test_unblockable_width_pads_one_layer(kernel):
    """Where a weight must be padded, the kernel takes its layer out of
    the stack and pads that: no pad in the traced program is the size of
    the stack, and the result is the per-layer call's."""
    cfg = ODD if kernel != "oproj_residual" else dataclasses.replace(
        ODD, d_model=200, num_heads=4)
    ws, ss = _stacks(kernel, cfg, "bf16")
    layer = jnp.int32(LAYERS - 1)
    jaxpr = jax.make_jaxpr(
        lambda w, l: _call(kernel, cfg, w, ss, l))(ws, layer).jaxpr

    def eqns(jx):
        for e in jx.eqns:
            yield e
            for sub in jax.core.jaxprs_in_params(e.params):
                yield from eqns(sub)

    pads = [e.outvars[0].aval.shape for e in eqns(jaxpr)
            if e.primitive.name == "pad"]
    weight_pads = [s for s in pads if len(s) == 3]
    assert weight_pads, pads             # the width really needs a pad
    assert all(s[0] == 1 for s in weight_pads), weight_pads
    got = _call(kernel, cfg, ws, ss, layer)
    want = _call(kernel, cfg, _at(ws, LAYERS - 1), ss, None)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert np.array_equal(np.asarray(g), np.asarray(w))
