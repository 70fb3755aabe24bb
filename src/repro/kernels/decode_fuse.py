"""Fused decode-layer stage kernels (the decode seams of models/layers.py).

Per-token decode runs a long chain of small memory-bound ops per layer;
each op boundary pays a dispatch bubble and an HBM round-trip of its
(M, ·) activation. These kernels collapse the two attention-side seams
the fused-FFN kernel does not cover:

  * :func:`decode_ingest_fused` — rmsnorm → QKV projections → bias →
    rope in one pass. The (M, D) residual-stream tile stays resident in
    VMEM: the norm runs once into a normalized-x scratch, the three
    weight streams share it across the K grid, and the rope rotation is
    applied to the q/k accumulators in the epilogue while they are still
    in VMEM — the normed activations and the pre-rope q/k never touch
    HBM.
  * :func:`oproj_residual_fused` — attention epilogue ``resid + o @ wo``:
    the residual add rides the GEMM epilogue, saving the (M, D)
    attention-output round-trip and one launch. The same kernel serves
    the FFN down-projection seam (``resid + h @ w_down``) — both are
    "GEMM into the residual stream" shapes.
  * :func:`ffn_norm_fused` — mlp_norm → gate/up projections →
    activation in one pass: the fused-FFN kernel's epilogue with the
    rmsnorm pulled inside, so the normed (M, D) activations never
    round-trip HBM between the norm and the GEMM pair.

Each kernel reads its weights as layer ``layer`` of ``(L, K, N)``
stacks, the layer index a scalar-prefetch operand of the weight
BlockSpecs (:func:`~repro.kernels.flat_gemm.layer_stacks`): the paged
layer scan hands down the whole stacked params, and no layer's weight is
copied out of the stack in a step. A per-layer ``(K, N)`` weight is the
one-layer stack at layer 0.

Decode M is tiny (the batch), so everything is flat-GEMM shaped: M pads
to the 8-sublane atom and the K dimension streams (same discipline as
``kernels/flat_gemm.py`` / ``kernels/fused_ffn.py``). The K-streamed f32
tile accumulation reassociates the dot relative to the single-dot
oracles in ``ref.py``, so kernel-vs-oracle equality is dtype-eps bounded
(like every other Pallas GEMM here), while on XLA the seams dispatch
the oracles themselves and stay bit-identical to the op chain.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import hardware
from repro.kernels.flat_gemm import layer_stacks, pick_bk, pick_bn, round_up


def _rope_pairs(t, cos, sin, n_heads: int, head_dim: int):
    """Rotate-half rope on a flat (M, n_heads*head_dim + pad) tile.

    Static per-head slices (no in-kernel reshape): head h's first half
    pairs with its second half, exactly ``models.layers.rope``'s
    ``[x1*cos - x2*sin, x2*cos + x1*sin]`` layout. Pad columns past the
    real heads pass through untouched.
    """
    half = head_dim // 2
    parts = []
    for h in range(n_heads):
        x1 = t[:, h * head_dim:h * head_dim + half]
        x2 = t[:, h * head_dim + half:(h + 1) * head_dim]
        parts.append(x1 * cos - x2 * sin)
        parts.append(x2 * cos + x1 * sin)
    if t.shape[1] > n_heads * head_dim:
        parts.append(t[:, n_heads * head_dim:])
    return jnp.concatenate(parts, axis=1)


def _ingest_kernel(layer_ref, x_ref, scale_ref, wq_ref, wk_ref, wv_ref,
                   bq_ref, bk_ref, bv_ref, pos_ref, *refs,
                   d_real: int, bk: int, num_heads: int,
                   num_kv_heads: int, head_dim: int, theta: float,
                   eps: float, use_rope: bool, quantized: bool = False):
    # ``layer_ref`` (scalar prefetch) only steers the weight index maps.
    # The quantized variant appends three per-output-channel step operands
    # ((1, NQ)/(1, NK) f32, full-width like the biases) after ``pos``; the
    # branches are trace-time, so the bf16 kernel's jaxpr is unchanged.
    del layer_ref
    if quantized:
        (sq_ref, sk_ref, sv_ref,
         outq_ref, outk_ref, outv_ref,
         xn_ref, accq_ref, acck_ref, accv_ref) = refs
    else:
        (outq_ref, outk_ref, outv_ref,
         xn_ref, accq_ref, acck_ref, accv_ref) = refs
    ki = pl.program_id(0)
    n_k = pl.num_programs(0)

    @pl.when(ki == 0)
    def _init():
        # rmsnorm once into the resident normed-x scratch (cast back to
        # the activation dtype before the dot, like the op chain);
        # zero K-pad columns keep the sum exact, the divisor is real D
        xf = x_ref[...].astype(jnp.float32)
        var = jnp.sum(xf * xf, axis=-1, keepdims=True) / d_real
        xn = xf * jax.lax.rsqrt(var + eps) * scale_ref[...].astype(
            jnp.float32)
        xn_ref[...] = xn.astype(xn_ref.dtype)
        accq_ref[...] = jnp.zeros_like(accq_ref)
        acck_ref[...] = jnp.zeros_like(acck_ref)
        accv_ref[...] = jnp.zeros_like(accv_ref)

    xt = xn_ref[:, pl.ds(ki * bk, bk)]
    dims = (((1,), (0,)), ((), ()))
    wq_t = wq_ref[...].astype(xt.dtype) if quantized else wq_ref[...]
    wk_t = wk_ref[...].astype(xt.dtype) if quantized else wk_ref[...]
    wv_t = wv_ref[...].astype(xt.dtype) if quantized else wv_ref[...]
    accq_ref[...] += jax.lax.dot_general(
        xt, wq_t, dims, preferred_element_type=jnp.float32)
    acck_ref[...] += jax.lax.dot_general(
        xt, wk_t, dims, preferred_element_type=jnp.float32)
    accv_ref[...] += jax.lax.dot_general(
        xt, wv_t, dims, preferred_element_type=jnp.float32)

    @pl.when(ki == n_k - 1)
    def _fin():
        # round the f32 accumulators to the activation dtype *before* the
        # bias add and rope, mirroring the op chain's rounding points
        # (matmul output cast, bf16 bias add, rope promoting to f32);
        # weight steps dequantize on the f32 accumulators first
        accq, acck, accv = accq_ref[...], acck_ref[...], accv_ref[...]
        if quantized:
            accq = accq * sq_ref[...]
            acck = acck * sk_ref[...]
            accv = accv * sv_ref[...]
        q = accq.astype(outq_ref.dtype) + bq_ref[...]
        k = acck.astype(outk_ref.dtype) + bk_ref[...]
        v = accv.astype(outv_ref.dtype) + bv_ref[...]
        if use_rope:
            half = head_dim // 2
            # Mosaic's iota is integer-only: build int32, then cast
            ih = jax.lax.broadcasted_iota(jnp.int32, (1, half), 1).astype(
                jnp.float32)
            freq = theta ** (-ih / half)
            ang = pos_ref[...] * freq            # (M, half)
            cos, sin = jnp.cos(ang), jnp.sin(ang)
            q = _rope_pairs(q, cos, sin, num_heads, head_dim)
            k = _rope_pairs(k, cos, sin, num_kv_heads, head_dim)
        outq_ref[...] = q.astype(outq_ref.dtype)
        outk_ref[...] = k.astype(outk_ref.dtype)
        outv_ref[...] = v.astype(outv_ref.dtype)


def decode_ingest_fused(
    x: jax.Array,             # (M, D) residual-stream rows
    norm_scale: jax.Array,    # (D,)
    wq: jax.Array,            # (D, HQ*Dh), or an (L, D, HQ*Dh) stack
    wk: jax.Array,            # (D, HK*Dh), or an (L, D, HK*Dh) stack
    wv: jax.Array,
    positions: jax.Array,     # (M,) int32
    *,
    num_heads: int,
    num_kv_heads: int,
    head_dim: int,
    rope_theta: float = 1e4,
    eps: float,
    use_rope: bool = True,
    bq: jax.Array | None = None,
    bk_bias: jax.Array | None = None,
    bv: jax.Array | None = None,
    wq_scale: jax.Array | None = None,   # (HQ*Dh,) f32 -> wq is codes
    wk_scale: jax.Array | None = None,   # (HK*Dh,) f32 -> wk is codes
    wv_scale: jax.Array | None = None,
    layer=None,
    block_k: int = 0,
    spec: hardware.HardwareSpec = hardware.TPU_V5E,
    interpret: bool = False,
):
    """Fused rmsnorm → QKV → bias → rope. Returns flat q (M, HQ*Dh) and
    k/v (M, HK*Dh) in x.dtype (the caller owns the head reshape). With a
    ``layer`` the weights (and steps, ``(L, N)``) are stacks read in
    place (:func:`~repro.kernels.flat_gemm.layer_stacks`)."""
    assert (wq_scale is None) == (wk_scale is None) == (wv_scale is None), \
        "qkv weights quantize together"
    m, d = x.shape
    nq, nk = wq.shape[-1], wk.shape[-1]
    assert nq == num_heads * head_dim and nk == num_kv_heads * head_dim
    dtype_bytes = jnp.dtype(x.dtype).itemsize

    m_pad = round_up(max(m, 1), 8)
    if m_pad != m:
        x = jnp.pad(x, ((0, m_pad - m), (0, 0)))
        positions = jnp.pad(positions, (0, m_pad - m))
    pos = positions.astype(jnp.float32)[:, None]     # (m_pad, 1)

    # absent biases ride as zeros: x + 0 is exact in f32, and one kernel
    # signature serves both bias conventions
    bq = jnp.zeros((nq,), x.dtype) if bq is None else bq
    bk_bias = jnp.zeros((nk,), x.dtype) if bk_bias is None else bk_bias
    bv = jnp.zeros((nk,), x.dtype) if bv is None else bv

    nqp, nkp = round_up(nq, 128), round_up(nk, 128)
    bk = block_k or pick_bk(m_pad, nqp + 2 * nkp, d,
                            dtype_bytes=dtype_bytes, spec=spec)
    # the working set holds three double-buffered weight streams, the
    # resident x + normed-x scratch, and three f32 accumulators — halve
    # B_K until it fits the same budget the single-GEMM picker assumed
    budget = spec.tile_budget
    kp = round_up(d, bk)
    while bk > 128 and (
            2 * bk * (nqp + 2 * nkp) * dtype_bytes
            + 2 * m_pad * kp * dtype_bytes
            + m_pad * (nqp + 2 * nkp) * 4) > budget:
        bk //= 2
        kp = round_up(d, bk)
    layer_op, (wq, wk, wv, wq_scale, wk_scale, wv_scale) = layer_stacks(
        layer, (wq, wk, wv, wq_scale, wk_scale, wv_scale),
        pad=(nqp, nkp, kp) != (nq, nk, d))
    if nqp != nq:
        wq = jnp.pad(wq, ((0, 0), (0, 0), (0, nqp - nq)))
        bq = jnp.pad(bq, (0, nqp - nq))
    if nkp != nk:
        wk = jnp.pad(wk, ((0, 0), (0, 0), (0, nkp - nk)))
        wv = jnp.pad(wv, ((0, 0), (0, 0), (0, nkp - nk)))
        bk_bias = jnp.pad(bk_bias, (0, nkp - nk))
        bv = jnp.pad(bv, (0, nkp - nk))
    if kp != d:
        x = jnp.pad(x, ((0, 0), (0, kp - d)))
        norm_scale = jnp.pad(norm_scale, (0, kp - d))
        wq = jnp.pad(wq, ((0, 0), (0, kp - d), (0, 0)))
        wk = jnp.pad(wk, ((0, 0), (0, kp - d), (0, 0)))
        wv = jnp.pad(wv, ((0, 0), (0, kp - d), (0, 0)))

    quantized = wq_scale is not None
    operands = [x, norm_scale[None, :], wq, wk, wv,
                bq[None, :], bk_bias[None, :], bv[None, :], pos]
    in_specs = [
        pl.BlockSpec((m_pad, kp), lambda k_, l_: (0, 0)),
        pl.BlockSpec((1, kp), lambda k_, l_: (0, 0)),
        pl.BlockSpec((None, bk, nqp), lambda k_, l_: (l_[0], k_, 0)),
        pl.BlockSpec((None, bk, nkp), lambda k_, l_: (l_[0], k_, 0)),
        pl.BlockSpec((None, bk, nkp), lambda k_, l_: (l_[0], k_, 0)),
        pl.BlockSpec((1, nqp), lambda k_, l_: (0, 0)),
        pl.BlockSpec((1, nkp), lambda k_, l_: (0, 0)),
        pl.BlockSpec((1, nkp), lambda k_, l_: (0, 0)),
        pl.BlockSpec((m_pad, 1), lambda k_, l_: (0, 0)),
    ]
    if quantized:
        for s, width in ((wq_scale, nqp), (wk_scale, nkp), (wv_scale, nkp)):
            s = s.astype(jnp.float32)[:, None, :]
            if s.shape[2] != width:
                s = jnp.pad(s, ((0, 0), (0, 0), (0, width - s.shape[2])))
            operands.append(s)
            in_specs.append(pl.BlockSpec(
                (None, 1, width), lambda k_, l_: (l_[0], 0, 0)))

    outq, outk, outv = pl.pallas_call(
        functools.partial(
            _ingest_kernel, d_real=d, bk=bk, num_heads=num_heads,
            num_kv_heads=num_kv_heads, head_dim=head_dim,
            theta=rope_theta, eps=eps, use_rope=use_rope,
            quantized=quantized),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(kp // bk,),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((m_pad, nqp), lambda k_, l_: (0, 0)),
                pl.BlockSpec((m_pad, nkp), lambda k_, l_: (0, 0)),
                pl.BlockSpec((m_pad, nkp), lambda k_, l_: (0, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((m_pad, kp), x.dtype),
                pltpu.VMEM((m_pad, nqp), jnp.float32),
                pltpu.VMEM((m_pad, nkp), jnp.float32),
                pltpu.VMEM((m_pad, nkp), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((m_pad, nqp), x.dtype),
            jax.ShapeDtypeStruct((m_pad, nkp), x.dtype),
            jax.ShapeDtypeStruct((m_pad, nkp), x.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=spec.vmem_limit_bytes,
        ),
        name="decode_ingest_fused",
        interpret=interpret,
    )(layer_op, *operands)
    return outq[:m, :nq], outk[:m, :nk], outv[:m, :nk]


def _oproj_kernel(layer_ref, o_ref, wo_ref, resid_ref, *refs,
                  quantized: bool = False):
    # ``layer_ref`` (scalar prefetch) only steers the weight index maps;
    # quantized appends one (1, B_N) f32 step operand after ``resid``;
    # trace-time branch, bf16 jaxpr unchanged
    del layer_ref
    if quantized:
        scale_ref, out_ref, acc_ref = refs
    else:
        out_ref, acc_ref = refs
    ki = pl.program_id(1)
    n_k = pl.num_programs(1)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    wo_t = wo_ref[...].astype(o_ref.dtype) if quantized else wo_ref[...]
    acc_ref[...] += jax.lax.dot_general(
        o_ref[...], wo_t, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(ki == n_k - 1)
    def _fin():
        # cast before the add, mirroring the op chain's
        # `x + matmul(o, wo)` operand dtypes; the weight step dequantizes
        # on the f32 accumulator first
        acc = acc_ref[...]
        if quantized:
            acc = acc * scale_ref[...]
        out_ref[...] = resid_ref[...] + acc.astype(out_ref.dtype)


def oproj_residual_fused(
    o: jax.Array,       # (M, Q) attention outputs
    wo: jax.Array,      # (Q, D), or an (L, Q, D) stack read at ``layer``
    resid: jax.Array,   # (M, D) residual stream
    *,
    w_scale: jax.Array | None = None,   # (D,) / (L, D) f32 -> wo is codes
    layer=None,
    block_n: int = 0,
    block_k: int = 0,
    spec: hardware.HardwareSpec = hardware.TPU_V5E,
    interpret: bool = False,
) -> jax.Array:
    """resid + o @ wo with the residual add fused into the GEMM epilogue.
    With a ``layer`` the weight is a stack read in place
    (:func:`~repro.kernels.flat_gemm.layer_stacks`)."""
    m, k = o.shape
    k2, n = wo.shape[-2:]
    assert k2 == k and resid.shape == (m, n), (o.shape, wo.shape,
                                               resid.shape)
    dtype_bytes = jnp.dtype(o.dtype).itemsize

    m_pad = round_up(max(m, 1), 8)
    if m_pad != m:
        o = jnp.pad(o, ((0, m_pad - m), (0, 0)))
        resid = jnp.pad(resid, ((0, m_pad - m), (0, 0)))

    bn = block_n or pick_bn(m_pad, n, k, dtype_bytes=dtype_bytes, spec=spec)
    bk = block_k or pick_bk(m_pad, bn, k, dtype_bytes=dtype_bytes, spec=spec)
    pad_n, pad_k = -n % bn, -k % bk
    layer_op, (wo, w_scale) = layer_stacks(layer, (wo, w_scale),
                                           pad=bool(pad_n or pad_k))
    if pad_n:
        wo = jnp.pad(wo, ((0, 0), (0, 0), (0, pad_n)))
        resid = jnp.pad(resid, ((0, 0), (0, pad_n)))
    if pad_k:
        o = jnp.pad(o, ((0, 0), (0, pad_k)))
        wo = jnp.pad(wo, ((0, 0), (0, pad_k), (0, 0)))
    kp, np_ = k + pad_k, n + pad_n

    quantized = w_scale is not None
    operands = [o, wo, resid]
    in_specs = [
        pl.BlockSpec((m_pad, bk), lambda n_, k_, l_: (0, k_)),
        pl.BlockSpec((None, bk, bn), lambda n_, k_, l_: (l_[0], k_, n_)),
        pl.BlockSpec((m_pad, bn), lambda n_, k_, l_: (0, n_)),
    ]
    if quantized:
        scale = w_scale.astype(jnp.float32)[:, None, :]
        if pad_n:
            scale = jnp.pad(scale, ((0, 0), (0, 0), (0, pad_n)))
        operands.append(scale)
        in_specs.append(pl.BlockSpec(
            (None, 1, bn), lambda n_, k_, l_: (l_[0], 0, n_)))

    out = pl.pallas_call(
        functools.partial(_oproj_kernel, quantized=quantized),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(np_ // bn, kp // bk),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((m_pad, bn),
                                   lambda n_, k_, l_: (0, n_)),
            scratch_shapes=[pltpu.VMEM((m_pad, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((m_pad, np_), resid.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=spec.vmem_limit_bytes,
        ),
        name="oproj_residual_fused",
        interpret=interpret,
    )(layer_op, *operands)
    return out[:m, :n]


def _ffn_norm_kernel(layer_ref, x_ref, scale_ref, wg_ref, wu_ref, *refs,
                     d_real: int, bk: int, activation: str, eps: float,
                     quantized: bool = False):
    # ``layer_ref`` (scalar prefetch) only steers the weight index maps;
    # quantized appends two (1, B_N) f32 step operands after ``w_up``;
    # trace-time branch, bf16 jaxpr unchanged
    del layer_ref
    if quantized:
        sg_ref, su_ref, out_ref, xn_ref, accg_ref, accu_ref = refs
    else:
        out_ref, xn_ref, accg_ref, accu_ref = refs
    ni = pl.program_id(0)
    ki = pl.program_id(1)
    n_k = pl.num_programs(1)

    @pl.when((ni == 0) & (ki == 0))
    def _norm():
        # rmsnorm once into the resident normed-x scratch; it persists
        # across the whole (N, K) grid (both dims "arbitrary" = sequential)
        xf = x_ref[...].astype(jnp.float32)
        var = jnp.sum(xf * xf, axis=-1, keepdims=True) / d_real
        xn = xf * jax.lax.rsqrt(var + eps) * scale_ref[...].astype(
            jnp.float32)
        xn_ref[...] = xn.astype(xn_ref.dtype)

    @pl.when(ki == 0)
    def _init():
        accg_ref[...] = jnp.zeros_like(accg_ref)
        accu_ref[...] = jnp.zeros_like(accu_ref)

    xt = xn_ref[:, pl.ds(ki * bk, bk)]
    dims = (((1,), (0,)), ((), ()))
    wg_t = wg_ref[...].astype(xt.dtype) if quantized else wg_ref[...]
    wu_t = wu_ref[...].astype(xt.dtype) if quantized else wu_ref[...]
    accg_ref[...] += jax.lax.dot_general(
        xt, wg_t, dims, preferred_element_type=jnp.float32)
    accu_ref[...] += jax.lax.dot_general(
        xt, wu_t, dims, preferred_element_type=jnp.float32)

    @pl.when(ki == n_k - 1)
    def _fin():
        # activation on the unrounded f32 accumulators, like the fused-FFN
        # kernel's epilogue (and fused_ffn_up_ref); weight steps
        # dequantize on the accumulators before the nonlinearity
        g, u = accg_ref[...], accu_ref[...]
        if quantized:
            g = g * sg_ref[...]
            u = u * su_ref[...]
        act = jax.nn.silu(g) if activation == "swiglu" else jax.nn.gelu(g)
        out_ref[...] = (act * u).astype(out_ref.dtype)


def ffn_norm_fused(
    x: jax.Array,             # (M, D) residual-stream rows (un-normed)
    norm_scale: jax.Array,    # (D,)
    w_gate: jax.Array,        # (D, F), or an (L, D, F) stack
    w_up: jax.Array,          # (D, F), or an (L, D, F) stack
    *,
    activation: str = "swiglu",
    eps: float,
    wg_scale: jax.Array | None = None,  # (F,) / (L, F) f32 -> codes
    wu_scale: jax.Array | None = None,  # (F,) / (L, F) f32 -> codes
    layer=None,
    block_n: int = 0,
    block_k: int = 0,
    spec: hardware.HardwareSpec = hardware.TPU_V5E,
    interpret: bool = False,
) -> jax.Array:
    """Fused rmsnorm → gate/up GEMMs → act(g)*u. Returns (M, F) in
    x.dtype — feed it to :func:`oproj_residual_fused` with ``w_down``
    for the full mlp seam. With a ``layer`` the weights are stacks read
    in place (:func:`~repro.kernels.flat_gemm.layer_stacks`)."""
    assert (wg_scale is None) == (wu_scale is None), \
        "gate/up weights quantize together"
    m, d = x.shape
    d2, f = w_gate.shape[-2:]
    assert d2 == d and w_up.shape == w_gate.shape, (x.shape, w_gate.shape,
                                                    w_up.shape)
    dtype_bytes = jnp.dtype(x.dtype).itemsize

    m_pad = round_up(max(m, 1), 8)
    if m_pad != m:
        x = jnp.pad(x, ((0, m_pad - m), (0, 0)))

    bn = block_n or pick_bn(m_pad, f, d, dtype_bytes=dtype_bytes, spec=spec)
    bk = block_k or pick_bk(m_pad, bn, d, dtype_bytes=dtype_bytes, spec=spec)
    # two double-buffered weight streams + resident x and normed-x +
    # two f32 accumulators — shrink blocks until the set fits
    budget = spec.tile_budget
    kp = round_up(d, bk)

    def _working_set(bn_, bk_, kp_):
        return (2 * 2 * bk_ * bn_ * dtype_bytes
                + 2 * m_pad * kp_ * dtype_bytes
                + 2 * m_pad * bn_ * 4)

    while bn > 128 and _working_set(bn, bk, kp) > budget:
        bn //= 2
    while bk > 128 and _working_set(bn, bk, kp) > budget:
        bk //= 2
        kp = round_up(d, bk)

    fp = round_up(f, bn)
    layer_op, (w_gate, w_up, wg_scale, wu_scale) = layer_stacks(
        layer, (w_gate, w_up, wg_scale, wu_scale), pad=(fp, kp) != (f, d))
    if fp != f:
        w_gate = jnp.pad(w_gate, ((0, 0), (0, 0), (0, fp - f)))
        w_up = jnp.pad(w_up, ((0, 0), (0, 0), (0, fp - f)))
    if kp != d:
        x = jnp.pad(x, ((0, 0), (0, kp - d)))
        norm_scale = jnp.pad(norm_scale, (0, kp - d))
        w_gate = jnp.pad(w_gate, ((0, 0), (0, kp - d), (0, 0)))
        w_up = jnp.pad(w_up, ((0, 0), (0, kp - d), (0, 0)))

    quantized = wg_scale is not None
    operands = [x, norm_scale[None, :], w_gate, w_up]
    in_specs = [
        pl.BlockSpec((m_pad, kp), lambda n_, k_, l_: (0, 0)),
        pl.BlockSpec((1, kp), lambda n_, k_, l_: (0, 0)),
        pl.BlockSpec((None, bk, bn), lambda n_, k_, l_: (l_[0], k_, n_)),
        pl.BlockSpec((None, bk, bn), lambda n_, k_, l_: (l_[0], k_, n_)),
    ]
    if quantized:
        for s in (wg_scale, wu_scale):
            s = s.astype(jnp.float32)[:, None, :]
            if fp != f:
                s = jnp.pad(s, ((0, 0), (0, 0), (0, fp - f)))
            operands.append(s)
            in_specs.append(pl.BlockSpec(
                (None, 1, bn), lambda n_, k_, l_: (l_[0], 0, n_)))

    out = pl.pallas_call(
        functools.partial(_ffn_norm_kernel, d_real=d, bk=bk,
                          activation=activation, eps=eps,
                          quantized=quantized),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(fp // bn, kp // bk),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((m_pad, bn),
                                   lambda n_, k_, l_: (0, n_)),
            scratch_shapes=[
                pltpu.VMEM((m_pad, kp), x.dtype),
                pltpu.VMEM((m_pad, bn), jnp.float32),
                pltpu.VMEM((m_pad, bn), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((m_pad, fp), x.dtype),
        compiler_params=pltpu.CompilerParams(
            # the N dim must run sequentially too: every N block reads
            # the normed-x scratch written at grid step (0, 0)
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=spec.vmem_limit_bytes,
        ),
        name="ffn_norm_fused",
        interpret=interpret,
    )(layer_op, *operands)
    return out[:m, :f]
