"""T2 extension — fused flat-GEMM SwiGLU FFN-up for the decode phase.

The decode-phase FFN does two flat GEMMs on the same (M, D) activations
(gate and up projections) followed by ``silu(gate) * up``. Running them as
separate kernels costs an extra read of x and a full HBM round-trip of the
(M, F) gate and up tensors. This kernel computes

    h = silu(x @ w_gate) * (x @ w_up)

in one pass: both K-stream pipelines share the (M_pad, B_K) x-tile, the
epilogue runs on the VPU while the accumulators are still in VMEM, and
only the final (M, B_N) h-tile is written to HBM — the paper's
double-buffering insight extended across the FFN pair:

    HBM traffic    separate: 2·M·K + 2·K·N + 3·M·N   (h read back for mul)
                   fused:      M·K + 2·K·N +   M·N
    (decode M=8..128, K=d_model, N=d_ff: the 2·K·N weight stream dominates
     both, but the fused epilogue removes every activation round-trip and
     half the kernel launches.)

Same minimal M-padding rule as flat_gemm (pad to the 8-sublane atom).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import hardware
from repro.kernels.flat_gemm import layer_stacks, pick_bk, pick_bn, round_up


def _fused_ffn_kernel(layer_ref, x_ref, wg_ref, wu_ref, *refs,
                      activation: str, quantized: bool = False):
    # ``layer_ref`` (scalar prefetch) only steers the weight index maps.
    # Quantized variant appends two per-output-channel step operands
    # ((1, B_N) f32) after the weights; the branches are trace-time, so
    # the bf16 kernel's jaxpr is unchanged. Steps apply on the f32
    # accumulators *before* the activation (dequant-then-nonlinearity).
    del layer_ref
    if quantized:
        sg_ref, su_ref, out_ref, accg_ref, accu_ref = refs
    else:
        out_ref, accg_ref, accu_ref = refs
    ki = pl.program_id(1)
    n_k = pl.num_programs(1)

    @pl.when(ki == 0)
    def _init():
        accg_ref[...] = jnp.zeros_like(accg_ref)
        accu_ref[...] = jnp.zeros_like(accu_ref)

    x = x_ref[...]
    wg = wg_ref[...].astype(x.dtype) if quantized else wg_ref[...]
    wu = wu_ref[...].astype(x.dtype) if quantized else wu_ref[...]
    accg_ref[...] += jax.lax.dot_general(
        x, wg, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    accu_ref[...] += jax.lax.dot_general(
        x, wu, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(ki == n_k - 1)
    def _fin():
        g = accg_ref[...]
        u = accu_ref[...]
        if quantized:
            g = g * sg_ref[...]
            u = u * su_ref[...]
        act = jax.nn.silu(g) if activation == "swiglu" else jax.nn.gelu(g)
        out_ref[...] = (act * u).astype(out_ref.dtype)


def fused_ffn_up(
    x: jax.Array,        # (M, K)
    w_gate: jax.Array,   # (K, N), or an (L, K, N) stack read at ``layer``
    w_up: jax.Array,     # (K, N), or an (L, K, N) stack read at ``layer``
    *,
    activation: str = "swiglu",
    wg_scale: jax.Array | None = None,  # (N,) / (L, N) f32 -> codes
    wu_scale: jax.Array | None = None,  # (N,) / (L, N) f32 -> codes
    layer=None,
    block_n: int = 0,
    block_k: int = 0,
    out_dtype=None,
    spec: hardware.HardwareSpec = hardware.TPU_V5E,
    interpret: bool = False,
) -> jax.Array:
    """h = act(x @ w_gate) * (x @ w_up), epilogue fused in VMEM. With a
    ``layer`` the weights are stacks read in place
    (:func:`~repro.kernels.flat_gemm.layer_stacks`)."""
    assert (wg_scale is None) == (wu_scale is None), \
        "gate/up weights quantize together"
    m, k = x.shape
    k2, n = w_gate.shape[-2:]
    assert k2 == k and w_up.shape == w_gate.shape, (x.shape, w_gate.shape,
                                                    w_up.shape)
    out_dtype = out_dtype or x.dtype
    dtype_bytes = jnp.dtype(x.dtype).itemsize

    m_pad = round_up(max(m, 1), 8)
    if m_pad != m:
        x = jnp.pad(x, ((0, m_pad - m), (0, 0)))

    bn = block_n or pick_bn(m_pad, n, k, dtype_bytes=dtype_bytes, spec=spec)
    bk = block_k or pick_bk(m_pad, bn, k, dtype_bytes=dtype_bytes, spec=spec)
    # halve B_K if the doubled (two weight streams + two f32 accumulators)
    # working set would overflow the tile budget the single-GEMM picker
    # assumed
    budget = spec.tile_budget
    while bk > 128 and (
            2 * (m_pad * bk + 2 * bk * bn) * dtype_bytes
            + 2 * m_pad * bn * 4) > budget:
        bk //= 2
    pad_n, pad_k = -n % bn, -k % bk
    layer_op, (w_gate, w_up, wg_scale, wu_scale) = layer_stacks(
        layer, (w_gate, w_up, wg_scale, wu_scale), pad=bool(pad_n or pad_k))
    if pad_n:
        w_gate = jnp.pad(w_gate, ((0, 0), (0, 0), (0, pad_n)))
        w_up = jnp.pad(w_up, ((0, 0), (0, 0), (0, pad_n)))
    if pad_k:
        x = jnp.pad(x, ((0, 0), (0, pad_k)))
        w_gate = jnp.pad(w_gate, ((0, 0), (0, pad_k), (0, 0)))
        w_up = jnp.pad(w_up, ((0, 0), (0, pad_k), (0, 0)))
    kp, np_ = k + pad_k, n + pad_n

    quantized = wg_scale is not None
    operands = [x, w_gate, w_up]
    in_specs = [
        pl.BlockSpec((m_pad, bk), lambda n_, k_, l_: (0, k_)),
        pl.BlockSpec((None, bk, bn), lambda n_, k_, l_: (l_[0], k_, n_)),
        pl.BlockSpec((None, bk, bn), lambda n_, k_, l_: (l_[0], k_, n_)),
    ]
    if quantized:
        for s in (wg_scale, wu_scale):
            s = s.astype(jnp.float32)[:, None, :]
            if pad_n:
                s = jnp.pad(s, ((0, 0), (0, 0), (0, pad_n)))
            operands.append(s)
            in_specs.append(pl.BlockSpec(
                (None, 1, bn), lambda n_, k_, l_: (l_[0], 0, n_)))

    out = pl.pallas_call(
        functools.partial(_fused_ffn_kernel, activation=activation,
                          quantized=quantized),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(np_ // bn, kp // bk),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((m_pad, bn),
                                   lambda n_, k_, l_: (0, n_)),
            scratch_shapes=[
                pltpu.VMEM((m_pad, bn), jnp.float32),
                pltpu.VMEM((m_pad, bn), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((m_pad, np_), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=spec.vmem_limit_bytes,
        ),
        name="fused_ffn_up",
        interpret=interpret,
    )(layer_op, *operands)
    return out[:m, :n]
