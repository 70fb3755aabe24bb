"""ImplA — VPU GEMV kernel for M ∈ {1..4} (paper §5's FastGEMV analogue).

On GPU the paper routes tiny-M workloads to CUDA cores (FastGEMV) because
Tensor-Core GEMM wastes the M tile. The TPU analogue: for M ≤ 4 even the
8-sublane MXU pass wastes ≥ 50 % of issue slots, and the workload is purely
memory-bound (arithmetic intensity ≈ M FLOP/byte). This kernel keeps the MXU
out of the picture: a broadcast-multiply-reduce on the VPU, streaming W
K-major with the same double-buffered pipeline as the flat GEMM.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import hardware

DEFAULT_BLOCK_N = 256
DEFAULT_BLOCK_K = 512


def _gemv_kernel(x_ref, w_ref, out_ref, acc_ref):
    ki = pl.program_id(1)
    n_k = pl.num_programs(1)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)       # (M, BK)
    w = w_ref[...].astype(jnp.float32)       # (BK, BN)
    # VPU path: broadcast-multiply-reduce, no MXU involvement.
    acc_ref[...] += jnp.sum(x[:, :, None] * w[None, :, :], axis=1)

    @pl.when(ki == n_k - 1)
    def _fin():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _gemv_quant_kernel(x_ref, w_ref, scale_ref, out_ref, acc_ref):
    """Quantized-weight variant: the body already lifts W to f32 for the
    VPU — codes lift the same way — and the per-output-channel step
    ((1, B_N) f32) multiplies the f32 accumulator in the epilogue."""
    ki = pl.program_id(1)
    n_k = pl.num_programs(1)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)       # (M, BK)
    w = w_ref[...].astype(jnp.float32)       # (BK, BN) codes
    acc_ref[...] += jnp.sum(x[:, :, None] * w[None, :, :], axis=1)

    @pl.when(ki == n_k - 1)
    def _fin():
        out_ref[...] = (acc_ref[...] * scale_ref[...]).astype(out_ref.dtype)


def gemv(
    x: jax.Array,   # (M, K), M <= 4 typical
    w: jax.Array,   # (K, N)
    *,
    w_scale: jax.Array | None = None,   # (N,) f32 -> w is quantized codes
    block_n: int = DEFAULT_BLOCK_N,
    block_k: int = DEFAULT_BLOCK_K,
    out_dtype=None,
    spec: hardware.HardwareSpec = hardware.TPU_V5E,
    interpret: bool = False,
) -> jax.Array:
    m, k = x.shape
    _, n = w.shape
    out_dtype = out_dtype or x.dtype
    bn = min(block_n, n)
    bk = min(block_k, k)
    if n % bn:
        w = jnp.pad(w, ((0, 0), (0, bn - n % bn)))
    if k % bk:
        x = jnp.pad(x, ((0, 0), (0, bk - k % bk)))
        w = jnp.pad(w, ((0, bk - k % bk), (0, 0)))
    kp, np_ = x.shape[1], w.shape[1]

    kernel = _gemv_kernel
    operands = [x, w]
    in_specs = [
        pl.BlockSpec((m, bk), lambda n_, k_: (0, k_)),
        pl.BlockSpec((bk, bn), lambda n_, k_: (k_, n_)),
    ]
    if w_scale is not None:
        scale = w_scale.astype(jnp.float32).reshape(1, -1)
        if np_ != n:
            scale = jnp.pad(scale, ((0, 0), (0, np_ - n)))
        kernel = _gemv_quant_kernel
        operands.append(scale)
        in_specs.append(pl.BlockSpec((1, bn), lambda n_, k_: (0, n_)))

    out = pl.pallas_call(
        kernel,
        grid=(np_ // bn, kp // bk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((m, bn), lambda n_, k_: (0, n_)),
        out_shape=jax.ShapeDtypeStruct((m, np_), out_dtype),
        scratch_shapes=[pltpu.VMEM((m, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=spec.vmem_limit_bytes,
        ),
        name="gemv",
        interpret=interpret,
    )(*operands)
    return out[:, :n]
