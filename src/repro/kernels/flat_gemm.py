"""T2 — Flat GEMM with minimal M-padding and pipelined double buffering.

Paper §4 adapted to TPU:

  * "pad to 8, not 64": the M (token) dimension of decode-phase GEMMs is
    padded only to the sublane atom (8 for f32, here ``round_up(M, 8)``),
    never to a 64/128 tile. The kernel claims exactly an
    ``(M_pad, B_K) × (B_K, B_N)`` working set in VMEM.
  * double buffering: grid = (N/B_N, K/B_K) with
    ``dimension_semantics = ("parallel", "arbitrary")``. Mosaic's pipeline
    emitter double-buffers the input DMAs across the sequential K dimension —
    the (K+1)-th A/B tiles stream into VMEM while the MXU consumes the K-th.
    This is the TPU-native realization of the paper's shared-memory double
    buffering (Fig. 8): we control it structurally via BlockSpec shape
    choice rather than hand-written cp.async.
  * the Eq.-5 parallelism-vs-reuse trade-off is resolved by
    :func:`pick_bn` — the same napkin math with HBM→VMEM traffic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import hardware


def round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def pick_bn(m: int, n: int, k: int, *, dtype_bytes: int = 2,
            spec: hardware.HardwareSpec = hardware.TPU_V5E) -> int:
    """Eq. 5 on TPU: choose B_N balancing grid parallelism vs reuse.

    compute/memory ratio of a tile pass ≈ 2·M·K / (K + M·K/B_N + M); larger
    B_N amortizes the A-tile reload, smaller B_N gives more parallel grid
    steps to pipeline. We want at least ``min_grid`` parallel N-steps to keep
    the pipeline busy, subject to the spec's tile budget (double-buffered).
    """
    min_grid = 8     # pipeline depth worth of independent N tiles
    budget = spec.tile_budget
    best = 128
    for bn in (128, 256, 512, 1024, 2048):
        if n % bn:
            continue
        bk = pick_bk(m, bn, k, dtype_bytes=dtype_bytes, spec=spec)
        # double-buffered A and B tiles must fit
        vmem = 2 * (m * bk + bk * bn) * dtype_bytes + m * bn * 4
        if vmem > budget:
            break
        if n // bn >= min_grid or bn == 128:
            best = bn
    return min(best, n)


def pick_bk(m: int, bn: int, k: int, *, dtype_bytes: int = 2,
            spec: hardware.HardwareSpec = hardware.TPU_V5E) -> int:
    """Largest K tile whose double-buffered tiles fit the tile budget."""
    budget = spec.tile_budget
    best = 128
    for bk in (128, 256, 512, 1024, 2048, 4096):
        if k % bk:
            continue
        vmem = 2 * (m * bk + bk * bn) * dtype_bytes + m * bn * 4
        if vmem <= budget:
            best = bk
    return min(best, k)


def layer_stacks(layer, leaves, *, pad: bool):
    """``(layer operand, leaves)`` for a kernel that reads its weights as
    layer ``layer`` of ``(L, K, N)`` stacks (steps ``(L, N)``).

    The layer index rides in as a scalar-prefetch operand and the weight
    BlockSpecs take ``(None, bk, bn)`` blocks at ``(layer_ref[0], k, n)``,
    so the kernel reads the stack in place and no layer is ever copied
    out of it. ``layer is None`` marks per-layer leaves (``(K, N)``
    weights, ``(N,)`` steps): they become one-layer stacks, a bitcast,
    read at layer 0. A kernel that must ``pad`` a weight (a width no
    block divides) takes that one layer out of the stack first, so the
    pad copies a layer, never the whole stack. ``None`` leaves pass
    through.
    """
    if layer is None:
        leaves = [None if a is None else a[None] for a in leaves]
        layer = 0
    elif pad:
        leaves = [None if a is None else
                  jax.lax.dynamic_slice_in_dim(a, layer, 1, axis=0)
                  for a in leaves]
        layer = 0
    return jnp.asarray(layer, jnp.int32).reshape(1), leaves


def _flat_gemm_kernel(x_ref, w_ref, out_ref, acc_ref):
    ki = pl.program_id(1)
    n_k = pl.num_programs(1)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(ki == n_k - 1)
    def _fin():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _flat_gemm_quant_kernel(x_ref, w_ref, scale_ref, out_ref, acc_ref):
    """Quantized-weight variant: ``w_ref`` holds int8/fp8 codes streamed
    at stored width; the per-output-channel step (``scale_ref``, (1, B_N)
    f32) multiplies the f32 accumulator once in the epilogue — ``codes *
    step`` factored out of the K sum. The codes cast to the activation
    dtype for the MXU pass (int8 ±127 / fp8 e4m3 are exact in bf16)."""
    ki = pl.program_id(1)
    n_k = pl.num_programs(1)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...].astype(x_ref.dtype), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(ki == n_k - 1)
    def _fin():
        out_ref[...] = (acc_ref[...] * scale_ref[...]).astype(out_ref.dtype)


def flat_gemm(
    x: jax.Array,   # (M, K)
    w: jax.Array,   # (K, N)
    *,
    w_scale: jax.Array | None = None,   # (N,) f32 -> w is quantized codes
    block_n: int = 0,
    block_k: int = 0,
    out_dtype=None,
    spec: hardware.HardwareSpec = hardware.TPU_V5E,
    interpret: bool = False,
) -> jax.Array:
    """Minimal-pad flat GEMM. M is padded to the sublane atom (8), only.
    Tiles are sized against ``spec``'s tile budget, and the kernel asks
    Mosaic for ``spec.vmem_limit_bytes`` of scoped VMEM."""
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    out_dtype = out_dtype or x.dtype
    dtype_bytes = jnp.dtype(x.dtype).itemsize

    m_pad = round_up(max(m, 1), 8)           # <- "pad to 8 not 64"
    if m_pad != m:
        x = jnp.pad(x, ((0, m_pad - m), (0, 0)))

    bn = block_n or pick_bn(m_pad, n, k, dtype_bytes=dtype_bytes, spec=spec)
    bk = block_k or pick_bk(m_pad, bn, k, dtype_bytes=dtype_bytes, spec=spec)
    # pad N/K up to tile multiples if the caller passed odd sizes
    if n % bn:
        w = jnp.pad(w, ((0, 0), (0, bn - n % bn)))
    if k % bk:
        x = jnp.pad(x, ((0, 0), (0, bk - k % bk)))
        w = jnp.pad(w, ((0, bk - k % bk), (0, 0)))
    kp, np_ = x.shape[1], w.shape[1]

    kernel = _flat_gemm_kernel
    operands = [x, w]
    in_specs = [
        pl.BlockSpec((m_pad, bk), lambda n_, k_: (0, k_)),
        pl.BlockSpec((bk, bn), lambda n_, k_: (k_, n_)),
    ]
    if w_scale is not None:
        scale = w_scale.astype(jnp.float32).reshape(1, -1)
        if np_ != n:
            scale = jnp.pad(scale, ((0, 0), (0, np_ - n)))
        kernel = _flat_gemm_quant_kernel
        operands.append(scale)
        in_specs.append(pl.BlockSpec((1, bn), lambda n_, k_: (0, n_)))

    out = pl.pallas_call(
        kernel,
        grid=(np_ // bn, kp // bk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((m_pad, bn), lambda n_, k_: (0, n_)),
        out_shape=jax.ShapeDtypeStruct((m_pad, np_), out_dtype),
        scratch_shapes=[pltpu.VMEM((m_pad, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=spec.vmem_limit_bytes,
        ),
        name="flat_gemm",
        interpret=interpret,
    )(*operands)
    return out[:m, :n]
