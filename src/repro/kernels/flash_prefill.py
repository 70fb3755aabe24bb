"""Fused causal flash attention for the prefill phase (Pallas TPU).

Two softmax schemes, selected by ``unified_max``:

  * ``unified_max=False`` — FlashAttention-2 style online softmax: carry
    ``(m, l, acc)`` across KV blocks, rescaling the accumulator whenever the
    running max grows (the paper's Fig. 4(b) synchronized scheme).
  * ``unified_max=True``  — the paper's T1: a static scaling constant φ.
    No max carry, no rescale; each KV block contributes an order-independent
    ``(num, den)`` partial. Also reports max(s−φ) for the overflow fallback.

GQA is handled inside the BlockSpec index map (``kv_head = q_head // group``)
so grouped query heads read the shared KV tile straight from HBM without
materializing repeated heads.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 256

_NEG_INF = -1e30


def _mask(block_q, block_k, qi, ki, seq_k_start_delta, causal, window):
    """Boolean (block_q, block_k) validity mask for this tile pair."""
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    ) + seq_k_start_delta
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )
    m = jnp.ones((block_q, block_k), dtype=jnp.bool_)
    if causal:
        m &= q_pos >= k_pos
    if window:
        m &= (q_pos - k_pos) < window
    return m


def _prefill_kernel_async(
    q_ref, k_ref, v_ref,
    out_ref, stat_ref,
    acc_ref, den_ref, msc_ref,
    *, phi, scale, block_q, block_k, causal, window, delta,
):
    ki = pl.program_id(3)
    n_k = pl.num_programs(3)
    qi = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        den_ref[...] = jnp.zeros_like(den_ref)
        msc_ref[0, 0] = -jnp.inf

    q = q_ref[0, 0].astype(jnp.float32) * scale           # (BQ, D)
    k = k_ref[0, 0].astype(jnp.float32)                   # (BK, D)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                                     # (BQ, BK)
    valid = _mask(block_q, block_k, qi, ki, delta, causal, window)
    centered = s - phi
    msc_ref[0, 0] = jnp.maximum(
        msc_ref[0, 0], jnp.max(jnp.where(valid, centered, -jnp.inf))
    )
    e = jnp.where(valid, jnp.exp(centered), 0.0)
    v = v_ref[0, 0].astype(jnp.float32)
    acc_ref[...] += jax.lax.dot_general(
        e, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    den_ref[...] += jnp.broadcast_to(
        jnp.sum(e, axis=1, keepdims=True), den_ref.shape
    )

    @pl.when(ki == n_k - 1)
    def _fin():
        out_ref[0, 0] = (acc_ref[...] / den_ref[:, :1]).astype(out_ref.dtype)
        stat_ref[0, 0, 0] = jnp.full(stat_ref.shape[3:], msc_ref[0, 0],
                                     stat_ref.dtype)


def _prefill_kernel_sync(
    q_ref, k_ref, v_ref,
    out_ref,
    acc_ref, den_ref, m_ref,
    *, scale, block_q, block_k, causal, window, delta,
):
    ki = pl.program_id(3)
    n_k = pl.num_programs(3)
    qi = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        den_ref[...] = jnp.zeros_like(den_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)

    q = q_ref[0, 0].astype(jnp.float32) * scale
    k = k_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    valid = _mask(block_q, block_k, qi, ki, delta, causal, window)
    s = jnp.where(valid, s, _NEG_INF)

    m_prev = m_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    rescale = jnp.exp(m_prev - m_new)
    e = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    v = v_ref[0, 0].astype(jnp.float32)
    acc_ref[...] = acc_ref[...] * rescale + jax.lax.dot_general(
        e, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    den_ref[...] = den_ref[...] * jnp.broadcast_to(rescale, den_ref.shape) + (
        jnp.broadcast_to(jnp.sum(e, axis=1, keepdims=True), den_ref.shape)
    )
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(ki == n_k - 1)
    def _fin():
        den = den_ref[:, :1]
        den = jnp.where(den == 0.0, 1.0, den)   # fully-masked rows -> 0 output
        out_ref[0, 0] = (acc_ref[...] / den).astype(out_ref.dtype)


def flash_prefill(
    q: jax.Array,   # (B, Sq, HQ, D)
    k: jax.Array,   # (B, Sk, HK, D)
    v: jax.Array,   # (B, Sk, HK, D)
    *,
    causal: bool = True,
    unified_max: bool = True,
    phi: float = 0.0,
    scale: float | None = None,
    sliding_window: int = 0,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
):
    """Fused prefill attention.

    Returns ``out`` (sync mode) or ``(out, stat)`` (unified-max mode) where
    ``stat: (B, HQ)`` is the max centered logit for the overflow fallback.
    Each (head, query block) writes its own lane-dense stat row, reduced
    over query blocks here.
    """
    b, sq, hq, d = q.shape
    _, sk, hk, _ = k.shape
    g = hq // hk
    scale = scale if scale is not None else d ** -0.5
    delta = sk - sq  # q positions offset when kv is longer (chunked prefill)

    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0, (sq, block_q, sk, block_k)

    # (B, S, H, D) -> (B, H, S, D) tiles
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    grid = (b, hq, sq // block_q, sk // block_k)
    q_spec = pl.BlockSpec(
        (1, 1, block_q, d), lambda b_, h_, q_, k_: (b_, h_, q_, 0)
    )
    kv_spec = pl.BlockSpec(
        (1, 1, block_k, d), lambda b_, h_, q_, k_: (b_, h_ // g, k_, 0)
    )
    out_spec = pl.BlockSpec(
        (1, 1, block_q, d), lambda b_, h_, q_, k_: (b_, h_, q_, 0)
    )
    common = dict(
        scale=scale, block_q=block_q, block_k=block_k,
        causal=causal, window=sliding_window, delta=delta,
    )
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
    )
    if unified_max:
        kernel = functools.partial(_prefill_kernel_async, phi=phi, **common)
        out, stat = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=[
                out_spec,
                pl.BlockSpec((1, 1, 1, 1, 128),
                             lambda b_, h_, q_, k_: (b_, h_, q_, 0, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
                jax.ShapeDtypeStruct((b, hq, sq // block_q, 1, 128),
                                     jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, d), jnp.float32),
                pltpu.VMEM((block_q, 128), jnp.float32),
                pltpu.SMEM((1, 1), jnp.float32),
            ],
            compiler_params=params,
            name="flash_prefill_unified_max",
            interpret=interpret,
        )(qt, kt, vt)
        return out.transpose(0, 2, 1, 3), stat[..., 0, 0].max(axis=2)

    kernel = functools.partial(_prefill_kernel_sync, **common)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        compiler_params=params,
        name="flash_prefill_sync",
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
