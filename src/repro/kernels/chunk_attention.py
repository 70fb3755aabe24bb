"""Fused paged chunk-prefill attention (Pallas TPU) — flash-style causal
attention for a ``(B, C)`` query chunk directly over the block pool.

This is the chunked-prefill twin of the paged decode kernels: the KV pool
``(num_pages, page_size, kv_heads, head_dim)`` is read **in place** through
a scalar-prefetched block table — no dense ``(B, NB*PS)`` gather is ever
materialized, which is what makes long-prompt admission bandwidth-bound
instead of gather-bound (the ROADMAP "chunk-attention kernel" item; the
fusion argument of Kernel Looping / Efficient Operation Fusion applied to
the admission path).

Two softmax schemes, mirroring :mod:`repro.kernels.decode_attention`:

  * ``paged_chunk_attention_unified_max`` — the paper's §3 asynchronized
    partial softmax with a static scaling constant φ: every page
    contributes an order-independent ``(num, den)`` partial (no running
    max, no accumulator rescale between pages), and the kernel reports
    ``max(s − φ)`` over valid positions so the wrapper can run the
    overflow-recompute fallback.
  * ``paged_chunk_attention_sync`` — the FlashAttention-style online-max
    scheme (Fig. 4(b)); the recompute target and paper baseline.

Layout: q ``(B, C, HQ, D)`` is regrouped to ``(B, HK, C·G, D)`` so the
grouped query heads of one KV head ride together. The grid steps over
``(b, page)``: each step DMAs one whole ``(PS, HK, D)`` page and runs two
MXU matmuls per KV head, ``(C·G, D) x (D, PS)`` and ``(C·G, PS) x (PS, D)``
(the page layout is shared with the paged decode kernels). Chunk-
local causality is masked in-kernel: query row ``r`` sits at absolute
position ``lengths[b] + r // G`` and sees keys at positions ``<=`` its
own (the chunk's KV must already be scattered into the pool, exactly the
:func:`repro.kernels.ref.attention_chunk_ref` contract). Pages wholly past
``lengths[b] + C`` are skipped via ``pl.when`` — with a resident-bounded
block table (see ``Engine._prefill_chunked``) the grid itself stays
O(resident pages). Rows past a sequence's ``chunk_lens`` produce garbage
that callers drop, same as the gather path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import merge
from repro.kernels.decode_attention import (page_kv, page_specs,
                                            scale_operands, stat_rows)


def _chunk_mask(cg: int, ps: int, groups: int, length, page_idx):
    """(C·G, PS) validity: key position <= query's absolute position."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (cg, ps), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (cg, ps), 1)
    q_pos = length + rows // groups          # lengths[b] + chunk offset
    k_pos = page_idx * ps + cols
    return k_pos <= q_pos


def _paged_chunk_kernel(
    bt_ref,       # (B, NB) int32 scalar-prefetch (consumed by index maps)
    len_ref,      # (B,) int32 scalar-prefetch — lengths *before* the chunk
    q_ref,        # (1, HK, C*G, D)
    k_ref,        # (1, PS, HK, D) — physical page bt[b, i]
    v_ref,        # (1, PS, HK, D)
    *rest,        # [ks_ref, vs_ref,] out_ref, stat_ref, acc, den, msc
    phi: float,
    scale: float,
    page_size: int,
    chunk: int,
    groups: int,
    quantized: bool = False,
):
    steps = rest[:2] if quantized else ()
    out_ref, stat_ref, acc_ref, den_ref, msc_ref = rest[len(steps):]
    hk = q_ref.shape[1]

    b_idx = pl.program_id(0)
    i_idx = pl.program_id(1)
    n_i = pl.num_programs(1)

    @pl.when(i_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        den_ref[...] = jnp.zeros_like(den_ref)
        for h in range(hk):
            msc_ref[h] = -jnp.inf

    length = len_ref[b_idx]

    # pages wholly past the chunk's last query position carry no valid key
    @pl.when(i_idx * page_size < length + chunk)
    def _accum():
        for h in range(hk):
            q = q_ref[0, h].astype(jnp.float32) * scale  # (CG, D)
            k, v = page_kv(k_ref, v_ref, steps, h)       # (PS, D)

            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )                                            # (CG, PS)
            valid = _chunk_mask(s.shape[0], page_size, groups, length, i_idx)

            acc, den, msc = merge.unified_accumulate(
                acc_ref[h], den_ref[h], msc_ref[h], s - phi, v, valid
            )
            acc_ref[h] = acc
            den_ref[h] = den
            msc_ref[h] = msc

    @pl.when(i_idx == n_i - 1)
    def _fin():
        for h in range(hk):
            # guard_zero: fully-masked rows -> 0 (callers drop them)
            out = merge.finalize(acc_ref[h], den_ref[h], guard_zero=True)
            out_ref[0, h] = out.astype(out_ref.dtype)
        stat_rows(stat_ref, msc_ref, hk)


def _paged_chunk_kernel_sync(
    bt_ref, len_ref,
    q_ref, k_ref, v_ref,
    *rest,
    scale: float,
    page_size: int,
    chunk: int,
    groups: int,
    quantized: bool = False,
):
    steps = rest[:2] if quantized else ()
    out_ref, acc_ref, den_ref, m_ref = rest[len(steps):]
    hk = q_ref.shape[1]

    b_idx = pl.program_id(0)
    i_idx = pl.program_id(1)
    n_i = pl.num_programs(1)

    @pl.when(i_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        den_ref[...] = jnp.zeros_like(den_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)

    length = len_ref[b_idx]

    @pl.when(i_idx * page_size < length + chunk)
    def _accum():
        for h in range(hk):
            q = q_ref[0, h].astype(jnp.float32) * scale
            k, v = page_kv(k_ref, v_ref, steps, h)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32
            )
            valid = _chunk_mask(s.shape[0], page_size, groups, length, i_idx)
            s = jnp.where(valid, s, -jnp.inf)

            # ---- the synchronized partial-softmax update T1 removes ----
            acc, den, m_new = merge.sync_accumulate(
                acc_ref[h], den_ref[h], m_ref[h][:, :1], s, v, valid=valid
            )
            acc_ref[h] = acc
            den_ref[h] = den
            m_ref[h] = jnp.broadcast_to(m_new, m_ref.shape[1:])

    @pl.when(i_idx == n_i - 1)
    def _fin():
        for h in range(hk):
            # guard_zero: fully-masked rows -> 0 (callers drop them)
            out = merge.finalize(acc_ref[h], den_ref[h], guard_zero=True)
            out_ref[0, h] = out.astype(out_ref.dtype)


def _regroup_q(q: jax.Array, hk: int):
    """(B, C, HQ, D) -> (B, HK, C*G, D): grouped heads of one KV head ride
    in one tile; row r of the tile is chunk position r // G."""
    b, c, hq, d = q.shape
    g = hq // hk
    return (q.reshape(b, c, hk, g, d)
             .transpose(0, 2, 1, 3, 4)
             .reshape(b, hk, c * g, d)), g


def _ungroup_out(out: jax.Array, c: int, g: int):
    """(B, HK, C*G, D) -> (B, C, HQ, D)."""
    b, hk, cg, d = out.shape
    return (out.reshape(b, hk, c, g, d)
               .transpose(0, 2, 1, 3, 4)
               .reshape(b, c, hk * g, d))


def _chunk_grid_spec(b, hk, nb, cg, d, ps, unified: bool,
                     quantized: bool = False):
    head_block = pl.BlockSpec((1, hk, cg, d),
                              lambda b_, i_, bt, ln: (b_, 0, 0, 0))
    in_specs = [head_block] + page_specs(
        ps, hk, d, lambda b_, i_, bt, ln: bt[b_, i_], quantized=quantized)
    scratch = [
        pltpu.VMEM((hk, cg, d), jnp.float32),
        pltpu.VMEM((hk, cg, 128), jnp.float32),
    ]
    if unified:
        out_specs = [
            head_block,
            pl.BlockSpec((1, hk, 128), lambda b_, i_, bt, ln: (b_, 0, 0)),
        ]
        scratch.append(pltpu.SMEM((hk,), jnp.float32))
    else:
        out_specs = head_block
        scratch.append(pltpu.VMEM((hk, cg, 128), jnp.float32))
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, nb),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )


def paged_chunk_attention_unified_max(
    q: jax.Array,             # (B, C, HQ, D) — a chunk of new tokens
    k_pool: jax.Array,        # (NP, PS, HK, D)
    v_pool: jax.Array,
    block_tables: jax.Array,  # (B, NB) int32
    lengths: jax.Array,       # (B,) int32 — lengths *before* the chunk
    *,
    phi: float = 0.0,
    scale: float | None = None,
    k_scale: jax.Array | None = None,   # (NP, HK) f32 — quantized pools
    v_scale: jax.Array | None = None,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """T1 fused chunk-prefill attention over the block pool.

    Returns ``(out, stat)`` with ``out: (B, C, HQ, D)`` and
    ``stat: (B, HK)`` = max centered logit over valid positions, for the
    overflow-recompute fallback. The chunk's own KV must already be
    scattered into the pool (same contract as
    :func:`repro.kernels.ref.attention_chunk_ref`). With ``k_scale``/
    ``v_scale`` the pools hold quantized codes, dequantized per page in
    VMEM right after the DMA.
    """
    b, c, hq, d = q.shape
    num_pages, ps, hk, _ = k_pool.shape
    nb = block_tables.shape[1]
    scale = scale if scale is not None else d ** -0.5
    quantized = k_scale is not None

    # unassigned table entries hold the OOB sentinel num_pages — clamp so
    # the page DMA stays in bounds (contents masked off causally / dropped
    # as garbage rows by the caller)
    block_tables = jnp.minimum(block_tables, num_pages - 1)
    qg, g = _regroup_q(q, hk)
    grid_spec = _chunk_grid_spec(b, hk, nb, c * g, d, ps, unified=True,
                                 quantized=quantized)
    operands = [qg, k_pool, v_pool]
    if quantized:
        operands += scale_operands(k_scale, v_scale)
    kernel = functools.partial(
        _paged_chunk_kernel, phi=phi, scale=scale, page_size=ps,
        chunk=c, groups=g, quantized=quantized)
    out, stat = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, hk, c * g, d), q.dtype),
            jax.ShapeDtypeStruct((b, hk, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        name="paged_chunk_attention_unified_max",
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
      *operands)
    return _ungroup_out(out, c, g), stat[..., 0]


def paged_chunk_attention_sync(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_tables: jax.Array,
    lengths: jax.Array,
    *,
    scale: float | None = None,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Online-max (synchronized) fused chunk attention — the overflow
    recompute target and paper baseline."""
    b, c, hq, d = q.shape
    num_pages, ps, hk, _ = k_pool.shape
    nb = block_tables.shape[1]
    scale = scale if scale is not None else d ** -0.5
    quantized = k_scale is not None

    block_tables = jnp.minimum(block_tables, num_pages - 1)
    qg, g = _regroup_q(q, hk)
    grid_spec = _chunk_grid_spec(b, hk, nb, c * g, d, ps, unified=False,
                                 quantized=quantized)
    operands = [qg, k_pool, v_pool]
    if quantized:
        operands += scale_operands(k_scale, v_scale)
    kernel = functools.partial(
        _paged_chunk_kernel_sync, scale=scale, page_size=ps,
        chunk=c, groups=g, quantized=quantized)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hk, c * g, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        name="paged_chunk_attention_sync",
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
      *operands)
    return _ungroup_out(out, c, g)
