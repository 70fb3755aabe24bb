"""T1 — Asynchronized-softmax decode attention (Pallas TPU).

The paper's §3 insight adapted to TPU: each KV chunk contributes
``num += exp(q·kᵀ − φ)·v`` and ``den += Σ exp(q·kᵀ − φ)`` with a *static*
scaling constant φ, so grid steps over the KV cache are order-independent —
no running-max carry, no rescale of the accumulator between chunks (the
"synchronized partial softmax update" that FlashAttention/FlashDecoding pay
for on every chunk).

The kernel additionally reports ``max(s − φ)`` per (batch, kv-head) block so
the wrapper can implement the paper's recomputation fallback: if any logit
left the safe band, the whole call is recomputed with the synchronized
(online-max) scheme.

Layout: caches are consumed as (batch, kv_head, seq, head_dim) so a KV chunk
is a contiguous (block_k, head_dim) VMEM tile; the grouped query heads that
share one KV head ride along as a (group, head_dim) tile, turning the GQA
decode attention into two small MXU matmuls per chunk:
(G,D)x(D,BK) and (G,BK)x(BK,D).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import merge

DEFAULT_BLOCK_K = 512


def _decode_kernel(
    # inputs
    q_ref,        # (1, 1, G, D)
    k_ref,        # (1, 1, BK, D)
    v_ref,        # (1, 1, BK, D)
    len_ref,      # (1, 1, 1) int32 in SMEM
    # outputs
    out_ref,      # (1, 1, G, D)
    stat_ref,     # (1, 1, 1, 128) f32 : max(s - phi) over valid positions
    # scratch
    acc_ref,      # (G, D) f32
    den_ref,      # (G, 128) f32
    msc_ref,      # (1, 1) f32  max centered score
    *,
    phi: float,
    scale: float,
    block_k: int,
    kv_len: int,
):
    s_idx = pl.program_id(2)
    n_s = pl.num_programs(2)

    @pl.when(s_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        den_ref[...] = jnp.zeros_like(den_ref)
        msc_ref[0, 0] = -jnp.inf

    q = q_ref[0, 0].astype(jnp.float32) * scale          # (G, D)
    k = k_ref[0, 0].astype(jnp.float32)                  # (BK, D)
    v = v_ref[0, 0].astype(jnp.float32)                  # (BK, D)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                    # (G, BK)

    length = len_ref[0, 0, 0]
    offs = s_idx * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    valid = offs < length

    acc, den, msc = merge.unified_accumulate(
        acc_ref[...], den_ref[...], msc_ref[0, 0], s - phi, v, valid
    )
    acc_ref[...] = acc
    den_ref[...] = den
    msc_ref[0, 0] = msc

    @pl.when(s_idx == n_s - 1)
    def _fin():
        out = merge.finalize(acc_ref[...], den_ref[...])
        out_ref[0, 0] = out.astype(out_ref.dtype)
        stat_ref[0, 0] = jnp.full(stat_ref.shape[2:], msc_ref[0, 0],
                                  stat_ref.dtype)


def decode_attention_unified_max(
    q: jax.Array,          # (B, HQ, D)
    k_cache: jax.Array,    # (B, HK, S, D)
    v_cache: jax.Array,    # (B, HK, S, D)
    lengths: jax.Array,    # (B,) int32
    *,
    phi: float = 0.0,
    scale: float | None = None,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Run the async-softmax decode kernel.

    Returns ``(out, stat)`` with ``out: (B, HQ, D)`` and
    ``stat: (B, HK)`` = max centered logit, for the overflow fallback.
    """
    b, hq, d = q.shape
    _, hk, s_max, _ = k_cache.shape
    g = hq // hk
    scale = scale if scale is not None else d ** -0.5

    block_k = min(block_k, s_max)
    if s_max % block_k:
        pad = block_k - s_max % block_k
        k_cache = jnp.pad(k_cache, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, 0), (0, pad), (0, 0)))
        s_max += pad

    qg = q.reshape(b, hk, g, d)
    lens = lengths.reshape(b, 1, 1).astype(jnp.int32)

    grid = (b, hk, s_max // block_k)
    kernel = functools.partial(
        _decode_kernel,
        phi=phi,
        scale=scale,
        block_k=block_k,
        kv_len=s_max,
    )
    out, stat = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, g, d), lambda b_, h_, s_: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, s_: (b_, h_, s_, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, s_: (b_, h_, s_, 0)),
            pl.BlockSpec(
                (1, 1, 1), lambda b_, h_, s_: (b_, 0, 0),
                memory_space=pltpu.SMEM,
            ),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, g, d), lambda b_, h_, s_: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, 1, 128),
                         lambda b_, h_, s_: (b_, h_, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hk, g, d), q.dtype),
            jax.ShapeDtypeStruct((b, hk, 1, 128), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((g, d), jnp.float32),
            pltpu.VMEM((g, 128), jnp.float32),
            pltpu.SMEM((1, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        name="decode_attention_unified_max",
        interpret=interpret,
    )(qg, k_cache, v_cache, lens)
    return out.reshape(b, hq, d), stat[..., 0, 0]


# ---------------------------------------------------------------------------
# Synchronized (online-max) fallback kernel — the paper's recomputation path.
# This is the FlashDecoding-style scheme of Fig. 4(b): every chunk updates the
# running max and rescales the accumulator. Used (a) as the overflow fallback
# and (b) as the "paper baseline" in benchmarks.
# ---------------------------------------------------------------------------


def _decode_kernel_sync(
    q_ref, k_ref, v_ref, len_ref,
    out_ref,
    acc_ref, den_ref, m_ref,
    *,
    scale: float,
    block_k: int,
):
    s_idx = pl.program_id(2)
    n_s = pl.num_programs(2)

    @pl.when(s_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        den_ref[...] = jnp.zeros_like(den_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)

    q = q_ref[0, 0].astype(jnp.float32) * scale
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    length = len_ref[0, 0, 0]
    offs = s_idx * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(offs < length, s, -jnp.inf)

    # ---- the synchronized partial-softmax update the paper removes ----
    acc, den, m_new = merge.sync_accumulate(
        acc_ref[...], den_ref[...], m_ref[:, :1], s, v
    )
    acc_ref[...] = acc
    den_ref[...] = den
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(s_idx == n_s - 1)
    def _fin():
        out = merge.finalize(acc_ref[...], den_ref[...])
        out_ref[0, 0] = out.astype(out_ref.dtype)


# ---------------------------------------------------------------------------
# Paged (block-table) variants — same math, KV gathered page-by-page.
#
# The KV pool is the storage layout of serving/blockpool.py:
# (num_pages, page_size, kv_heads, head_dim). The per-sequence block table
# rides in as a *scalar-prefetch* operand (PrefetchScalarGridSpec) so the
# BlockSpec index_map can translate logical block i of batch row b into the
# physical page bt[b, i] before the DMA issues. One grid step per (b, page)
# DMAs the whole (PS, HK, D) page once — every KV head rides along and the
# kernel loops over heads in VMEM (a (1, PS, 1, D) per-head block is not
# a legal TPU tile when HK is not a multiple of 8, and a head axis in the
# grid would DMA every page once per head). The grid spans the full table
# width (NB = ceil(max_seq/PS)) for every sequence; steps past a
# sequence's length hit clamped/sentinel table entries, their compute is
# skipped via pl.when, and their (repeated) page fetch is wasted DMA.
#
# Per-head partial stats leave the kernel as lane-dense (…, HK, 128) rows
# (the wrapper reads lane 0); the overflow stat's running max lives in an
# (HK,) SMEM scratch. Quantized pools pass their (NP, HK) f32 steps as
# (NP, 1, HK) so one page's row is a legal SMEM block.
# ---------------------------------------------------------------------------


def page_specs(ps: int, hk: int, d: int, page_index, *, quantized: bool):
    """BlockSpecs for one page of K and V (plus their per-head dequant
    steps when ``quantized``). ``page_index(*grid_and_prefetch)`` names the
    physical page a grid step reads."""
    page = pl.BlockSpec(
        (1, ps, hk, d), lambda *a: (page_index(*a), 0, 0, 0))
    specs = [page, page]
    if quantized:
        step = pl.BlockSpec((1, 1, hk), lambda *a: (page_index(*a), 0, 0),
                            memory_space=pltpu.SMEM)
        specs += [step, step]
    return specs


def scale_operands(k_scale: jax.Array, v_scale: jax.Array) -> list:
    """(NP, HK) dequant steps -> the (NP, 1, HK) f32 operands
    :func:`page_specs` blocks over."""
    return [s.astype(jnp.float32)[:, None, :] for s in (k_scale, v_scale)]


def page_kv(k_ref, v_ref, steps, h: int):
    """Head ``h``'s (PS, D) K and V tiles of the resident page, in f32 and
    dequantized in VMEM when ``steps`` holds the page's scale refs."""
    k = k_ref[0, :, h].astype(jnp.float32)
    v = v_ref[0, :, h].astype(jnp.float32)
    if steps:
        ks_ref, vs_ref = steps
        k = k * ks_ref[0, 0, h]
        v = v * vs_ref[0, 0, h]
    return k, v


def stat_rows(stat_ref, msc_ref, hk: int):
    """Write the per-head overflow stat as lane-dense rows."""
    for h in range(hk):
        stat_ref[0, h] = jnp.full(stat_ref.shape[-1:], msc_ref[h],
                                  stat_ref.dtype)


def _paged_decode_kernel(
    bt_ref,       # (B, NB) int32 scalar-prefetch (unused in body; index maps)
    len_ref,      # (B,) int32 scalar-prefetch
    q_ref,        # (1, HK, G, D)
    k_ref,        # (1, PS, HK, D) — physical page bt[b, i]
    v_ref,        # (1, PS, HK, D)
    *rest,        # [ks_ref, vs_ref,] out_ref, stat_ref, acc, den, msc
    phi: float,
    scale: float,
    page_size: int,
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

    @pl.when(i_idx * page_size < length)   # fully-masked pages: skip compute
    def _accum():
        for h in range(hk):
            q = q_ref[0, h].astype(jnp.float32) * scale  # (G, D)
            # codes -> values in VMEM: one fused multiply per tile; the
            # full-precision page never exists in HBM
            k, v = page_kv(k_ref, v_ref, steps, h)       # (PS, D)

            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )                                            # (G, PS)

            offs = i_idx * page_size + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            valid = offs < length

            acc, den, msc = merge.unified_accumulate(
                acc_ref[h], den_ref[h], msc_ref[h], s - phi, v, valid
            )
            acc_ref[h] = acc
            den_ref[h] = den
            msc_ref[h] = msc

    @pl.when(i_idx == n_i - 1)
    def _fin():
        for h in range(hk):
            out = merge.finalize(acc_ref[h], den_ref[h])
            out_ref[0, h] = out.astype(out_ref.dtype)
        stat_rows(stat_ref, msc_ref, hk)


def _paged_decode_call(kernel, q, k_pool, v_pool, block_tables, lengths,
                       k_scale, v_scale, *, name: str, unified: bool,
                       interpret: bool, **kernel_kw):
    """Shared pallas_call plumbing of the two paged decode schemes;
    ``name`` is the kernel's name in the compiled program."""
    b, hq, d = q.shape
    num_pages, ps, hk, _ = k_pool.shape
    nb = block_tables.shape[1]
    g = hq // hk
    quantized = k_scale is not None

    # unassigned table entries hold the OOB sentinel num_pages — clamp so
    # the page DMA stays in bounds (contents masked off by `lengths`)
    block_tables = jnp.minimum(block_tables, num_pages - 1)
    qg = q.reshape(b, hk, g, d)
    head_block = pl.BlockSpec((1, hk, g, d),
                              lambda b_, i_, bt, ln: (b_, 0, 0, 0))
    in_specs = [head_block] + page_specs(
        ps, hk, d, lambda b_, i_, bt, ln: bt[b_, i_], quantized=quantized)
    operands = [qg, k_pool, v_pool]
    if quantized:
        operands += scale_operands(k_scale, v_scale)
    out_specs = [head_block]
    out_shape = [jax.ShapeDtypeStruct((b, hk, g, d), q.dtype)]
    scratch = [pltpu.VMEM((hk, g, d), jnp.float32),
               pltpu.VMEM((hk, g, 128), jnp.float32)]
    if unified:
        out_specs.append(pl.BlockSpec((1, hk, 128),
                                      lambda b_, i_, bt, ln: (b_, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((b, hk, 128), jnp.float32))
        scratch.append(pltpu.SMEM((hk,), jnp.float32))
    else:
        scratch.append(pltpu.VMEM((hk, g, 128), jnp.float32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, nb),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    outs = pl.pallas_call(
        functools.partial(kernel, page_size=ps, quantized=quantized,
                          **kernel_kw),
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        name=name,
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
      *operands)
    out = outs[0].reshape(b, hq, d)
    if unified:
        return out, outs[1][..., 0]
    return out


def paged_decode_attention_unified_max(
    q: jax.Array,             # (B, HQ, D)
    k_pool: jax.Array,        # (NP, PS, HK, D)
    v_pool: jax.Array,
    block_tables: jax.Array,  # (B, NB) int32
    lengths: jax.Array,       # (B,) int32
    *,
    phi: float = 0.0,
    scale: float | None = None,
    k_scale: jax.Array | None = None,   # (NP, HK) f32 — quantized pools
    v_scale: jax.Array | None = None,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Async-softmax decode attention over a block-paged KV pool.

    Returns ``(out, stat)`` exactly like :func:`decode_attention_unified_max`;
    the block table is a scalar-prefetch operand so each grid step DMAs one
    physical page, all KV heads at once. With ``k_scale``/``v_scale`` the
    pools hold quantized codes; each page is dequantized in VMEM right
    after its DMA.
    """
    d = q.shape[-1]
    return _paged_decode_call(
        _paged_decode_kernel, q, k_pool, v_pool, block_tables, lengths,
        k_scale, v_scale, name="paged_decode_attention_unified_max",
        unified=True, interpret=interpret, phi=phi,
        scale=scale if scale is not None else d ** -0.5)


def _paged_decode_kernel_sync(
    bt_ref, len_ref,
    q_ref, k_ref, v_ref,
    *rest,
    scale: float,
    page_size: int,
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

    @pl.when(i_idx * page_size < length)   # fully-masked pages: skip compute
    def _accum():
        for h in range(hk):
            q = q_ref[0, h].astype(jnp.float32) * scale
            k, v = page_kv(k_ref, v_ref, steps, h)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32
            )
            offs = i_idx * page_size + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(offs < length, s, -jnp.inf)

            acc, den, m_new = merge.sync_accumulate(
                acc_ref[h], den_ref[h], m_ref[h][:, :1], s, v
            )
            acc_ref[h] = acc
            den_ref[h] = den
            m_ref[h] = jnp.broadcast_to(m_new, m_ref.shape[1:])

    @pl.when(i_idx == n_i - 1)
    def _fin():
        for h in range(hk):
            out = merge.finalize(acc_ref[h], den_ref[h])
            out_ref[0, h] = out.astype(out_ref.dtype)


def paged_decode_attention_sync(
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
    """Online-max (synchronized) paged decode attention — fallback path."""
    d = q.shape[-1]
    return _paged_decode_call(
        _paged_decode_kernel_sync, q, k_pool, v_pool, block_tables, lengths,
        k_scale, v_scale, name="paged_decode_attention_sync",
        unified=False, interpret=interpret,
        scale=scale if scale is not None else d ** -0.5)


def decode_attention_sync(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    lengths: jax.Array,
    *,
    scale: float | None = None,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> jax.Array:
    """Online-max (synchronized) decode attention — fallback / baseline."""
    b, hq, d = q.shape
    _, hk, s_max, _ = k_cache.shape
    g = hq // hk
    scale = scale if scale is not None else d ** -0.5

    block_k = min(block_k, s_max)
    if s_max % block_k:
        pad = block_k - s_max % block_k
        k_cache = jnp.pad(k_cache, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, 0), (0, pad), (0, 0)))
        s_max += pad

    qg = q.reshape(b, hk, g, d)
    lens = lengths.reshape(b, 1, 1).astype(jnp.int32)
    grid = (b, hk, s_max // block_k)
    kernel = functools.partial(_decode_kernel_sync, scale=scale, block_k=block_k)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, g, d), lambda b_, h_, s_: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, s_: (b_, h_, s_, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, s_: (b_, h_, s_, 0)),
            pl.BlockSpec(
                (1, 1, 1), lambda b_, h_, s_: (b_, 0, 0),
                memory_space=pltpu.SMEM,
            ),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d), lambda b_, h_, s_: (b_, h_, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hk, g, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, d), jnp.float32),
            pltpu.VMEM((g, 128), jnp.float32),
            pltpu.VMEM((g, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        name="decode_attention_sync",
        interpret=interpret,
    )(qg, k_cache, v_cache, lens)
    return out.reshape(b, hq, d)
