"""Grouped prefix-shared decode attention (Pallas TPU).

PR 4 deduplicated shared-prefix *storage* (refcounted COW pages); this
module deduplicates the decode-step *compute* over those pages. Requests
whose block tables begin with the same run of refcount>1 pages form a
group; the shared run is read once per group instead of once per
request:

  * **Stage 1** (:func:`_group_prefix_kernel`): grid ``(NG, LP)`` over
    the *group* block table (scalar-prefetched). Every member's grouped
    query heads ride in one ``(M·G, D)`` tile, so one pass over the prefix
    pages produces every member's partial — emitted raw as unified-max
    ``(num, den, stat)``, not normalized.
  * **Stage 2** (:func:`_tail_merge_kernel`): per-request grid over the
    full block table, skipping pages wholly inside the shared prefix. The
    scratch accumulators are *initialized from the stage-1 partials*, so
    the merge is the unified-max add itself — the paper's §3 asynchronized
    softmax with static φ makes the combine a plain ``(num, den)`` sum
    with no rescale (see :mod:`repro.kernels.merge`), which is exactly why
    two independently-produced partials can meet here without a
    synchronization pass.

Both stages report ``max(s − φ)`` so the wrapper keeps the overflow-
recompute fallback contract of the ungrouped kernels.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import merge
from repro.kernels.decode_attention import (page_kv, page_specs,
                                            scale_operands, stat_rows)


class DecodeGroups(NamedTuple):
    """Device operands of one tick's shared-prefix group plan.

    NG/LP/M are pow2-padded (group count / max prefix pages / max members)
    so tick-to-tick shape churn doesn't retrace; B is the slot count.
    Padding groups have ``n_pages == num_members == g_prefix_len == 0``;
    padded table entries and member rows hold out-of-bounds sentinels
    (consumers clamp). Solo rows have ``gid == NG`` and ``prefix_len == 0``.
    """

    tables: jax.Array        # (NG, LP) int32 physical pages of shared runs
    n_pages: jax.Array       # (NG,) int32 live pages per group
    g_prefix_len: jax.Array  # (NG,) int32 shared tokens per group
    num_members: jax.Array   # (NG,) int32
    member_rows: jax.Array   # (NG, M) int32 batch row of each member
    gid: jax.Array           # (B,) int32 group of each row (NG = solo)
    member: jax.Array        # (B,) int32 rank of the row within its group
    prefix_len: jax.Array    # (B,) int32 shared tokens of each row (0 = solo)


def _group_prefix_kernel(
    gt_ref,       # (NG, LP) int32 scalar-prefetch (consumed by index maps)
    plen_ref,     # (NG,) int32 scalar-prefetch — shared tokens per group
    nm_ref,       # (NG,) int32 scalar-prefetch — live members per group
    q_ref,        # (1, HK, M*G, D) — all members' grouped query heads
    k_ref,        # (1, PS, HK, D) — physical page gt[g, i]
    v_ref,        # (1, PS, HK, D)
    *rest,        # [ks_ref, vs_ref,] num, den, stat, acc, dacc, msc
    phi: float,
    scale: float,
    page_size: int,
    heads_per_kv: int,
    quantized: bool = False,
):
    steps = rest[:2] if quantized else ()
    num_ref, den_ref, stat_ref, acc_ref, dacc_ref, msc_ref = \
        rest[len(steps):]
    hk = q_ref.shape[1]

    g_idx = pl.program_id(0)
    i_idx = pl.program_id(1)
    n_i = pl.num_programs(1)

    @pl.when(i_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        dacc_ref[...] = jnp.zeros_like(dacc_ref)
        for h in range(hk):
            msc_ref[h] = -jnp.inf

    plen = plen_ref[g_idx]
    nm = nm_ref[g_idx]

    # pages past the shared run (incl. every page of padding groups): skip
    @pl.when(i_idx * page_size < plen)
    def _accum():
        for h in range(hk):
            q = q_ref[0, h].astype(jnp.float32) * scale  # (MG, D)
            k, v = page_kv(k_ref, v_ref, steps, h)       # (PS, D)

            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )                                            # (MG, PS)
            offs = i_idx * page_size + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            # padding member slots ride along with clamped (garbage) q
            # rows — keep them out of the group's shared stat so they can
            # never flip the overflow fallback
            row = jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0) // heads_per_kv
            valid = jnp.logical_and(offs < plen,    # partial last page
                                    row < nm)

            acc, den, msc = merge.unified_accumulate(
                acc_ref[h], dacc_ref[h], msc_ref[h], s - phi, v, valid
            )
            acc_ref[h] = acc
            dacc_ref[h] = den
            msc_ref[h] = msc

    @pl.when(i_idx == n_i - 1)
    def _fin():
        num_ref[0] = acc_ref[...]
        den_ref[0] = dacc_ref[...]
        stat_rows(stat_ref, msc_ref, hk)


def _tail_merge_kernel(
    bt_ref,       # (B, NB) int32 scalar-prefetch (consumed by index maps)
    len_ref,      # (B,) int32 scalar-prefetch
    plen_ref,     # (B,) int32 scalar-prefetch — per-row shared tokens
    q_ref,        # (1, HK, G, D)
    num_in_ref,   # (1, HK, G, D) f32 — stage-1 partial (zeros for solo rows)
    den_in_ref,   # (1, HK, G, 128) f32
    k_ref,        # (1, PS, HK, D)
    v_ref,        # (1, PS, HK, D)
    *rest,        # [ks_ref, vs_ref,] out, stat, acc, den, msc
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

    # the merge: seed the accumulators with the prefix partial — the
    # unified-max scheme needs no rescale to continue accumulating
    @pl.when(i_idx == 0)
    def _init():
        acc_ref[...] = num_in_ref[0]
        den_ref[...] = den_in_ref[0]
        for h in range(hk):
            msc_ref[h] = -jnp.inf

    length = len_ref[b_idx]
    plen = plen_ref[b_idx]

    # pages wholly inside the shared prefix (stage 1 covered them) or
    # wholly past the sequence carry no tail key
    @pl.when(jnp.logical_and((i_idx + 1) * page_size > plen,
                             i_idx * page_size < length))
    def _accum():
        for h in range(hk):
            q = q_ref[0, h].astype(jnp.float32) * scale  # (G, D)
            k, v = page_kv(k_ref, v_ref, steps, h)       # (PS, D)

            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )                                            # (G, PS)
            offs = i_idx * page_size + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            valid = jnp.logical_and(offs >= plen, offs < length)

            acc, den, msc = merge.unified_accumulate(
                acc_ref[h], den_ref[h], msc_ref[h], s - phi, v, valid
            )
            acc_ref[h] = acc
            den_ref[h] = den
            msc_ref[h] = msc

    @pl.when(i_idx == n_i - 1)
    def _fin():
        for h in range(hk):
            # guard_zero: empty batch slots (length 0, no carry) -> 0 rows
            out = merge.finalize(acc_ref[h], den_ref[h], guard_zero=True)
            out_ref[0, h] = out.astype(out_ref.dtype)
        stat_rows(stat_ref, msc_ref, hk)


def grouped_paged_decode_attention_unified_max(
    q: jax.Array,             # (B, HQ, D)
    k_pool: jax.Array,        # (NP, PS, HK, D)
    v_pool: jax.Array,
    block_tables: jax.Array,  # (B, NB) int32 — full per-request tables
    lengths: jax.Array,       # (B,) int32
    groups: DecodeGroups,
    *,
    phi: float = 0.0,
    scale: float | None = None,
    k_scale: jax.Array | None = None,   # (NP, HK) f32 — quantized pools
    v_scale: jax.Array | None = None,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Two-stage grouped decode attention over a block-paged KV pool.

    Returns ``(out, stat)`` exactly like
    :func:`~repro.kernels.decode_attention.paged_decode_attention_unified_max`
    — ``stat`` is the max over prefix *and* tail contributions, so the
    wrapper-level overflow fallback fires on the same condition as the
    ungrouped kernel. With ``k_scale``/``v_scale`` both stages dequantize
    each page in VMEM right after its DMA.
    """
    b, hq, d = q.shape
    num_pages, ps, hk, _ = k_pool.shape
    nb = block_tables.shape[1]
    g = hq // hk
    ng, lp = groups.tables.shape
    m = groups.member_rows.shape[1]
    mg = m * g
    scale = scale if scale is not None else d ** -0.5
    quantized = k_scale is not None
    scales = scale_operands(k_scale, v_scale) if quantized else []

    qg = q.reshape(b, hk, g, d)

    # ---- stage 1: shared-prefix partials, one pass per group
    gtables = jnp.minimum(groups.tables, num_pages - 1)
    rows = jnp.clip(groups.member_rows, 0, b - 1).reshape(-1)
    qs = (jnp.take(qg, rows, axis=0)
             .reshape(ng, m, hk, g, d)
             .transpose(0, 2, 1, 3, 4)
             .reshape(ng, hk, mg, d))

    s1_heads = pl.BlockSpec((1, hk, mg, d),
                            lambda g_, i_, gt, pn, nm: (g_, 0, 0, 0))
    s1_in = [s1_heads] + page_specs(
        ps, hk, d, lambda g_, i_, gt, pn, nm: gt[g_, i_], quantized=quantized)
    s1_operands = [qs, k_pool, v_pool] + scales

    s1_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(ng, lp),
        in_specs=s1_in,
        out_specs=[
            s1_heads,
            pl.BlockSpec((1, hk, mg, 128),
                         lambda g_, i_, gt, pn, nm: (g_, 0, 0, 0)),
            pl.BlockSpec((1, hk, 128), lambda g_, i_, gt, pn, nm: (g_, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((hk, mg, d), jnp.float32),
            pltpu.VMEM((hk, mg, 128), jnp.float32),
            pltpu.SMEM((hk,), jnp.float32),
        ],
    )
    s1_kernel = functools.partial(
        _group_prefix_kernel, phi=phi, scale=scale, page_size=ps,
        heads_per_kv=g, quantized=quantized)
    num, den, stat1 = pl.pallas_call(
        s1_kernel,
        grid_spec=s1_spec,
        out_shape=[
            jax.ShapeDtypeStruct((ng, hk, mg, d), jnp.float32),
            jax.ShapeDtypeStruct((ng, hk, mg, 128), jnp.float32),
            jax.ShapeDtypeStruct((ng, hk, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        name="grouped_decode_prefix",
        interpret=interpret,
    )(gtables.astype(jnp.int32), groups.g_prefix_len.astype(jnp.int32),
      groups.num_members.astype(jnp.int32), *s1_operands)

    # un-scatter each row's own partial; solo rows carry zeros (= empty)
    gid_c = jnp.clip(groups.gid, 0, ng - 1)
    mem_c = jnp.clip(groups.member, 0, m - 1)
    has_pref = groups.prefix_len > 0
    num_b = num.reshape(ng, hk, m, g, d)[gid_c, :, mem_c]       # (B,HK,G,D)
    den_b = den.reshape(ng, hk, m, g, 128)[gid_c, :, mem_c]     # (B,HK,G,128)
    stat_b = stat1[gid_c, :, 0]                                 # (B,HK)
    num_b = jnp.where(has_pref[:, None, None, None], num_b, 0.0)
    den_b = jnp.where(has_pref[:, None, None, None], den_b, 0.0)
    stat_b = jnp.where(has_pref[:, None], stat_b, -jnp.inf)

    # ---- stage 2: private tail, accumulating on top of the carry
    block_tables = jnp.minimum(block_tables, num_pages - 1)
    s2_heads = pl.BlockSpec((1, hk, g, d),
                            lambda b_, i_, bt, ln, pn: (b_, 0, 0, 0))
    s2_in = [
        s2_heads,
        s2_heads,
        pl.BlockSpec((1, hk, g, 128),
                     lambda b_, i_, bt, ln, pn: (b_, 0, 0, 0)),
    ] + page_specs(ps, hk, d, lambda b_, i_, bt, ln, pn: bt[b_, i_],
                   quantized=quantized)
    s2_operands = [qg, num_b, den_b, k_pool, v_pool] + scales
    s2_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, nb),
        in_specs=s2_in,
        out_specs=[
            s2_heads,
            pl.BlockSpec((1, hk, 128), lambda b_, i_, bt, ln, pn: (b_, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((hk, g, d), jnp.float32),
            pltpu.VMEM((hk, g, 128), jnp.float32),
            pltpu.SMEM((hk,), jnp.float32),
        ],
    )
    s2_kernel = functools.partial(
        _tail_merge_kernel, phi=phi, scale=scale, page_size=ps,
        quantized=quantized)
    out, stat2 = pl.pallas_call(
        s2_kernel,
        grid_spec=s2_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, hk, g, d), q.dtype),
            jax.ShapeDtypeStruct((b, hk, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        name="grouped_decode_tail",
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
      groups.prefix_len.astype(jnp.int32), *s2_operands)

    return out.reshape(b, hq, d), jnp.maximum(stat_b, stat2[..., 0])
