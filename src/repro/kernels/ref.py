"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth for kernel tests (``assert_allclose`` against the
``interpret=True`` kernel execution) and also serve as the XLA execution path
used by the dry-run (Pallas-for-TPU does not lower on the CPU backend).

Shapes follow the kernel conventions:
  * prefill attention:  q,k,v = (batch, seq, heads, head_dim)   (kv heads may differ)
  * decode attention:   q = (batch, q_heads, head_dim),
                        k,v = (batch, kv_len, kv_heads, head_dim)
  * flat gemm / gemv:   x = (M, K), w = (K, N)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# Softmax schemes (paper Fig. 4)
# ---------------------------------------------------------------------------


def softmax_ref(x: jax.Array, axis: int = -1) -> jax.Array:
    """Fig. 4(a): classic max-stabilized softmax."""
    m = jnp.max(x, axis=axis, keepdims=True)
    e = jnp.exp(x - m)
    return e / jnp.sum(e, axis=axis, keepdims=True)


def softmax_unified_max(x: jax.Array, phi: float, axis: int = -1) -> jax.Array:
    """Fig. 4(c): partial-softmax with a unified scaling constant ``phi``.

    Algebraically identical to :func:`softmax_ref` for any finite ``phi``
    (Eq. 3); numerically safe while ``x - phi`` stays inside the band.
    """
    e = jnp.exp(x - phi)
    return e / jnp.sum(e, axis=axis, keepdims=True)


# ---------------------------------------------------------------------------
# Attention oracles
# ---------------------------------------------------------------------------


def _repeat_kv(k: jax.Array, groups: int) -> jax.Array:
    """(B, S, KVH, D) -> (B, S, KVH*groups, D) by repeating each kv head."""
    if groups == 1:
        return k
    return jnp.repeat(k, groups, axis=2)


def attention_prefill_ref(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: float | None = None,
    sliding_window: int = 0,
) -> jax.Array:
    """Full (quadratic) softmax attention, fp32 internals."""
    b, sq, hq, d = q.shape
    _, sk, hk, _ = k.shape
    groups = hq // hk
    k = _repeat_kv(k, groups)
    v = _repeat_kv(v, groups)
    scale = scale if scale is not None else d ** -0.5
    qf = q.astype(jnp.float32) * scale
    s = jnp.einsum("bqhd,bkhd->bhqk", qf, k.astype(jnp.float32))
    if causal or sliding_window:
        qi = jnp.arange(sq)[:, None] + (sk - sq)
        ki = jnp.arange(sk)[None, :]
        mask = jnp.ones((sq, sk), dtype=bool)
        if causal:
            mask &= qi >= ki
        if sliding_window:
            mask &= qi - ki < sliding_window
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = softmax_ref(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return o.astype(q.dtype)


def attention_decode_ref(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    lengths: jax.Array,
    *,
    scale: float | None = None,
    shard=None,
) -> jax.Array:
    """One-new-token attention against a KV cache. Safe (max-stabilized).

    q: (B, HQ, D); k_cache/v_cache: (B, S, HK, D); lengths: (B,) valid KV len.
    ``shard``: optional role-based constraint fn — keeps the score tensor
    sequence-sharded (split-KV; the *synchronized* combine: the max and the
    (num, den) reductions are separate collectives, paper Eq. 2).
    """
    b, hq, d = q.shape
    _, s_max, hk, _ = k_cache.shape
    groups = hq // hk
    scale = scale if scale is not None else d ** -0.5
    # GQA via grouped einsum — never materializes a repeated (x groups)
    # copy of the KV cache, and reads it in its stored dtype (bf16); the
    # f32 upcast happens per-tile inside the dot (deepseek decode
    # hillclimb: 8x1.6 TB of repeat+convert traffic removed).
    qg = q.reshape(b, hk, groups, d)   # native dtype: no extra rounding
    s = jnp.einsum("bhgd,bkhd->bhgk", qg, k_cache,
                   preferred_element_type=jnp.float32) * scale
    if shard is not None:
        s = shard(s, "act_scores_decode")
    valid = jnp.arange(s_max)[None, None, None, :] < lengths[:, None, None,
                                                            None]
    s = jnp.where(valid, s, -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)          # cross-shard max
    if shard is not None:
        m = shard(m[..., 0], "act_decode_rep")[..., None]
    e = jnp.exp(s - m)
    den = jnp.sum(e, axis=-1)
    num = jnp.einsum("bhgk,bkhd->bhgd", e.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    if shard is not None:
        num = shard(num, "act_decode_rep")
        den = shard(den, "act_decode_rep")
    o = (num / den[..., None]).reshape(b, hq, d)
    return o.astype(q.dtype)


def attention_decode_unified_max_ref(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    lengths: jax.Array,
    *,
    phi: float,
    scale: float | None = None,
    shard=None,
) -> tuple[jax.Array, jax.Array]:
    """T1 oracle: async partial-softmax decode with unified max value.

    Returns ``(out, max_abs_centered)`` where the second value is
    ``max_i |s_i - phi|`` per batch row — the overflow statistic the kernel
    reports so the wrapper can trigger the paper's recomputation fallback.

    With ``shard`` the scores stay sequence-sharded and the only cross-shard
    traffic is the additive (num, den) reduction — the asynchronous combine
    of paper Eq. 4 (contrast the extra max collective in the sync scheme).
    """
    b, hq, d = q.shape
    _, s_max, hk, _ = k_cache.shape
    groups = hq // hk
    scale = scale if scale is not None else d ** -0.5
    # grouped GQA einsum straight off the stored-dtype cache (see
    # attention_decode_ref) — T1 needs no row max, so this is one pass:
    # exp(s - phi) -> (num, den), order-independent.
    qg = q.reshape(b, hk, groups, d)   # native dtype: no extra rounding
    s = jnp.einsum("bhgd,bkhd->bhgk", qg, k_cache,
                   preferred_element_type=jnp.float32) * scale
    if shard is not None:
        s = shard(s, "act_scores_decode")
    valid = jnp.arange(s_max)[None, None, None, :] < lengths[:, None, None,
                                                             None]
    centered = s - phi
    e = jnp.where(valid, jnp.exp(centered), 0.0)
    num = jnp.einsum("bhgk,bkhd->bhgd", e.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    den = jnp.sum(e, axis=-1)
    stat = jnp.max(jnp.where(valid, jnp.abs(centered), 0.0),
                   axis=(1, 2, 3))
    if shard is not None:
        num = shard(num, "act_decode_rep")
        den = shard(den, "act_decode_rep")
        stat = shard(stat, "act_decode_rep")
    out = (num / den[..., None]).reshape(b, hq, d).astype(q.dtype)
    return out, stat


# ---------------------------------------------------------------------------
# Paged (block-table) attention oracles
# ---------------------------------------------------------------------------


def dequantize_pool_ref(pool: jax.Array, scales: jax.Array) -> jax.Array:
    """f32 full-precision view of a quantized page pool (oracle path).

    pool: (NP, PS, HK, D) int8/fp8 codes; scales: (NP, HK) f32 steps.
    The expression is exactly the in-kernel dequant (``codes * step`` in
    f32, elementwise per (page, kv head)), so gathering before or after
    dequantization yields identical values — every XLA oracle below can
    therefore take the dequantized pool through its existing math and
    stay bitwise consistent across gather/grouped/fused disciplines.
    """
    return pool.astype(jnp.float32) * scales[:, None, :, None]


def gather_paged_kv(pool: jax.Array, block_tables: jax.Array) -> jax.Array:
    """Materialize the dense per-sequence view of a paged KV pool.

    pool: (num_pages, page_size, HK, D); block_tables: (B, NB) int32.
    Returns (B, NB * page_size, HK, D). Positions past a sequence's length
    read whatever the addressed pages hold — callers mask by ``lengths``.
    """
    b, nb = block_tables.shape
    ps = pool.shape[1]
    # unassigned table entries hold the OOB sentinel num_pages: clamp to a
    # real page — whatever it holds is masked off by the caller's lengths
    gathered = jnp.take(pool, block_tables.reshape(-1), axis=0, mode="clip")
    return gathered.reshape(b, nb * ps, *pool.shape[2:])


def attention_decode_paged_ref(
    q: jax.Array,             # (B, HQ, D)
    k_pool: jax.Array,        # (NP, PS, HK, D)
    v_pool: jax.Array,
    block_tables: jax.Array,  # (B, NB) int32
    lengths: jax.Array,       # (B,)
    *,
    scale: float | None = None,
    shard=None,
) -> jax.Array:
    """Safe (max-stabilized) decode attention over a block-paged cache.

    The XLA path gathers each sequence's pages into a dense view and reuses
    :func:`attention_decode_ref` — bitwise identical to the dense-cache path
    whenever ``NB * PS`` equals the dense ``max_seq`` (additions of masked
    exact zeros do not perturb the reduction).
    """
    k = gather_paged_kv(k_pool, block_tables)
    v = gather_paged_kv(v_pool, block_tables)
    return attention_decode_ref(q, k, v, lengths, scale=scale, shard=shard)


def attention_decode_paged_unified_max_ref(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_tables: jax.Array,
    lengths: jax.Array,
    *,
    phi: float,
    scale: float | None = None,
    shard=None,
) -> tuple[jax.Array, jax.Array]:
    """T1 (async partial-softmax) oracle over a block-paged cache."""
    k = gather_paged_kv(k_pool, block_tables)
    v = gather_paged_kv(v_pool, block_tables)
    return attention_decode_unified_max_ref(
        q, k, v, lengths, phi=phi, scale=scale, shard=shard)


# ---------------------------------------------------------------------------
# Grouped (prefix-shared) decode oracles
# ---------------------------------------------------------------------------


def gather_grouped_kv(pool: jax.Array, block_tables: jax.Array,
                      groups) -> jax.Array:
    """Dense per-sequence KV view reconstructed *through* the group plan.

    ``groups`` duck-types :class:`repro.kernels.group_attention.DecodeGroups`
    (``tables (NG, LP)``, ``gid (B,)``, ``prefix_len (B,)``). Each row's
    positions below its ``prefix_len`` are read via its *group's* block
    table; the rest via its own table — exactly the data sources of the
    two-stage grouped kernel. Because a grouped row's leading block-table
    entries ARE its group's pages (the group key is a leading run of the
    row's own shared pages), the result is elementwise bitwise-equal to
    ``gather_paged_kv(pool, block_tables)`` — while making the group
    operands load-bearing, which is what lets the grouped XLA path promise
    bit-identical outputs versus the ungrouped one.
    """
    b, nb = block_tables.shape
    ps = pool.shape[1]
    ng, lp = groups.tables.shape
    width = nb * ps
    tail = gather_paged_kv(pool, block_tables)          # (B, NB*PS, ...)
    gkv = gather_paged_kv(pool, groups.tables)          # (NG, LP*PS, ...)
    if lp * ps < width:
        pad = [(0, 0), (0, width - lp * ps)] + [(0, 0)] * (gkv.ndim - 2)
        gkv = jnp.pad(gkv, pad)
    else:
        gkv = gkv[:, :width]
    pref = jnp.take(gkv, jnp.clip(groups.gid, 0, ng - 1), axis=0)
    pos = jnp.arange(width)
    use_pref = (pos[None, :, None, None]
                < groups.prefix_len[:, None, None, None])
    return jnp.where(use_pref, pref, tail)


def attention_decode_grouped_ref(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_tables: jax.Array,
    lengths: jax.Array,
    groups,
    *,
    scale: float | None = None,
    shard=None,
) -> jax.Array:
    """Safe (max-stabilized) grouped decode oracle: the grouped gather
    feeds the identical dense ref, so grouped == ungrouped bitwise."""
    k = gather_grouped_kv(k_pool, block_tables, groups)
    v = gather_grouped_kv(v_pool, block_tables, groups)
    return attention_decode_ref(q, k, v, lengths, scale=scale, shard=shard)


def attention_decode_grouped_unified_max_ref(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_tables: jax.Array,
    lengths: jax.Array,
    groups,
    *,
    phi: float,
    scale: float | None = None,
    shard=None,
) -> tuple[jax.Array, jax.Array]:
    """T1 (async partial-softmax) grouped decode oracle."""
    k = gather_grouped_kv(k_pool, block_tables, groups)
    v = gather_grouped_kv(v_pool, block_tables, groups)
    return attention_decode_unified_max_ref(
        q, k, v, lengths, phi=phi, scale=scale, shard=shard)


# ---------------------------------------------------------------------------
# Chunk-append attention (chunked prefill)
# ---------------------------------------------------------------------------


def _chunk_attention(q, k_cache, v_cache, lengths, phi, scale):
    """Shared chunk-attention math. Returns (out, stat) where stat is the
    per-batch max |s - phi| over valid positions — the same two-sided T1
    overflow statistic as :func:`attention_decode_unified_max_ref` (the
    under-band side matters too: exp underflow of every valid logit makes
    den 0 -> NaN) — or zeros when ``phi`` is None (safe scheme)."""
    b, c, hq, d = q.shape
    _, s_max, hk, _ = k_cache.shape
    groups = hq // hk
    scale = scale if scale is not None else d ** -0.5
    qg = q.reshape(b, c, hk, groups, d)
    s = jnp.einsum("bchgd,bkhd->bhgck", qg, k_cache,
                   preferred_element_type=jnp.float32) * scale
    qpos = lengths[:, None] + jnp.arange(c)[None, :]        # (B, C)
    valid = (jnp.arange(s_max)[None, None, None, None, :]
             <= qpos[:, None, None, :, None])               # (B,1,1,C,S)
    if phi is not None:
        centered = s - phi
        e = jnp.where(valid, jnp.exp(centered), 0.0)
        stat = jnp.max(jnp.where(valid, jnp.abs(centered), 0.0),
                       axis=(1, 2, 3, 4))
    else:
        m = jnp.max(jnp.where(valid, s, -jnp.inf), axis=-1, keepdims=True)
        e = jnp.where(valid, jnp.exp(s - m), 0.0)
        stat = jnp.zeros((b,), jnp.float32)
    den = jnp.sum(e, axis=-1)                               # (B, HK, G, C)
    num = jnp.einsum("bhgck,bkhd->bchgd", e.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    den_q = den.transpose(0, 3, 1, 2)[..., None]            # (B, C, HK, G, 1)
    o = (num / den_q).reshape(b, c, hq, d)
    return o.astype(q.dtype), stat


def attention_chunk_ref(
    q: jax.Array,          # (B, C, HQ, D) — chunk of new tokens
    k_cache: jax.Array,    # (B, S, HK, D) — chunk already scattered in
    v_cache: jax.Array,
    lengths: jax.Array,    # (B,) lengths *before* this chunk
    *,
    phi: float | None = None,
    scale: float | None = None,
) -> jax.Array:
    """Chunked-prefill attention: C new tokens attend to prefix + chunk.

    Query i of row b sits at absolute position ``lengths[b] + i``; valid keys
    are cache positions ``<= lengths[b] + i`` (chunk-local causality — the
    chunk's own KV must already be scattered into the cache). Rows past a
    sequence's chunk length produce garbage that callers drop. ``phi`` picks
    the T1 unified-max scheme; None is the safe per-row max.
    """
    out, _ = _chunk_attention(q, k_cache, v_cache, lengths, phi, scale)
    return out


def attention_chunk_unified_max_ref(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    lengths: jax.Array,
    *,
    phi: float,
    scale: float | None = None,
) -> tuple[jax.Array, jax.Array]:
    """T1 chunk-attention oracle returning ``(out, stat)`` — stat is the
    per-batch max centered logit for the overflow recompute fallback
    (chunk twin of :func:`attention_decode_unified_max_ref`)."""
    return _chunk_attention(q, k_cache, v_cache, lengths, phi, scale)


def attention_chunk_paged_ref(
    q: jax.Array,             # (B, C, HQ, D)
    k_pool: jax.Array,        # (NP, PS, HK, D)
    v_pool: jax.Array,
    block_tables: jax.Array,  # (B, NB)
    lengths: jax.Array,
    *,
    phi: float | None = None,
    scale: float | None = None,
) -> jax.Array:
    """Chunked-prefill attention over a block-paged cache (gather + ref).

    Bounded-table identity: trailing table columns whose pages carry only
    causally-masked positions contribute exact zeros to every (num, den)
    partial, so slicing them off (``block_tables[:, :bound]``) leaves the
    result bitwise unchanged — the engine's fused-mode resident bound
    rests on this (and the bit-identity tests enforce it).
    """
    k = gather_paged_kv(k_pool, block_tables)
    v = gather_paged_kv(v_pool, block_tables)
    return attention_chunk_ref(q, k, v, lengths, phi=phi, scale=scale)


def attention_chunk_paged_fused_ref(
    q: jax.Array,             # (B, C, HQ, D)
    k_pool: jax.Array,        # (NP, PS, HK, D)
    v_pool: jax.Array,
    block_tables: jax.Array,  # (B, NB)
    lengths: jax.Array,       # (B,) lengths *before* the chunk
    *,
    phi: float | None = None,
    scale: float | None = None,
    k_scale: jax.Array | None = None,  # (NP, HK) quantized-pool steps
    v_scale: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Page-blocked oracle for the fused chunk kernel
    (:mod:`repro.kernels.chunk_attention`): accumulates one order-
    independent ``(num, den)`` partial per page, mirroring the kernel's
    grid walk — the T1 unified-max scheme when ``phi`` is set, the
    two-pass safe scheme (global max first, then the page walk) when
    ``phi`` is None. Returns ``(out, stat)``; ``stat: (B, HK)`` is the max
    centered logit (zeros for the safe scheme). With ``k_scale`` /
    ``v_scale`` the pools hold quantized codes and each page dequantizes
    inside the walk — the oracle twin of the kernel's in-VMEM dequant.
    """
    b, c, hq, d = q.shape
    num_pages, ps, hk, _ = k_pool.shape
    nb = block_tables.shape[1]
    groups = hq // hk
    scale = scale if scale is not None else d ** -0.5
    bt = jnp.minimum(block_tables, num_pages - 1)
    qg = q.reshape(b, c, hk, groups, d).astype(jnp.float32) * scale

    def page(pool, steps, i):
        pg = jnp.take(pool, bt[:, i], axis=0).astype(jnp.float32)
        if steps is None:
            return pg                                       # (B, PS, HK, D)
        st = jnp.take(steps, bt[:, i], axis=0)              # (B, HK)
        return pg * st[:, None, :, None]

    qpos = lengths[:, None] + jnp.arange(c)[None, :]        # (B, C)
    num = jnp.zeros((b, c, hk, groups, d), jnp.float32)
    den = jnp.zeros((b, hk, groups, c), jnp.float32)
    stat = jnp.full((b, hk), -jnp.inf, jnp.float32)

    if phi is None:
        # safe scheme: one extra pass for the global row max
        m = jnp.full((b, hk, groups, c), -jnp.inf, jnp.float32)
        for i in range(nb):
            kpg = page(k_pool, k_scale, i)                  # (B, PS, HK, D)
            s = jnp.einsum("bchgd,bkhd->bhgck", qg, kpg)
            kpos = i * ps + jnp.arange(ps)
            valid = (kpos[None, None, None, None, :]
                     <= qpos[:, None, None, :, None])
            m = jnp.maximum(
                m, jnp.max(jnp.where(valid, s, -jnp.inf), axis=-1))
        center = m[..., None]
    else:
        center = phi

    for i in range(nb):
        kpg = page(k_pool, k_scale, i)                      # (B, PS, HK, D)
        vpg = page(v_pool, v_scale, i)
        s = jnp.einsum("bchgd,bkhd->bhgck", qg, kpg)
        kpos = i * ps + jnp.arange(ps)
        valid = (kpos[None, None, None, None, :]
                 <= qpos[:, None, None, :, None])           # (B,1,1,C,PS)
        centered = s - center
        e = jnp.where(valid, jnp.exp(centered), 0.0)
        num = num + jnp.einsum("bhgck,bkhd->bchgd", e,
                               vpg.astype(jnp.float32))
        den = den + jnp.sum(e, axis=-1)
        if phi is not None:
            stat = jnp.maximum(
                stat,
                jnp.max(jnp.where(valid, centered, -jnp.inf),
                        axis=(2, 3, 4)))
    den_q = den.transpose(0, 3, 1, 2)[..., None]            # (B, C, HK, G, 1)
    den_q = jnp.where(den_q == 0.0, 1.0, den_q)
    out = (num / den_q).reshape(b, c, hq, d).astype(q.dtype)
    if phi is None:
        stat = jnp.zeros((b, hk), jnp.float32)
    return out, stat


def attention_prefill_chunked(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: float | None = None,
    sliding_window: int = 0,
    phi: float | None = 0.0,
    block_q: int = 512,
) -> jax.Array:
    """Flash-style blockwise prefill attention on the XLA path.

    Never materializes the (B, H, S, S) score tensor: a python-unrolled loop
    over query blocks (flat HLO — exactly countable by ``cost_analysis``,
    and bounded live memory ≈ (B, H, block_q, S)). With ``phi`` set this is
    the T1 unified-max scheme — each block's (num, den) needs no running-max
    rescale; with ``phi=None`` it uses the per-block max (safe baseline).

    Used by the dry-run and any long-context XLA execution; the Pallas
    kernel covers real-TPU execution.
    """
    b, sq, hq, d = q.shape
    _, sk, hk, _ = k.shape
    groups = hq // hk
    scale = scale if scale is not None else d ** -0.5
    # grouped GQA einsums off the stored dtype — no repeated KV copy
    # (at 32k context the repeat costs `groups` x the KV bytes per layer)
    qf = q.reshape(b, sq, hk, groups, d)   # native dtype; scale on scores

    bq = min(block_q, sq)
    n_blocks = -(-sq // bq)
    ki = jnp.arange(sk)[None, :]
    outs = []
    for i in range(n_blocks):
        lo = i * bq
        cur = min(bq, sq - lo)
        qb = jax.lax.dynamic_slice_in_dim(qf, lo, cur, axis=1)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qb, k,
                       preferred_element_type=jnp.float32) * scale
        qi = (lo + jnp.arange(cur))[:, None] + (sk - sq)
        mask = jnp.ones((cur, sk), dtype=bool)
        if causal:
            mask &= qi >= ki
        if sliding_window:
            mask &= qi - ki < sliding_window
        mask4 = mask[None, None, None]
        if phi is not None:
            e = jnp.where(mask4, jnp.exp(s - phi), 0.0)
        else:
            m = jnp.max(jnp.where(mask4, s, -jnp.inf),
                        axis=-1, keepdims=True)
            e = jnp.where(mask4, jnp.exp(s - m), 0.0)
        den = jnp.sum(e, axis=-1)                      # (B, HK, G, cur)
        num = jnp.einsum("bhgqk,bkhd->bqhgd", e.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
        den_q = den.transpose(0, 3, 1, 2)[..., None]   # (B, cur, HK, G, 1)
        outs.append((num / den_q).reshape(b, cur, hq, d))
    o = jnp.concatenate(outs, axis=1) if len(outs) > 1 else outs[0]
    return o.astype(q.dtype)


# ---------------------------------------------------------------------------
# GEMM oracles
# ---------------------------------------------------------------------------


def flat_gemm_ref(x: jax.Array, w: jax.Array,
                  *, w_scale: jax.Array | None = None) -> jax.Array:
    """(M, K) @ (K, N), fp32 accumulation, result in x.dtype.

    ``w_scale`` ((N,) f32 per-output-channel steps, models/wquant.py)
    marks ``w`` as int8/fp8 codes: the dot runs on the codes cast to
    x.dtype (int8 ±127 and fp8 e4m3 values are exact in bf16) and the
    step multiplies the f32 accumulator — ``codes * step`` factored out
    of the K sum, the one dequant expression the kernel epilogues also
    use. ``w_scale=None`` is the unchanged full-precision expression
    (the bitwise contract)."""
    if w_scale is None:
        return jnp.dot(
            x, w, preferred_element_type=jnp.float32
        ).astype(x.dtype)
    acc = jnp.dot(
        x, w.astype(x.dtype), preferred_element_type=jnp.float32
    )
    return (acc * w_scale.astype(jnp.float32)[None, :]).astype(x.dtype)


def gemv_ref(x: jax.Array, w: jax.Array,
             *, w_scale: jax.Array | None = None) -> jax.Array:
    """Same math as flat_gemm_ref; kept separate as the ImplA oracle."""
    return flat_gemm_ref(x, w, w_scale=w_scale)


def fused_ffn_up_ref(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
                     *, activation: str = "swiglu",
                     wg_scale: jax.Array | None = None,
                     wu_scale: jax.Array | None = None) -> jax.Array:
    """Oracle for kernels/fused_ffn.py: act(x@w_gate) * (x@w_up), f32.

    Per-output-channel weight steps (``wg_scale``/``wu_scale``) apply on
    the f32 accumulators *before* the activation — the same order as the
    kernel epilogue, so the nonlinearity sees dequantized values."""
    g = jnp.dot(x, w_gate if wg_scale is None else w_gate.astype(x.dtype),
                preferred_element_type=jnp.float32)
    if wg_scale is not None:
        g = g * wg_scale.astype(jnp.float32)[None, :]
    u = jnp.dot(x, w_up if wu_scale is None else w_up.astype(x.dtype),
                preferred_element_type=jnp.float32)
    if wu_scale is not None:
        u = u * wu_scale.astype(jnp.float32)[None, :]
    act = jax.nn.silu(g) if activation == "swiglu" else jax.nn.gelu(g)
    return (act * u).astype(x.dtype)


# ---------------------------------------------------------------------------
# Decode-fusion stage oracles (kernels/decode_fuse.py)
# ---------------------------------------------------------------------------


def rmsnorm_ref(x: jax.Array, scale: jax.Array,
                eps: float) -> jax.Array:
    """Expression-for-expression copy of ``models.layers.rmsnorm`` (the
    kernels layer cannot import models); the fused-ingest oracle composes
    it so the XLA ``fused``/``looped`` granularities stay bit-identical
    to the split chain."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    return out.astype(x.dtype)


def rope_ref(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Expression-for-expression copy of ``models.layers.rope``."""
    d = x.shape[-1]
    half = d // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freq
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


def decode_ingest_ref(
    x: jax.Array,             # (B, 1, D) residual-stream input
    norm_scale: jax.Array,    # (D,)
    wq: jax.Array,            # (D, HQ*Dh)
    wk: jax.Array,            # (D, HK*Dh)
    wv: jax.Array,
    positions: jax.Array,     # (B,) int32 absolute positions
    *,
    num_heads: int,
    num_kv_heads: int,
    head_dim: int,
    rope_theta: float = 1e4,
    eps: float,
    use_rope: bool = True,
    bq: jax.Array | None = None,
    bk: jax.Array | None = None,
    bv: jax.Array | None = None,
    wq_scale: jax.Array | None = None,
    wk_scale: jax.Array | None = None,
    wv_scale: jax.Array | None = None,
):
    """Oracle for the fused decode-ingest stage: rmsnorm → QKV → bias →
    rope in one seam. Composes exactly the split chain's expressions in
    the same order (norm, three f32-accumulated GEMMs, bias add, head
    reshape, rope on q/k), so on the XLA backend the fused granularities
    are bitwise equal to split. Returns q (B,1,HQ,Dh), k/v (B,1,HK,Dh).
    Weight steps (``w*_scale``) dequantize on the f32 accumulators before
    the bias add, matching the kernel epilogue order.
    """
    b, s, d = x.shape
    h = rmsnorm_ref(x, norm_scale, eps)
    h2 = h.reshape(b * s, d)
    q = flat_gemm_ref(h2, wq, w_scale=wq_scale).reshape(b, s, wq.shape[-1])
    k = flat_gemm_ref(h2, wk, w_scale=wk_scale).reshape(b, s, wk.shape[-1])
    v = flat_gemm_ref(h2, wv, w_scale=wv_scale).reshape(b, s, wv.shape[-1])
    if bq is not None:
        q, k, v = q + bq, k + bk, v + bv
    q = q.reshape(b, s, num_heads, head_dim)
    k = k.reshape(b, s, num_kv_heads, head_dim)
    v = v.reshape(b, s, num_kv_heads, head_dim)
    if use_rope:
        pos = positions[:, None]
        q = rope_ref(q, pos, rope_theta)
        k = rope_ref(k, pos, rope_theta)
    return q, k, v


def oproj_residual_ref(o: jax.Array, wo: jax.Array, resid: jax.Array,
                       *, w_scale: jax.Array | None = None) -> jax.Array:
    """Oracle for the fused attention epilogue: ``resid + o @ wo`` — the
    split chain's o_proj GEMM and residual add, same f32 accumulation and
    operand order. o: (B, 1, HQ*Dh); wo: (HQ*Dh, D); resid: (B, 1, D).
    ``w_scale`` dequantizes on the f32 accumulator before the residual
    add (kernel epilogue order)."""
    b, s, qd = o.shape
    out = flat_gemm_ref(
        o.reshape(b * s, qd), wo, w_scale=w_scale
    ).reshape(b, s, wo.shape[-1])
    return resid + out


def ffn_norm_ref(
    x: jax.Array,           # (B, 1, D) residual-stream input (un-normed)
    norm_scale: jax.Array,  # (D,)
    w_gate: jax.Array,      # (D, F)
    w_up: jax.Array,        # (D, F)
    *,
    activation: str = "swiglu",
    eps: float,
    fused: bool = True,
    wg_scale: jax.Array | None = None,
    wu_scale: jax.Array | None = None,
) -> jax.Array:
    """Oracle for the fused mlp-ingest stage: rmsnorm → gate/up GEMMs →
    act(g)*u. ``fused`` selects which split composition to mirror —
    the plan's ``fused_ffn`` knob decides whether the split chain runs
    ``fused_ffn_up_ref`` (f32 epilogue) or two dispatched GEMMs rounded
    to the activation dtype before the activation; the fused seam must
    compose the *same* expressions — with the same reshape placement,
    since the split/looped scan bodies must trace to identical jaxprs
    for XLA to round identically — to stay bitwise."""
    b, s, d = x.shape
    f = w_gate.shape[-1]
    h = rmsnorm_ref(x, norm_scale, eps)
    if fused:
        # mirror ops.fused_ffn: flatten, fused epilogue, reshape back
        return fused_ffn_up_ref(
            h.reshape(b * s, d), w_gate, w_up, activation=activation,
            wg_scale=wg_scale, wu_scale=wu_scale,
        ).reshape(b, s, f)
    # mirror the unfused mlp_block: each GEMM flattens and reshapes back
    # (ops.matmul's XLA path), activation applied on the 3-D tensors
    g = flat_gemm_ref(
        h.reshape(b * s, d), w_gate, w_scale=wg_scale).reshape(b, s, f)
    u = flat_gemm_ref(
        h.reshape(b * s, d), w_up, w_scale=wu_scale).reshape(b, s, f)
    act = jax.nn.silu(g) if activation == "swiglu" else jax.nn.gelu(g)
    return act * u
