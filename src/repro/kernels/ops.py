"""Jit'd public wrappers around the Pallas kernels, dispatched by plan.

``matmul`` is the single GEMM entry point used by the model zoo: it routes
a (M, K) × (K, N) workload to ImplA/ImplB/ImplC per the plan's tuned
[K, N] inflection entries (or an explicit ``impl=``). The attention front
doors wrap the fused kernels with the T1 overflow fallback.

Every wrapper takes exactly one ``plan=`` operand — an
:class:`~repro.core.plan.ExecutionPlan` (``None`` = the untuned
``DEFAULT_PLAN``) deciding backend (``"pallas"`` kernels vs. the XLA
reference math in ``ref.py``), softmax scheme, decode ``block_k``, the
chunked prefill threshold, and whether the ``lax.cond`` overflow-recompute
branch is emitted. Plans choose *which* implementation runs, never the
math.

On a TPU the Pallas backend lowers through Mosaic, sized for the chip's
:class:`~repro.hardware.HardwareSpec`. On the CPU the same kernels run in
interpret mode against the v5e spec, which is how the tests check them;
:func:`kernel_target` is the one place that decides which.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro import hardware
from repro.config import SoftmaxPhiConfig
from repro.core.dispatch import Impl
from repro.core.plan import DEFAULT_PLAN, ExecutionPlan
from repro.kernels import ref
from repro.kernels.chunk_attention import (
    paged_chunk_attention_sync,
    paged_chunk_attention_unified_max,
)
from repro.kernels.decode_attention import (
    decode_attention_sync,
    decode_attention_unified_max,
    paged_decode_attention_sync,
    paged_decode_attention_unified_max,
)
from repro.kernels.flat_gemm import flat_gemm
from repro.kernels.flash_prefill import flash_prefill
from repro.kernels.gemv import gemv
from repro.kernels.group_attention import (
    grouped_paged_decode_attention_unified_max,
)


def kernel_target() -> tuple[bool, hardware.HardwareSpec]:
    """``(interpret, spec)`` for the device the kernels run on, read when
    a wrapper traces. Interpret mode is for the CPU alone, which emulates
    the v5e; any other device must be in the hardware table, so an unknown
    accelerator is an error rather than a silently interpreted run."""
    device = jax.devices()[0]
    if device.platform == "cpu":
        return True, hardware.TPU_V5E
    return False, hardware.for_device(device)


def _unified(phi_cfg: SoftmaxPhiConfig, scheme: str) -> bool:
    """T1 unified-max runs only when the model has a calibrated φ *and*
    the plan asks for it; either veto falls back to the sync scheme."""
    return phi_cfg.active and scheme == "unified_max"


def _wparts(w):
    """Split a GEMM weight operand into ``(array, per-output-channel
    scale-or-None)``. Quantized weights arrive as the ``{"codes",
    "scale"}`` dict the engine's quantize-at-load pass produces
    (models/wquant.py); full-precision weights are plain arrays. The
    dict form is the single structural signal that threads dequant
    scales into the kernels — model call sites never change, and the
    plain-array path stays expression-identical (the bitwise bf16
    contract)."""
    if isinstance(w, dict):
        return w["codes"], w["scale"]
    return w, None


def at_layer(tree, layer):
    """Layer ``layer`` of stacked params, sliced by XLA, which fuses the
    slice into its consumer (``layer is None``: ``tree`` is one layer's
    params already).

    The paged layer scan hands each block the whole stacked params and
    its layer index: the GEMM seams pass their weight stacks and the
    index on to the kernels, which read the layer in place
    (:func:`~repro.kernels.flat_gemm.layer_stacks`); everything else —
    norm scales, biases, XLA-dot weights, the XLA oracles' weights — goes
    through here."""
    if layer is None:
        return tree
    return jax.tree.map(lambda a: a[layer], tree)


# ---------------------------------------------------------------------------
# GEMM front door (T3)
# ---------------------------------------------------------------------------


def matmul(
    x: jax.Array,
    w: jax.Array,
    *,
    plan: Optional[ExecutionPlan] = None,
    impl: Optional[Impl] = None,
) -> jax.Array:
    """Plan-dispatched GEMM. x: (..., K), w: (K, N) array or quantized
    ``{"codes", "scale"}`` leaf."""
    mp = (plan or DEFAULT_PLAN).matmul
    interpret, spec = kernel_target()
    w, w_scale = _wparts(w)
    lead = x.shape[:-1]
    k = x.shape[-1]
    n = w.shape[-1]
    m = 1
    for s in lead:
        m *= s
    x2 = x.reshape(m, k)

    if impl is None:
        impl = mp.pick(m, k, n)

    if mp.backend != "pallas" or impl is Impl.XLA_DOT:
        out = ref.flat_gemm_ref(x2, w, w_scale=w_scale)
    elif impl is Impl.GEMV:
        out = gemv(x2, w, w_scale=w_scale, spec=spec, interpret=interpret)
    else:
        out = flat_gemm(x2, w, w_scale=w_scale, spec=spec,
                        interpret=interpret)
    return out.reshape(*lead, n)


def fused_ffn(
    x: jax.Array,        # (..., K)
    w_gate: jax.Array,   # (K, N)
    w_up: jax.Array,     # (K, N)
    *,
    activation: str = "swiglu",
    layer=None,
    plan: Optional[ExecutionPlan] = None,
) -> jax.Array:
    """act(x @ w_gate) * (x @ w_up) — the single fused epilogue kernel
    when the plan's ``fused_ffn`` entry says ``fused`` on the Pallas
    backend (kernels/fused_ffn.py), oracle math otherwise. With a
    ``layer`` the weights are ``(L, K, N)`` stacks, which the kernel
    reads in place."""
    fp = (plan or DEFAULT_PLAN).fused_ffn
    interpret, spec = kernel_target()
    w_gate, wg_scale = _wparts(w_gate)
    w_up, wu_scale = _wparts(w_up)
    lead = x.shape[:-1]
    k = x.shape[-1]
    n = w_gate.shape[-1]
    x2 = x.reshape(-1, k)
    if fp.fused and fp.backend == "pallas":
        from repro.kernels.fused_ffn import fused_ffn_up
        out = fused_ffn_up(x2, w_gate, w_up, activation=activation,
                           wg_scale=wg_scale, wu_scale=wu_scale,
                           layer=layer, spec=spec, interpret=interpret)
    else:
        w_gate, w_up, wg_scale, wu_scale = at_layer(
            (w_gate, w_up, wg_scale, wu_scale), layer)
        out = ref.fused_ffn_up_ref(x2, w_gate, w_up, activation=activation,
                                   wg_scale=wg_scale, wu_scale=wu_scale)
    return out.reshape(*lead, n)


def decode_ingest(
    x: jax.Array,             # (B, 1, D) residual-stream input
    norm_scale: jax.Array,    # (D,)
    wq: jax.Array,
    wk: jax.Array,
    wv: jax.Array,
    positions: jax.Array,     # (B,) int32
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
    layer=None,
    plan: Optional[ExecutionPlan] = None,
):
    """Fused decode-ingest stage: rmsnorm → QKV → bias → rope in one
    seam (kernels/decode_fuse.py on the Pallas backend, the bit-exact
    op-chain composition in ``ref.py`` otherwise; a GEMM seam, so it
    routes on the plan's matmul backend). With a ``layer`` the three
    weights are ``(L, D, N)`` stacks, which the kernel reads in place;
    the norm scale and biases are that layer's. Returns q (B,1,HQ,Dh),
    k/v (B,1,HK,Dh)."""
    mp = (plan or DEFAULT_PLAN).matmul
    interpret, spec = kernel_target()
    wq, wq_scale = _wparts(wq)
    wk, wk_scale = _wparts(wk)
    wv, wv_scale = _wparts(wv)
    if mp.backend == "pallas":
        from repro.kernels.decode_fuse import decode_ingest_fused
        b, s, d = x.shape
        q, k, v = decode_ingest_fused(
            x.reshape(b * s, d), norm_scale, wq, wk, wv, positions,
            num_heads=num_heads, num_kv_heads=num_kv_heads,
            head_dim=head_dim, rope_theta=rope_theta, eps=eps,
            use_rope=use_rope, bq=bq, bk_bias=bk, bv=bv,
            wq_scale=wq_scale, wk_scale=wk_scale, wv_scale=wv_scale,
            layer=layer, spec=spec, interpret=interpret,
        )
        return (q.reshape(b, s, num_heads, head_dim),
                k.reshape(b, s, num_kv_heads, head_dim),
                v.reshape(b, s, num_kv_heads, head_dim))
    wq, wk, wv, wq_scale, wk_scale, wv_scale = at_layer(
        (wq, wk, wv, wq_scale, wk_scale, wv_scale), layer)
    return ref.decode_ingest_ref(
        x, norm_scale, wq, wk, wv, positions,
        num_heads=num_heads, num_kv_heads=num_kv_heads, head_dim=head_dim,
        rope_theta=rope_theta, eps=eps, use_rope=use_rope,
        bq=bq, bk=bk, bv=bv,
        wq_scale=wq_scale, wk_scale=wk_scale, wv_scale=wv_scale,
    )


def oproj_residual(
    o: jax.Array,       # (B, 1, HQ*Dh) attention outputs
    wo: jax.Array,      # (HQ*Dh, D)
    resid: jax.Array,   # (B, 1, D)
    *,
    layer=None,
    plan: Optional[ExecutionPlan] = None,
) -> jax.Array:
    """Fused attention epilogue ``resid + o @ wo`` (the o_proj GEMM with
    the residual add riding its epilogue on the Pallas backend; the
    bit-exact op-chain composition otherwise). With a ``layer``, ``wo``
    is an ``(L, Q, D)`` stack, which the kernel reads in place."""
    mp = (plan or DEFAULT_PLAN).matmul
    interpret, spec = kernel_target()
    wo, wo_scale = _wparts(wo)
    if mp.backend == "pallas":
        from repro.kernels.decode_fuse import oproj_residual_fused
        b, s, qd = o.shape
        out = oproj_residual_fused(
            o.reshape(b * s, qd), wo, resid.reshape(b * s, -1),
            w_scale=wo_scale, layer=layer, spec=spec, interpret=interpret,
        )
        return out.reshape(resid.shape)
    wo, wo_scale = at_layer((wo, wo_scale), layer)
    return ref.oproj_residual_ref(o, wo, resid, w_scale=wo_scale)


def ffn_norm(
    x: jax.Array,           # (B, 1, D) residual-stream input (un-normed)
    norm_scale: jax.Array,  # (D,)
    w_gate: jax.Array,      # (D, F)
    w_up: jax.Array,        # (D, F)
    *,
    activation: str = "swiglu",
    eps: float,
    layer=None,
    plan: Optional[ExecutionPlan] = None,
) -> jax.Array:
    """Fused mlp-ingest stage: rmsnorm → gate/up GEMMs → act(g)*u in one
    seam (kernels/decode_fuse.py on the Pallas backend; on XLA the
    oracle composes whichever op chain the plan's ``fused_ffn`` knob
    selects, so the seam stays bitwise). With a ``layer`` the weights
    are ``(L, D, F)`` stacks, which the kernel reads in place; the norm
    scale is that layer's. Returns (B, 1, F) — feed to
    :func:`oproj_residual` with ``w_down`` for the full seam."""
    p = plan or DEFAULT_PLAN
    interpret, spec = kernel_target()
    w_gate, wg_scale = _wparts(w_gate)
    w_up, wu_scale = _wparts(w_up)
    if p.matmul.backend == "pallas":
        from repro.kernels.decode_fuse import ffn_norm_fused
        b, s, d = x.shape
        out = ffn_norm_fused(
            x.reshape(b * s, d), norm_scale, w_gate, w_up,
            activation=activation, eps=eps,
            wg_scale=wg_scale, wu_scale=wu_scale, layer=layer, spec=spec,
            interpret=interpret,
        )
        return out.reshape(b, s, -1)
    w_gate, w_up, wg_scale, wu_scale = at_layer(
        (w_gate, w_up, wg_scale, wu_scale), layer)
    return ref.ffn_norm_ref(x, norm_scale, w_gate, w_up,
                            activation=activation, eps=eps,
                            fused=p.fused_ffn.fused,
                            wg_scale=wg_scale, wu_scale=wu_scale)


# ---------------------------------------------------------------------------
# Attention front doors (T1)
# ---------------------------------------------------------------------------


def attention_prefill(
    q: jax.Array,   # (B, Sq, HQ, D)
    k: jax.Array,   # (B, Sk, HK, D)
    v: jax.Array,
    *,
    phi_cfg: SoftmaxPhiConfig = SoftmaxPhiConfig(),
    causal: bool = True,
    sliding_window: int = 0,
    plan: Optional[ExecutionPlan] = None,
) -> jax.Array:
    """Prefill attention with T1 + overflow recomputation fallback.

    The plan's ``attention_prefill`` entry decides: the softmax scheme
    (``unified_max`` needs an active φ config), the chunking threshold —
    quadratic (B,H,S,S) scores are only materialized on the XLA path below
    it; above, the blockwise T1 scheme keeps live memory ≈ (B,H,block_q,S),
    mandatory for the 32k dry-run cells — and whether the ``lax.cond``
    recompute branch is emitted (``fallback=False`` is dry-run hygiene so
    cost_analysis doesn't double-count; the calibrated φ band makes the
    branch probability ≈ 0 — paper §3).
    """
    ap = (plan or DEFAULT_PLAN).attention_prefill
    interpret = kernel_target()[0]
    unified = _unified(phi_cfg, ap.scheme)
    if ap.backend != "pallas":
        if q.shape[1] * k.shape[1] >= ap.chunk_threshold ** 2:
            return ref.attention_prefill_chunked(
                q, k, v, causal=causal, sliding_window=sliding_window,
                phi=phi_cfg.phi if unified else None,
            )
        return ref.attention_prefill_ref(
            q, k, v, causal=causal, sliding_window=sliding_window
        )
    if not unified:
        return flash_prefill(
            q, k, v, causal=causal, unified_max=False,
            sliding_window=sliding_window, interpret=interpret,
        )
    out, stat = flash_prefill(
        q, k, v, causal=causal, unified_max=True, phi=phi_cfg.phi,
        sliding_window=sliding_window, interpret=interpret,
    )
    if not ap.fallback:
        return out
    overflow = jnp.any(stat > phi_cfg.band[1])

    def recompute(_):
        # paper §3 "Recomputation": rerun with the synchronized scheme
        return flash_prefill(
            q, k, v, causal=causal, unified_max=False,
            sliding_window=sliding_window, interpret=interpret,
        )

    return jax.lax.cond(overflow, recompute, lambda _: out, operand=None)


def attention_decode(
    q: jax.Array,        # (B, HQ, D) — one new token per sequence
    k_cache: jax.Array,  # (B, S, HK, D)
    v_cache: jax.Array,
    lengths: jax.Array,  # (B,)
    *,
    phi_cfg: SoftmaxPhiConfig = SoftmaxPhiConfig(),
    plan: Optional[ExecutionPlan] = None,
    shard=None,
) -> jax.Array:
    """Decode attention with T1 + overflow recomputation fallback.

    The plan's ``attention_decode`` entry decides scheme, the KV grid
    ``block_k``, and the recompute branch. ``shard`` (optional, a
    LayerCtx.shard) pins the split-KV dataflow on the XLA path: scores
    stay sequence-sharded and GSPMD combines the per-shard (num, den)
    partials with a single additive all-reduce — the pod-scale payoff of
    the unified-max softmax.
    """
    dp = (plan or DEFAULT_PLAN).attention_decode
    interpret = kernel_target()[0]
    unified = _unified(phi_cfg, dp.scheme)
    if dp.backend != "pallas":
        if not unified:
            return ref.attention_decode_ref(
                q, k_cache, v_cache, lengths, shard=shard)
        out, stat = ref.attention_decode_unified_max_ref(
            q, k_cache, v_cache, lengths, phi=phi_cfg.phi, shard=shard
        )
        if not dp.fallback:
            return out
        overflow = jnp.any(stat > phi_cfg.band[1])
        safe = functools.partial(
            ref.attention_decode_ref, q, k_cache, v_cache, lengths,
            shard=shard,
        )
        return jax.lax.cond(overflow, lambda _: safe(), lambda _: out, None)

    # kernel layout: (B, HK, S, D)
    kt = k_cache.transpose(0, 2, 1, 3)
    vt = v_cache.transpose(0, 2, 1, 3)
    if not unified:
        return decode_attention_sync(
            q, kt, vt, lengths, block_k=dp.block_k, interpret=interpret
        )
    out, stat = decode_attention_unified_max(
        q, kt, vt, lengths, phi=phi_cfg.phi, block_k=dp.block_k,
        interpret=interpret,
    )
    if not dp.fallback:
        return out
    overflow = jnp.any(stat > phi_cfg.band[1])

    def recompute(_):
        return decode_attention_sync(
            q, kt, vt, lengths, block_k=dp.block_k, interpret=interpret
        )

    return jax.lax.cond(overflow, recompute, lambda _: out, operand=None)


def attention_decode_paged(
    q: jax.Array,             # (B, HQ, D) — one new token per sequence
    k_pool: jax.Array,        # (NP, PS, HK, D) — shared block pool
    v_pool: jax.Array,
    block_tables: jax.Array,  # (B, NB) int32 — logical block -> physical page
    lengths: jax.Array,       # (B,)
    *,
    phi_cfg: SoftmaxPhiConfig = SoftmaxPhiConfig(),
    plan: Optional[ExecutionPlan] = None,
    shard=None,
    groups=None,
    k_scale: jax.Array | None = None,   # (NP, HK) f32 — quantized pools
    v_scale: jax.Array | None = None,
) -> jax.Array:
    """Decode attention over a block-paged KV cache (T1 + overflow fallback).

    Paged twin of :func:`attention_decode`, governed by the plan's
    ``paged`` entry: the KV cache is a flat page pool shared by all
    sequences and each sequence's pages are named by its block table. On
    the XLA backend the pages are gathered into a dense per-sequence view
    (bitwise identical to the dense path when NB*PS == max_seq); on the
    Pallas backend the block table is scalar-prefetched so the kernel
    DMAs exactly the pages each sequence owns.

    ``groups`` (a :class:`~repro.kernels.group_attention.DecodeGroups`)
    activates the prefix-shared grouped path: the shared-prefix pages are
    read once per group and merged with each request's private tail via
    the unified-max combine. On the XLA backend the dense view is
    reconstructed *through* the group plan
    (:func:`~repro.kernels.ref.gather_grouped_kv`) and fed to the
    identical ref math — grouped outputs are bitwise-equal to ungrouped by
    construction. On the Pallas backend the two-stage group kernel runs
    for the unified-max scheme (the sync scheme and the overflow
    recompute fall back to the ungrouped sync kernel).

    ``k_scale``/``v_scale`` mark the pools as quantized codes (the
    kv_dtype subsystem, :mod:`repro.serving.kvquant`). The XLA backend
    takes a pool-level f32 dequant view up front — gather commutes with
    the per-(page, head) scale multiply, so every ref below sees exactly
    the values the Pallas kernels reconstruct per page in VMEM.
    """
    pp = (plan or DEFAULT_PLAN).paged
    interpret = kernel_target()[0]
    unified = _unified(phi_cfg, pp.scheme)
    if pp.backend != "pallas":
        if k_scale is not None:
            k_pool = ref.dequantize_pool_ref(k_pool, k_scale)
            v_pool = ref.dequantize_pool_ref(v_pool, v_scale)
        if not unified:
            if groups is not None:
                return ref.attention_decode_grouped_ref(
                    q, k_pool, v_pool, block_tables, lengths, groups,
                    shard=shard)
            return ref.attention_decode_paged_ref(
                q, k_pool, v_pool, block_tables, lengths, shard=shard)
        if groups is not None:
            out, stat = ref.attention_decode_grouped_unified_max_ref(
                q, k_pool, v_pool, block_tables, lengths, groups,
                phi=phi_cfg.phi, shard=shard,
            )
            safe = functools.partial(
                ref.attention_decode_grouped_ref, q, k_pool, v_pool,
                block_tables, lengths, groups, shard=shard,
            )
        else:
            out, stat = ref.attention_decode_paged_unified_max_ref(
                q, k_pool, v_pool, block_tables, lengths, phi=phi_cfg.phi,
                shard=shard,
            )
            safe = functools.partial(
                ref.attention_decode_paged_ref, q, k_pool, v_pool,
                block_tables, lengths, shard=shard,
            )
        if not pp.fallback:
            return out
        overflow = jnp.any(stat > phi_cfg.band[1])
        return jax.lax.cond(overflow, lambda _: safe(), lambda _: out, None)

    if not unified:
        # grouped sync has no kernel — the ungrouped sync kernel is exact
        return paged_decode_attention_sync(
            q, k_pool, v_pool, block_tables, lengths,
            k_scale=k_scale, v_scale=v_scale, interpret=interpret
        )
    if groups is not None:
        out, stat = grouped_paged_decode_attention_unified_max(
            q, k_pool, v_pool, block_tables, lengths, groups,
            phi=phi_cfg.phi, k_scale=k_scale, v_scale=v_scale,
            interpret=interpret,
        )
    else:
        out, stat = paged_decode_attention_unified_max(
            q, k_pool, v_pool, block_tables, lengths, phi=phi_cfg.phi,
            k_scale=k_scale, v_scale=v_scale, interpret=interpret,
        )
    if not pp.fallback:
        return out
    overflow = jnp.any(stat > phi_cfg.band[1])

    def recompute(_):
        return paged_decode_attention_sync(
            q, k_pool, v_pool, block_tables, lengths,
            k_scale=k_scale, v_scale=v_scale, interpret=interpret
        )

    return jax.lax.cond(overflow, recompute, lambda _: out, operand=None)


def attention_chunk(
    q: jax.Array,        # (B, C, HQ, D) — a chunk of new tokens
    k_cache: jax.Array,  # (B, S, HK, D) — chunk KV already scattered in
    v_cache: jax.Array,
    lengths: jax.Array,  # (B,) lengths before the chunk
    *,
    phi_cfg: SoftmaxPhiConfig = SoftmaxPhiConfig(),
    plan: Optional[ExecutionPlan] = None,
) -> jax.Array:
    """Chunked-prefill attention: C tokens attend to prefix + chunk.

    The decode-shaped admission path: long prompts stream through this in
    fixed-size chunks instead of compiling one prefill per prompt bucket.
    Runs the ref math on both backends today (the chunk GEMMs are
    MXU-shaped already; a fused kernel is a ROADMAP follow-on), with the
    scheme and safe-softmax recompute fallback taken from the plan's
    ``attention_prefill`` entry (this is a prefill-phase op).
    """
    ap = (plan or DEFAULT_PLAN).attention_prefill
    if not _unified(phi_cfg, ap.scheme):
        return ref.attention_chunk_ref(q, k_cache, v_cache, lengths, phi=None)
    out, stat = ref.attention_chunk_unified_max_ref(
        q, k_cache, v_cache, lengths, phi=phi_cfg.phi)
    if not ap.fallback:
        return out
    overflow = jnp.any(stat > phi_cfg.band[1])
    safe = functools.partial(
        ref.attention_chunk_ref, q, k_cache, v_cache, lengths, phi=None)
    return jax.lax.cond(overflow, lambda _: safe(), lambda _: out, None)


def attention_chunk_paged(
    q: jax.Array,
    k_pool: jax.Array,        # (NP, PS, HK, D)
    v_pool: jax.Array,
    block_tables: jax.Array,  # (B, NB)
    lengths: jax.Array,
    *,
    phi_cfg: SoftmaxPhiConfig = SoftmaxPhiConfig(),
    plan: Optional[ExecutionPlan] = None,
    k_scale: jax.Array | None = None,   # (NP, HK) f32 — quantized pools
    v_scale: jax.Array | None = None,
) -> jax.Array:
    """Paged twin of :func:`attention_chunk`, governed by the plan's
    ``paged`` entry (scheme, fallback, and ``gather_chunk`` mode).

    ``gather_chunk="fused"`` on the Pallas backend runs the fused chunk
    kernel (:mod:`repro.kernels.chunk_attention`): K/V pages are read in
    place through scalar-prefetched block tables — no dense ``(B, NB*PS)``
    view is ever materialized — with the T1 unified-max scheme and the
    sync-kernel overflow recompute, exactly the decode kernel's contract.
    Quantized pools (``k_scale``/``v_scale``) dequantize per page in VMEM
    on this path; the gather path below takes the pool-level dequant view
    first (elementwise-identical, see :func:`attention_decode_paged`).

    Every other combination gathers the *caller-supplied* table into a
    dense view and reuses :func:`attention_chunk`: on the XLA backend the
    fused mode's win is realized upstream — ``Engine._prefill_chunked``
    bounds the table to O(resident pages), and because trailing masked
    pages contribute exact zeros, the bounded gather is bitwise identical
    to the full one (so greedy outputs match across modes by
    construction).
    """
    pp = (plan or DEFAULT_PLAN).paged
    interpret = kernel_target()[0]
    if pp.backend == "pallas" and pp.gather_chunk == "fused":
        unified = _unified(phi_cfg, pp.scheme)
        if not unified:
            return paged_chunk_attention_sync(
                q, k_pool, v_pool, block_tables, lengths,
                k_scale=k_scale, v_scale=v_scale, interpret=interpret)
        out, stat = paged_chunk_attention_unified_max(
            q, k_pool, v_pool, block_tables, lengths, phi=phi_cfg.phi,
            k_scale=k_scale, v_scale=v_scale, interpret=interpret)
        if not pp.fallback:
            return out
        overflow = jnp.any(stat > phi_cfg.band[1])

        def recompute(_):
            return paged_chunk_attention_sync(
                q, k_pool, v_pool, block_tables, lengths,
                k_scale=k_scale, v_scale=v_scale, interpret=interpret)

        return jax.lax.cond(overflow, recompute, lambda _: out, operand=None)

    if k_scale is not None:
        k_pool = ref.dequantize_pool_ref(k_pool, k_scale)
        v_pool = ref.dequantize_pool_ref(v_pool, v_scale)
    k = ref.gather_paged_kv(k_pool, block_tables)
    v = ref.gather_paged_kv(v_pool, block_tables)
    return attention_chunk(q, k, v, lengths, phi_cfg=phi_cfg, plan=plan)
