"""Write-time quantization of block-paged KV — the pool-side half of the
kv_dtype subsystem (:mod:`repro.kernels.quant` holds the elementwise code
math; this module owns the page/step pool algebra).

Storage layout: alongside the code pools (NP, PS, HK, D) in the code
dtype, each of K and V carries a parallel f32 *step pool* (NP, HK) — one
symmetric scale per (page, kv head). Both ride in the cache pytree as
extra leaves (``k_scale`` / ``v_scale``), so every page-indexed bulk op
the engine already has (COW page copy, tier demotion gather, promotion
scatter) moves scales with slabs for free via tree mapping.

The scatter below is the quantized twin of the layers' bf16
``_paged_scatter_chunk``: appended tokens land as codes, and the step of
every touched page is the running amax/qmax over everything written to it
while live. Two properties make this deterministic and safe across page
reuse, chunk partitioning, and COW sharing:

  * **enters-at-zero reset** — a write that covers a page's position 0
    (i.e. the page's first token in this sequence) zeroes the page's step
    first. Fresh pages are always first written at their position 0, so a
    reused physical page can never inherit a stale step (or stale codes:
    the rescale ratio from a zero step launders them to zero codes).
  * **monotone rescale** — when a later write raises a page's amax, the
    page's existing codes are re-expressed under the new step
    (``rescale_codes``); a ratio of exactly 1 is a bitwise no-op, so
    pages whose amax didn't move are untouched.

Codes are therefore a pure function of (page content, write partition):
for page-aligned writes (prefill chunks with chunk % page_size == 0, and
every page written by exactly one chunk) the codes equal one-shot
quantization of the full page, making greedy decode bitwise identical
across {gather, fused, grouped} x {sharing on/off} x {tier round-trip}
at a fixed write history. Token-by-token decode appends may double-round
relative to a chunked replay of the same tokens — within the dtype
tolerance the plan's logits-closeness guard enforces.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import quant

# cache-pytree leaf names for the step pools (present iff quantized)
K_SCALE = "k_scale"
V_SCALE = "v_scale"


def cache_is_quantized(cache: dict) -> bool:
    return K_SCALE in cache


def scatter_chunk_quantized(codes, steps, new, block_tables, lengths,
                            chunk_lens, spec: quant.QuantSpec):
    """Append a (possibly ragged) token chunk into quantized page pools.

    codes:  (NP, PS, HK, D) code pool (one layer's K or V, or every
            layer's as one flat page axis, with tables translated to it)
    steps:  (NP, HK) f32 step pool, paged alike
    new:    (B, C, HK, D) full-precision values; row b contributes its
            first chunk_lens[b] tokens at positions lengths[b]..
    block_tables: (B, NB) logical->physical page map
    Returns (codes, steps) updated. Invalid/out-of-span lanes scatter to
    the sentinel index NP and drop, mirroring the bf16 scatter.
    """
    np_, ps = codes.shape[0], codes.shape[1]
    b, c = new.shape[:2]
    nb = block_tables.shape[1]

    pos = lengths[:, None] + jnp.arange(c)[None, :]            # (B, C)
    valid = jnp.arange(c)[None, :] < chunk_lens[:, None]
    page = jnp.clip(pos // ps, 0, nb - 1)
    phys = jnp.take_along_axis(block_tables, page, axis=1)
    phys = jnp.where(valid, phys, np_)

    # logical pages this write can touch: static span bound
    nspan = (c + ps - 2) // ps + 1
    span_log = (lengths // ps)[:, None] + jnp.arange(nspan)[None, :]
    end = lengths + chunk_lens
    touched = ((span_log * ps < end[:, None]) & (chunk_lens[:, None] > 0)
               & (span_log < nb))
    span_phys = jnp.take_along_axis(
        block_tables, jnp.clip(span_log, 0, nb - 1), axis=1)
    span_phys = jnp.where(touched, span_phys, np_)             # (B, nspan)
    span_safe = jnp.clip(span_phys, 0, np_ - 1)

    # 1) enters-at-zero reset: page's position 0 falls inside the write
    entered = (span_log * ps >= lengths[:, None]) & touched
    steps = steps.at[jnp.where(entered, span_phys, np_)].set(
        0.0, mode="drop")

    # 2) each touched page's step as its current codes were encoded
    old_step = steps[span_safe]                                # (B,S,HK)

    # 3) fold this chunk's per-token amax into the step pool (scatter-max
    # is order-free, so partitioning tokens across chunks can't change
    # the final step of a page)
    contrib = jnp.max(jnp.abs(new.astype(jnp.float32)), axis=-1) / spec.qmax
    contrib = jnp.where(valid[..., None], contrib, 0.0)        # (B,C,HK)
    steps = steps.at[phys].max(contrib, mode="drop")

    # 4) the settled step per touched page / per appended token
    new_step = steps[span_safe]                                # (B,S,HK)
    tok_step = steps[jnp.clip(phys, 0, np_ - 1)]               # (B,C,HK)

    # 5) re-express each touched page's existing codes under its new step
    # (ratio 1 -> bitwise no-op; old_step 0 -> stale codes launder to 0).
    # Pages are addressed row by row, (page, offset), like the token
    # write below, so both keep the pool in its own layout
    rows = jnp.arange(ps)[None, None, :]
    old_codes = codes[span_safe[..., None], rows]      # (B,S,PS,HK,D)
    requant = quant.rescale_codes(
        old_codes, old_step[:, :, None, :], new_step[:, :, None, :], spec)

    # 6) write rescaled pages back, then 7) the new tokens on top
    codes = codes.at[span_phys[..., None], rows].set(requant, mode="drop")
    codes = codes.at[phys, pos % ps].set(
        quant.encode(new, tok_step, spec), mode="drop")
    return codes, steps


# ---------------------------------------------------------------------------
# Whole-page helpers (tests, oracles)
# ---------------------------------------------------------------------------


def quantize_pages(x, spec: quant.QuantSpec):
    """One-shot quantization of full page slabs.

    x: (..., PS, HK, D) -> (codes same shape in code dtype, steps (..., HK)).
    Matches what the scatter above produces for a page written in a single
    page-aligned chunk.
    """
    step = quant.compute_step(x, spec, axes=(-3, -1))
    return quant.encode(x, step[..., None, :], spec), step


def dequantize_pages(codes, steps):
    """f32 view of quantized page slabs: codes (..., PS, HK, D) * steps."""
    return quant.decode(codes, steps[..., None, :])
