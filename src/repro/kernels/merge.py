"""The softmax-merge algebra — one implementation for every partial merge.

Every attention kernel in this repo splits the KV sequence into pieces
(grid steps over cache chunks, pool pages, or a shared-prefix/private-tail
pair) and combines per-piece partials. Two schemes exist:

  * **unified-max** (the paper's §3 asynchronized softmax): a partial is
    ``(num, den, msc)`` with ``num = Σ exp(s − φ)·v``, ``den = Σ exp(s − φ)``
    and ``msc = max(s − φ)`` over valid positions. φ is a *static* constant,
    so merging partials is pure addition (plus a max for the overflow stat)
    — commutative and associative, no rescale between pieces.
  * **online-max / LSE** (FlashAttention-style, the recompute fallback): a
    partial is ``(acc, den, m)`` stabilized by its own running max; merging
    rescales by ``exp(m − m_new)``.

The in-kernel accumulate steps (:func:`unified_accumulate`,
:func:`sync_accumulate`) are bitwise-identical to the bodies they were
extracted from — the Pallas kernels in ``decode_attention`` /
``chunk_attention`` / ``group_attention`` all call them, so the property
suite in ``tests/test_merge_properties.py`` exercises the exact fp op
sequence every kernel runs. The symmetric two-partial merges
(:func:`merge_unified`, :func:`merge_lse`) are the algebra those tests
check for split-point equivalence and order invariance.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _weighted_sum(e: jax.Array, v: jax.Array) -> jax.Array:
    """(R, K) exp-weights x (K, D) values -> (R, D), f32 on the MXU."""
    return jax.lax.dot_general(
        e, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


# ---------------------------------------------------------------------------
# Unified-max (asynchronized) scheme
# ---------------------------------------------------------------------------


def unified_accumulate(acc, den, msc, centered, v, valid):
    """Fold one KV piece into a unified-max partial.

    acc: (R, D) f32 running numerator; den: (R, *) f32 running denominator
    (lane-broadcast); msc: scalar f32 running max centered score;
    centered: (R, K) f32 logits already shifted by φ; v: (K, D);
    valid: (R, K) bool. Returns the updated ``(acc, den, msc)``.
    """
    msc = jnp.maximum(msc, jnp.max(jnp.where(valid, centered, -jnp.inf)))
    e = jnp.where(valid, jnp.exp(centered), 0.0)
    acc = acc + _weighted_sum(e, v)
    den = den + jnp.broadcast_to(
        jnp.sum(e, axis=1, keepdims=True), den.shape
    )
    return acc, den, msc


def merge_unified(p1, p2):
    """Symmetric merge of two unified-max partials ``(num, den, msc)``."""
    n1, d1, m1 = p1
    n2, d2, m2 = p2
    return n1 + n2, d1 + d2, jnp.maximum(m1, m2)


# ---------------------------------------------------------------------------
# Online-max (synchronized / LSE) scheme
# ---------------------------------------------------------------------------


def sync_accumulate(acc, den, m_prev, s, v, *, valid=None):
    """Fold one KV piece into an online-max partial.

    acc: (R, D) f32; den: (R, *) f32; m_prev: (R, 1) f32 running max;
    s: (R, K) f32 logits with invalid positions already at ``-inf``;
    ``valid`` is passed by kernels that additionally zero the exp weights
    (the chunk kernels) and omitted by those that rely on the ``-inf``
    masking alone (the decode kernels) — the two differ bitwise only on
    fully-masked rows. Returns ``(acc, den, m_new)`` with m_new (R, 1).
    A row with no valid position yet keeps ``m_new == -inf``; its rescale
    is pinned to 1 (``exp(-inf - -inf)`` would be NaN, and NaN·0 would
    poison every later merge of the still-empty partial).
    """
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    rescale = jnp.exp(jnp.where(m_new == -jnp.inf, 0.0, m_prev - m_new))
    if valid is None:
        e = jnp.exp(s - m_new)
    else:
        e = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    acc = acc * rescale + _weighted_sum(e, v)
    den = den * jnp.broadcast_to(rescale, den.shape) + jnp.broadcast_to(
        jnp.sum(e, axis=1, keepdims=True), den.shape
    )
    return acc, den, m_new


def merge_lse(p1, p2):
    """Symmetric merge of two max-stabilized partials ``(acc, den, m)``."""
    a1, d1, m1 = p1
    a2, d2, m2 = p2
    m = jnp.maximum(m1, m2)
    r1 = jnp.exp(m1 - m)
    r2 = jnp.exp(m2 - m)
    return a1 * r1 + a2 * r2, d1 * r1 + d2 * r2, m


# ---------------------------------------------------------------------------
# Finalize
# ---------------------------------------------------------------------------


def finalize(acc, den, *, guard_zero: bool = False):
    """num/den -> output rows. ``guard_zero`` substitutes 1 for an all-
    masked row's zero denominator (chunk/group kernels, whose callers drop
    those garbage rows); the plain decode kernels divide unguarded."""
    d = den[:, :1]
    if guard_zero:
        d = jnp.where(d == 0.0, 1.0, d)
    return acc / d
