"""Property harness for the softmax-merge algebra (repro.kernels.merge).

Every attention kernel in the tree — decode, chunked prefill, grouped
prefix-shared decode — splits the KV sequence and combines partials
through the helpers in :mod:`repro.kernels.merge`. These properties pin
the algebra those kernels rely on:

  * **split equivalence** — folding any 2-way split of the KV axis and
    merging equals the unsplit softmax-attention, for both the
    unified-max (φ) scheme and the online-max / LSE scheme;
  * **order invariance** — merging 3+ unified-max partials is
    permutation- and association-insensitive (the paper's §3 claim: with
    a static φ the combine is pure addition);
  * **overflow detection** — whenever ``max(s − φ)`` leaves the φ band,
    the unified-max stat reports it (``msc`` is exact), so the wrapper's
    ``lax.cond`` recompute can never miss an overflow; inside the band
    the unified output itself matches the stable reference.
"""
import numpy as np
import pytest

from _hypothesis_compat import example, given, settings, st

from repro.config import SoftmaxPhiConfig
from repro.kernels import merge

settings.register_profile("fast", max_examples=20, deadline=None)
settings.load_profile("fast")

R, D = 4, 8      # rows x value dim — small, the algebra is dim-blind


def _case(seed, kv_len, spread=1.0):
    """Random (centered logits, values, validity) for one property draw."""
    rng = np.random.default_rng(seed)
    s = (rng.standard_normal((R, kv_len)) * spread).astype(np.float32)
    v = rng.standard_normal((kv_len, D)).astype(np.float32)
    # at least one valid position per row keeps the reference well-defined
    valid = rng.random((R, kv_len)) < 0.8
    valid[:, 0] = True
    return s, v, valid


def _softmax_attention(s, v, valid):
    """Unsplit stable reference in float64."""
    s = np.where(valid, s.astype(np.float64), -np.inf)
    m = s.max(axis=1, keepdims=True)
    e = np.exp(s - m)
    return (e @ v.astype(np.float64)) / e.sum(axis=1, keepdims=True)


def _unified_partial(s, v, valid, phi):
    acc = np.zeros((R, D), np.float32)
    den = np.zeros((R, 1), np.float32)
    acc, den, msc = merge.unified_accumulate(
        acc, den, np.float32(-np.inf), s - phi, v, valid)
    return np.asarray(acc), np.asarray(den), np.asarray(msc)


@given(st.integers(0, 10_000), st.integers(1, 31))
def test_unified_split_equivalence(seed, split):
    """Unified-max: fold [0, t) and [t, S) separately, merge, finalize —
    equals the unsplit softmax-attention at any split point t."""
    s, v, valid = _case(seed, kv_len=32)
    phi = 0.0
    p1 = _unified_partial(s[:, :split], v[:split], valid[:, :split], phi)
    p2 = _unified_partial(s[:, split:], v[split:], valid[:, split:], phi)
    num, den, msc = merge.merge_unified(p1, p2)
    out = np.asarray(merge.finalize(num, den))
    np.testing.assert_allclose(
        out, _softmax_attention(s, v, valid), rtol=1e-4, atol=1e-5)
    assert np.asarray(msc) == np.where(valid, s, -np.inf).max() - phi


@given(st.integers(0, 10_000), st.integers(1, 15), st.integers(16, 31))
@example(seed=9, t1=1, t2=31)   # a row fully masked in [31, 32): NaN before
def test_sync_split_equivalence(seed, t1, t2):
    """Online-max/LSE: two independently max-stabilized partials merged
    via merge_lse equal the unsplit softmax-attention (any 3 segments:
    [0,t1) folded onto [t1,t2), then LSE-merged with [t2,S))."""
    s, v, valid = _case(seed, kv_len=32)
    sm = np.where(valid, s, -np.inf).astype(np.float32)

    def sync_fold(lo, hi):
        acc = np.zeros((R, D), np.float32)
        den = np.zeros((R, 1), np.float32)
        m = np.full((R, 1), -np.inf, np.float32)
        acc, den, m = merge.sync_accumulate(
            acc, den, m, sm[:, lo:hi], v[lo:hi], valid=valid[:, lo:hi])
        return acc, den, m

    # sequential accumulate across the first two segments = one partial
    acc, den, m = sync_fold(0, t1)
    acc, den, m = merge.sync_accumulate(
        acc, den, m, sm[:, t1:t2], v[t1:t2], valid=valid[:, t1:t2])
    a, d, mm = merge.merge_lse((acc, den, m), sync_fold(t2, 32))
    out = np.asarray(merge.finalize(a, d, guard_zero=True))
    np.testing.assert_allclose(
        out, _softmax_attention(s, v, valid), rtol=1e-4, atol=1e-5)


@given(st.integers(0, 10_000),
       st.lists(st.integers(0, 5), min_size=4, max_size=4))
def test_unified_merge_order_invariance(seed, perm_draw):
    """Unified-max partials merge by addition: any permutation and any
    association of 4 segment partials agrees (up to fp addition
    reordering) — and the msc stat is exactly permutation-invariant."""
    s, v, valid = _case(seed, kv_len=32)
    cuts = [0, 8, 16, 24, 32]
    parts = [
        _unified_partial(s[:, a:b], v[a:b], valid[:, a:b], phi=0.0)
        for a, b in zip(cuts, cuts[1:])
    ]
    order = np.argsort(np.asarray(perm_draw), kind="stable")

    def chain(ps):
        acc = ps[0]
        for p in ps[1:]:
            acc = merge.merge_unified(acc, p)
        return acc

    base = chain(parts)
    shuffled = chain([parts[i] for i in order])
    # tree association vs left fold
    tree = merge.merge_unified(
        merge.merge_unified(parts[0], parts[1]),
        merge.merge_unified(parts[2], parts[3]))
    for other in (shuffled, tree):
        np.testing.assert_allclose(
            np.asarray(merge.finalize(base[0], base[1])),
            np.asarray(merge.finalize(other[0], other[1])),
            rtol=1e-5, atol=1e-6)
        assert np.asarray(base[2]) == np.asarray(other[2])  # max: exact


@given(st.integers(0, 10_000), st.floats(2.0, 8.0))
def test_unified_overflow_stat_detects_band_exit(seed, boost):
    """The fallback contract: scale logits until max(s − φ) exceeds the
    calibrated band's upper edge — the merged msc stat must report it
    exactly (it is a running max, not an estimate), because the wrapper's
    recompute cond fires on ``any(stat > band[1])``. Inside the band the
    unified output must already match the stable reference."""
    phi_cfg = SoftmaxPhiConfig()
    hi = phi_cfg.band[1]
    s, v, valid = _case(seed, kv_len=32)
    for scale in (1.0, float(boost) * hi):    # in-band, out-of-band
        sb = (s * scale).astype(np.float32)
        p1 = _unified_partial(sb[:, :16], v[:16], valid[:, :16], phi_cfg.phi)
        p2 = _unified_partial(sb[:, 16:], v[16:], valid[:, 16:], phi_cfg.phi)
        num, den, msc = merge.merge_unified(p1, p2)
        true_max = np.where(valid, sb, -np.inf).max() - phi_cfg.phi
        assert np.asarray(msc) == np.float32(true_max)
        if true_max <= hi:
            out = np.asarray(merge.finalize(num, den))
            np.testing.assert_allclose(
                out, _softmax_attention(sb, v, valid), rtol=2e-4, atol=1e-5)
        else:
            # the stat crossing the band is exactly the recompute trigger
            assert np.asarray(msc) > hi


def test_finalize_guard_zero_only_touches_empty_rows():
    """guard_zero substitutes den=1 for fully-masked rows (callers drop
    them) and must not perturb any live row."""
    acc = np.arange(R * D, dtype=np.float32).reshape(R, D)
    den = np.array([[2.0], [0.0], [1.0], [0.0]], np.float32)
    out = np.asarray(merge.finalize(acc, den, guard_zero=True))
    live = np.asarray(merge.finalize(acc[::2], den[::2]))
    np.testing.assert_array_equal(out[::2], live)
    np.testing.assert_array_equal(out[1::2], acc[1::2])   # den treated as 1


def test_unified_accumulate_matches_sync_in_band():
    """Cross-scheme agreement on benign logits: the unified-max fold and
    the online-max fold of the same piece agree after finalize."""
    s, v, valid = _case(0, kv_len=32)
    num, den, _ = _unified_partial(s, v, valid, phi=0.0)
    uni = np.asarray(merge.finalize(num, den))
    acc = np.zeros((R, D), np.float32)
    d = np.zeros((R, 1), np.float32)
    m = np.full((R, 1), -np.inf, np.float32)
    sm = np.where(valid, s, -np.inf).astype(np.float32)
    acc, d, m = merge.sync_accumulate(acc, d, m, sm, v, valid=valid)
    syn = np.asarray(merge.finalize(acc, d))
    np.testing.assert_allclose(uni, syn, rtol=2e-4, atol=1e-5)
