"""Per-kernel allclose sweeps against the pure-jnp oracles (interpret mode).

Every Pallas kernel is exercised over a shape x dtype grid and asserted
against ref.py — the contract required for real-TPU deployment confidence.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import TOL
from repro.kernels import ref
from repro.kernels.decode_attention import (
    decode_attention_sync,
    decode_attention_unified_max,
)
from repro.kernels.flash_prefill import flash_prefill
from repro.kernels.flat_gemm import flat_gemm, pick_bk, pick_bn
from repro.kernels.gemv import gemv


# ---------------------------------------------------------------------------
# T2: flat GEMM
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "dtype", ["float32",
              pytest.param("bfloat16", marks=pytest.mark.slow)])
@pytest.mark.parametrize("m,k,n", [
    (1, 256, 512), (3, 256, 512), (8, 512, 256), (13, 384, 640),
    (32, 1024, 256), (64, 256, 1024),
])
def test_flat_gemm_matches_oracle(m, k, n, dtype):
    kx, kw = jax.random.split(jax.random.PRNGKey(m * 1000 + n))
    x = jax.random.normal(kx, (m, k), dtype)
    w = jax.random.normal(kw, (k, n), dtype)
    got = flat_gemm(x, w, interpret=True)
    want = ref.flat_gemm_ref(x, w)
    assert got.shape == (m, n) and got.dtype == x.dtype
    np.testing.assert_allclose(
        got.astype(np.float32), want.astype(np.float32), **TOL[dtype])


def test_flat_gemm_block_pickers_respect_vmem():
    from repro import hardware
    spec = hardware.TPU_V5E
    for m in (8, 16, 64):
        for n in (512, 4096, 16384):
            for k in (512, 4096):
                bn = pick_bn(m, n, k, spec=spec)
                bk = pick_bk(m, bn, k, spec=spec)
                assert n % bn == 0 or bn == n
                assert k % bk == 0 or bk == k
                vmem = 2 * (m * bk + bk * bn) * 2 + m * bn * 4
                assert vmem <= spec.tile_budget or (bn == 128 and bk == 128)


def test_hardware_spec_by_device_kind():
    """The chip path looks its spec up by device_kind: v5e resolves, any
    other kind (the CPU included) is an error, never a silent v5e."""
    from types import SimpleNamespace

    from repro import hardware
    v5e = SimpleNamespace(device_kind="TPU v5 lite", platform="tpu")
    assert hardware.for_device(v5e) is hardware.TPU_V5E
    with pytest.raises(hardware.UnknownDeviceError):
        hardware.for_device(SimpleNamespace(device_kind="TPU v9",
                                            platform="tpu"))
    with pytest.raises(hardware.UnknownDeviceError):
        hardware.for_device(jax.devices()[0])     # the CPU


def test_flat_gemm_min_padding_is_8():
    """The T2 claim: M padded to 8, not 64/128."""
    x = jnp.ones((3, 128), jnp.float32)
    w = jnp.ones((128, 128), jnp.float32)
    out = flat_gemm(x, w, interpret=True)
    assert out.shape == (3, 128)  # sliced back from M_pad=8


# ---------------------------------------------------------------------------
# ImplA: GEMV
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "dtype", ["float32",
              pytest.param("bfloat16", marks=pytest.mark.slow)])
@pytest.mark.parametrize("m,k,n", [(1, 512, 768), (2, 300, 500), (4, 128, 128)])
def test_gemv_matches_oracle(m, k, n, dtype):
    kx, kw = jax.random.split(jax.random.PRNGKey(7))
    x = jax.random.normal(kx, (m, k), dtype)
    w = jax.random.normal(kw, (k, n), dtype)
    got = gemv(x, w, interpret=True)
    np.testing.assert_allclose(
        got.astype(np.float32), ref.gemv_ref(x, w).astype(np.float32),
        **TOL[dtype])


# ---------------------------------------------------------------------------
# T1: decode attention (async unified-max + sync fallback)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "dtype", ["float32",
              pytest.param("bfloat16", marks=pytest.mark.slow)])
@pytest.mark.parametrize("b,hq,hk,d,s,block", [
    (2, 8, 2, 64, 256, 128),     # GQA 4:1
    (1, 4, 4, 128, 512, 256),    # MHA
    (3, 14, 2, 64, 384, 128),    # qwen2-style 7:1
])
def test_decode_attention_unified_max(b, hq, hk, d, s, block, dtype):
    ks = jax.random.split(jax.random.PRNGKey(b * 17 + s), 4)
    q = jax.random.normal(ks[0], (b, hq, d), dtype)
    kc = jax.random.normal(ks[1], (b, hk, s, d), dtype)
    vc = jax.random.normal(ks[2], (b, hk, s, d), dtype)
    lengths = jnp.asarray(
        np.random.default_rng(0).integers(1, s + 1, size=b), jnp.int32)
    out, stat = decode_attention_unified_max(
        q, kc, vc, lengths, phi=0.0, block_k=block, interpret=True)
    want = ref.attention_decode_ref(
        q, kc.transpose(0, 2, 1, 3), vc.transpose(0, 2, 1, 3), lengths)
    np.testing.assert_allclose(
        out.astype(np.float32), want.astype(np.float32), **TOL[dtype])
    assert stat.shape == (b, hk) and bool(jnp.all(jnp.isfinite(stat)))


@pytest.mark.parametrize(
    "dtype", ["float32",
              pytest.param("bfloat16", marks=pytest.mark.slow)])
def test_decode_attention_sync_matches(dtype):
    b, hq, hk, d, s = 2, 8, 2, 64, 320
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (b, hq, d), dtype)
    kc = jax.random.normal(ks[1], (b, hk, s, d), dtype)
    vc = jax.random.normal(ks[2], (b, hk, s, d), dtype)
    lengths = jnp.array([100, 320], jnp.int32)
    out = decode_attention_sync(q, kc, vc, lengths, block_k=128,
                                interpret=True)
    want = ref.attention_decode_ref(
        q, kc.transpose(0, 2, 1, 3), vc.transpose(0, 2, 1, 3), lengths)
    np.testing.assert_allclose(
        out.astype(np.float32), want.astype(np.float32), **TOL[dtype])


def test_decode_attention_phi_invariance():
    """Output is independent of φ while inside the safe band (Eq. 3)."""
    b, hq, hk, d, s = 1, 4, 2, 32, 128
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (b, hq, d), jnp.float32)
    kc = jax.random.normal(ks[1], (b, hk, s, d), jnp.float32)
    vc = jax.random.normal(ks[2], (b, hk, s, d), jnp.float32)
    lengths = jnp.array([s], jnp.int32)
    outs = [
        decode_attention_unified_max(
            q, kc, vc, lengths, phi=phi, block_k=64, interpret=True)[0]
        for phi in (-2.0, 0.0, 3.5)
    ]
    np.testing.assert_allclose(outs[0], outs[1], rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(outs[1], outs[2], rtol=1e-4, atol=1e-5)


def test_decode_attention_overflow_stat_reports():
    """Scaled-up logits must push the stat past a tight band -> fallback."""
    b, hq, hk, d, s = 1, 2, 2, 32, 64
    q = 50.0 * jnp.ones((b, hq, d), jnp.float32)
    kc = jnp.ones((b, hk, s, d), jnp.float32)
    vc = jnp.ones((b, hk, s, d), jnp.float32)
    lengths = jnp.array([s], jnp.int32)
    _, stat = decode_attention_unified_max(
        q, kc, vc, lengths, phi=0.0, block_k=32, interpret=True)
    assert float(stat.max()) > 16.0  # way outside a (-16, 16) band


# ---------------------------------------------------------------------------
# Prefill attention (fused kernel + chunked XLA path)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "dtype", ["float32",
              pytest.param("bfloat16", marks=pytest.mark.slow)])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("hq,hk", [(4, 4), (8, 2)])
def test_flash_prefill_matches_oracle(hq, hk, causal, dtype):
    b, s, d = 2, 256, 64
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(ks[0], (b, s, hq, d), dtype)
    k = jax.random.normal(ks[1], (b, s, hk, d), dtype)
    v = jax.random.normal(ks[2], (b, s, hk, d), dtype)
    want = ref.attention_prefill_ref(q, k, v, causal=causal)
    res = flash_prefill(q, k, v, causal=causal, unified_max=True, phi=0.0,
                        interpret=True)
    out = res[0] if isinstance(res, tuple) else res
    np.testing.assert_allclose(
        out.astype(np.float32), want.astype(np.float32), **TOL[dtype])
    res = flash_prefill(q, k, v, causal=causal, unified_max=False,
                        interpret=True)
    out = res[0] if isinstance(res, tuple) else res
    np.testing.assert_allclose(
        out.astype(np.float32), want.astype(np.float32), **TOL[dtype])


@pytest.mark.parametrize("window", [0, 64])
@pytest.mark.parametrize("phi", [0.0, None])
def test_chunked_prefill_ref(window, phi):
    b, s, hq, hk, d = 2, 300, 4, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(13), 3)
    q = jax.random.normal(ks[0], (b, s, hq, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, hk, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, hk, d), jnp.float32)
    want = ref.attention_prefill_ref(q, k, v, causal=True,
                                     sliding_window=window)
    got = ref.attention_prefill_chunked(
        q, k, v, causal=True, sliding_window=window, phi=phi, block_q=128)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------------------------
# T2 extension: fused flat-GEMM SwiGLU FFN-up
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "dtype", ["float32",
              pytest.param("bfloat16", marks=pytest.mark.slow)])
@pytest.mark.parametrize("activation", ["swiglu", "gelu"])
@pytest.mark.parametrize("m,k,n", [(3, 256, 512), (8, 512, 384),
                                   (17, 384, 256)])
def test_fused_ffn_up_matches_oracle(m, k, n, activation, dtype):
    from repro.kernels.fused_ffn import fused_ffn_up
    ks = jax.random.split(jax.random.PRNGKey(m * 31 + n), 3)
    x = jax.random.normal(ks[0], (m, k), dtype)
    wg = jax.random.normal(ks[1], (k, n), dtype) * 0.05
    wu = jax.random.normal(ks[2], (k, n), dtype) * 0.05
    got = fused_ffn_up(x, wg, wu, activation=activation, interpret=True)
    want = ref.fused_ffn_up_ref(x, wg, wu, activation=activation)
    assert got.shape == (m, n)
    np.testing.assert_allclose(
        got.astype(np.float32), want.astype(np.float32), **TOL[dtype])


def test_fused_ffn_traffic_accounting():
    """The fusion claim: activation HBM round-trips removed (2·M·N of
    gate/up tensors never leave VMEM; x read once, not twice)."""
    m, k, n = 8, 4096, 11008
    db = 2
    separate = (2 * m * k + 2 * k * n + 3 * m * n) * db
    fused = (m * k + 2 * k * n + m * n) * db
    assert fused < separate
    saved = separate - fused
    assert saved == (m * k + 2 * m * n) * db


# ---------------------------------------------------------------------------
# Paged (block-table) decode attention
# ---------------------------------------------------------------------------


def _paged_fixture(dtype, seed=0):
    """Random pool + disjoint per-row page assignment with sentinel tails."""
    from repro.kernels.ref import gather_paged_kv  # noqa: F401
    rng = np.random.default_rng(seed)
    b, hq, hk, d, ps, num_pages, nb = 3, 8, 2, 64, 32, 24, 8
    q = jnp.asarray(rng.normal(size=(b, hq, d)), dtype)
    kp = jnp.asarray(rng.normal(size=(num_pages, ps, hk, d)), dtype)
    vp = jnp.asarray(rng.normal(size=(num_pages, ps, hk, d)), dtype)
    perm = rng.permutation(num_pages)
    bt = np.full((b, nb), num_pages, np.int32)   # sentinel padding
    for i in range(b):
        bt[i] = perm[i * nb:(i + 1) * nb]
    bt[2, 5:] = num_pages                        # short row: fewer pages
    lengths = jnp.asarray([200, 37, 5 * ps], jnp.int32)
    return q, kp, vp, jnp.asarray(bt), lengths


@pytest.mark.parametrize(
    "dtype", ["float32",
              pytest.param("bfloat16", marks=pytest.mark.slow)])
def test_paged_decode_attention_matches_oracle(dtype):
    from repro.kernels.decode_attention import (
        paged_decode_attention_sync, paged_decode_attention_unified_max)
    q, kp, vp, bt, lengths = _paged_fixture(dtype)
    got, _ = paged_decode_attention_unified_max(
        q, kp, vp, bt, lengths, phi=0.0, interpret=True)
    want, _ = ref.attention_decode_paged_unified_max_ref(
        q, kp, vp, bt, lengths, phi=0.0)
    np.testing.assert_allclose(
        got.astype(np.float32), want.astype(np.float32), **TOL[dtype])
    got_s = paged_decode_attention_sync(q, kp, vp, bt, lengths,
                                        interpret=True)
    want_s = ref.attention_decode_paged_ref(q, kp, vp, bt, lengths)
    np.testing.assert_allclose(
        got_s.astype(np.float32), want_s.astype(np.float32), **TOL[dtype])


def test_paged_oracle_equals_dense_on_gathered_view():
    """gather(pool, block_table) + dense decode == paged decode, bitwise —
    the identity the engine's dense/paged token-equality rests on."""
    q, kp, vp, bt, lengths = _paged_fixture("float32")
    k_dense = ref.gather_paged_kv(kp, bt)
    v_dense = ref.gather_paged_kv(vp, bt)
    dense = ref.attention_decode_ref(q, k_dense, v_dense, lengths)
    paged = ref.attention_decode_paged_ref(q, kp, vp, bt, lengths)
    assert bool(jnp.all(dense == paged))


def test_chunk_attention_overflow_falls_back_to_safe():
    """T1 chunk attention recomputes with the safe scheme when any centered
    logit leaves the band (paper's recomputation fallback, chunk path)."""
    from repro.config import SoftmaxPhiConfig
    from repro.kernels import ops
    rng = np.random.default_rng(3)
    b, c, hq, hk, d, s = 2, 4, 4, 2, 32, 64
    kc = jnp.asarray(rng.normal(size=(b, s, hk, d)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(b, s, hk, d)), jnp.float32)
    lens = jnp.asarray([10, 30], jnp.int32)
    q_big = jnp.asarray(rng.normal(size=(b, c, hq, d)), jnp.float32) * 50
    out = ops.attention_chunk(
        q_big, kc, vc, lens,
        phi_cfg=SoftmaxPhiConfig(phi=0.0, band=(-1.0, 1.0)))
    safe = ref.attention_chunk_ref(q_big, kc, vc, lens, phi=None)
    # the T1 scheme overflows to inf/nan on these logits, so a finite
    # output close to the safe oracle proves the recompute branch ran
    # (cond-compiled vs eager fusion keeps this from being bitwise)
    assert bool(jnp.all(jnp.isfinite(out)))
    np.testing.assert_allclose(np.asarray(out), np.asarray(safe),
                               rtol=1e-5, atol=1e-5)
    q_small = jnp.asarray(rng.normal(size=(b, c, hq, d)), jnp.float32) * 0.01
    out2 = ops.attention_chunk(
        q_small, kc, vc, lens,
        phi_cfg=SoftmaxPhiConfig(phi=0.0, band=(-40.0, 40.0)))
    t1 = ref.attention_chunk_ref(q_small, kc, vc, lens, phi=0.0)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(t1),
                               rtol=1e-5, atol=1e-5)  # T1 branch kept
