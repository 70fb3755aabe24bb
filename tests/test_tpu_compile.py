"""Ahead-of-time compiles of the serving path's Pallas kernels for a TPU
v5e at qwen2-0.5b's published widths (24 layers, d_model 896, 14/2 heads,
head_dim 64, d_ff 4864, vocab 151936).

Interpret mode cannot see Mosaic's tiling rules or its scoped-VMEM limit;
the v5e compiler, which runs here against a described (not attached)
``v5e:2x2`` topology, refuses exactly those. Every test lowers one kernel
with ``interpret=False`` for one chip of that topology, compiles it, and
checks that the program really contains the Mosaic kernel
(``tpu_custom_call``). Nothing runs, so these say nothing about results or
times — the interpret-mode oracle tests cover results.

The last tests compile whole paged step programs as the engine builds
them, on four-layer stacks at phi3's and qwen's attention widths, and
read the optimized HLO: the pool must be updated in place (no copy,
slice or transpose of a pool or a layer's slab, the cache outputs
aliased to the donated inputs) and every paged kernel must read it as a
``(pages, PS, HK, D)`` operand.

The topology is described inside a module fixture (never at import), so
only the worker that runs this file loads the TPU compiler, and every
worker collects the same tests.
"""
import dataclasses
import functools
import json
import os
import re

import jax
import numpy as np
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs, hardware
from repro.core.plan import make_plan
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
from repro.kernels.decode_fuse import (
    decode_ingest_fused,
    ffn_norm_fused,
    oproj_residual_fused,
)
from repro.kernels.flash_prefill import flash_prefill
from repro.kernels.flat_gemm import flat_gemm
from repro.kernels.fused_ffn import fused_ffn_up
from repro.kernels.gemv import gemv
from repro.kernels import ops
from repro.kernels.group_attention import (
    DecodeGroups,
    grouped_paged_decode_attention_unified_max,
)
from repro.models.api import get_model
from repro.models.kvlayout import PagedLayout
from repro.models.layers import LayerCtx
from repro.serving.engine import copy_page, promote_upload

CFG = configs.get("qwen2-0.5b")
SLOTS, PAGE, MAX_SEQ, CHUNK = 8, 64, 1024, 64   # the served engine's shape
NB = MAX_SEQ // PAGE
NUM_PAGES = SLOTS * NB
HQ, HK, DH = CFG.num_heads, CFG.num_kv_heads, CFG.head_dim
D, F = CFG.d_model, CFG.d_ff
VOCAB = 152064            # lm-head width after models.layers.vocab_padded
PLAN = os.path.join(os.path.dirname(os.path.dirname(__file__)), "plans",
                    "qwen2-0.5b-tpu-v5e.json")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described v5e chip, with the persistent compilation cache off
    for the module: entries compiled for a described chip cannot be read
    back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def shape(one_chip):
    """``shape(dims, dtype)`` -> a ShapeDtypeStruct placed on the chip."""
    return lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype,
                                                    sharding=one_chip)


def _groups(shape, ng, lp, m):
    i32 = jnp.int32
    return DecodeGroups(
        tables=shape((ng, lp), i32), n_pages=shape((ng,), i32),
        g_prefix_len=shape((ng,), i32), num_members=shape((ng,), i32),
        member_rows=shape((ng, m), i32), gid=shape((SLOTS,), i32),
        member=shape((SLOTS,), i32), prefix_len=shape((SLOTS,), i32))


def _compiles_to_mosaic(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _weight(shape, k, n, wdtype):
    """A (K, N) weight operand and its dequant step (None for bf16)."""
    if wdtype == "bf16":
        return shape((k, n), jnp.bfloat16), None
    return shape((k, n), jnp.int8), shape((n,), jnp.float32)


def _pools(shape, kv):
    dtype = jnp.bfloat16 if kv == "bf16" else jnp.int8
    pools = [shape((NUM_PAGES, PAGE, HK, DH), dtype)] * 2
    scales = ([shape((NUM_PAGES, HK), jnp.float32)] * 2
              if kv == "int8" else [None, None])
    return pools, scales


# ---------------------------------------------------------------------------
# Attention over the block pool
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("scheme", ["unified_max", "sync"])
def test_paged_decode_compiles(shape, scheme, kv):
    kernel = (paged_decode_attention_unified_max if scheme == "unified_max"
              else paged_decode_attention_sync)
    (kp, vp), (ks, vs) = _pools(shape, kv)

    def fn(q, k_pool, v_pool, bt, lengths, k_scale, v_scale):
        return kernel(q, k_pool, v_pool, bt, lengths,
                      k_scale=k_scale, v_scale=v_scale)

    _compiles_to_mosaic(fn, shape((SLOTS, HQ, DH), jnp.bfloat16), kp, vp,
                        shape((SLOTS, NB), jnp.int32),
                        shape((SLOTS,), jnp.int32), ks, vs)


@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("scheme", ["unified_max", "sync"])
def test_paged_chunk_compiles(shape, scheme, kv):
    kernel = (paged_chunk_attention_unified_max if scheme == "unified_max"
              else paged_chunk_attention_sync)
    (kp, vp), (ks, vs) = _pools(shape, kv)

    def fn(q, k_pool, v_pool, bt, lengths, k_scale, v_scale):
        return kernel(q, k_pool, v_pool, bt, lengths,
                      k_scale=k_scale, v_scale=v_scale)

    _compiles_to_mosaic(fn, shape((SLOTS, CHUNK, HQ, DH), jnp.bfloat16),
                        kp, vp, shape((SLOTS, NB), jnp.int32),
                        shape((SLOTS,), jnp.int32), ks, vs)


@pytest.mark.parametrize("scheme", ["unified_max", "sync"])
def test_dense_decode_compiles(shape, scheme):
    """The slot-cache (non-paged) decode kernels, (B, HK, S, D) layout."""
    kernel = (decode_attention_unified_max if scheme == "unified_max"
              else decode_attention_sync)
    cache = shape((SLOTS, HK, MAX_SEQ, DH), jnp.bfloat16)
    _compiles_to_mosaic(kernel, shape((SLOTS, HQ, DH), jnp.bfloat16),
                        cache, cache, shape((SLOTS,), jnp.int32))


@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_grouped_decode_compiles(shape, kv):
    (kp, vp), (ks, vs) = _pools(shape, kv)
    groups = _groups(shape, 2, 2, SLOTS)
    i32 = jnp.int32

    def fn(q, k_pool, v_pool, bt, lengths, groups, k_scale, v_scale):
        return grouped_paged_decode_attention_unified_max(
            q, k_pool, v_pool, bt, lengths, groups,
            k_scale=k_scale, v_scale=v_scale)

    _compiles_to_mosaic(fn, shape((SLOTS, HQ, DH), jnp.bfloat16), kp, vp,
                        shape((SLOTS, NB), i32), shape((SLOTS,), i32),
                        groups, ks, vs)


@pytest.mark.parametrize("unified", [True, False])
def test_flash_prefill_compiles(shape, unified):
    s = 2048
    _compiles_to_mosaic(
        functools.partial(flash_prefill, unified_max=unified),
        shape((1, s, HQ, DH), jnp.bfloat16),
        shape((1, s, HK, DH), jnp.bfloat16),
        shape((1, s, HK, DH), jnp.bfloat16))


# ---------------------------------------------------------------------------
# The decode-layer seams
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("wdtype", ["bf16", "int8"])
def test_decode_ingest_compiles(shape, wdtype):
    wq, sq = _weight(shape, D, HQ * DH, wdtype)
    wk, sk = _weight(shape, D, HK * DH, wdtype)
    wv, sv = _weight(shape, D, HK * DH, wdtype)

    def fn(x, norm, wq, wk, wv, pos, bq, bk, bv, sq, sk, sv):
        return decode_ingest_fused(
            x, norm, wq, wk, wv, pos, num_heads=HQ, num_kv_heads=HK,
            head_dim=DH, rope_theta=CFG.rope_theta, eps=CFG.rms_norm_eps,
            bq=bq, bk_bias=bk,
            bv=bv, wq_scale=sq, wk_scale=sk, wv_scale=sv)

    bf16 = jnp.bfloat16
    _compiles_to_mosaic(
        fn, shape((SLOTS, D), bf16), shape((D,), bf16), wq, wk, wv,
        shape((SLOTS,), jnp.int32), shape((HQ * DH,), bf16),
        shape((HK * DH,), bf16), shape((HK * DH,), bf16), sq, sk, sv)


@pytest.mark.parametrize("wdtype", ["bf16", "int8"])
@pytest.mark.parametrize("k", [HQ * DH, F], ids=["o_proj", "down_proj"])
def test_oproj_residual_compiles(shape, k, wdtype):
    wo, so = _weight(shape, k, D, wdtype)

    def fn(o, wo, resid, so):
        return oproj_residual_fused(o, wo, resid, w_scale=so)

    _compiles_to_mosaic(fn, shape((SLOTS, k), jnp.bfloat16), wo,
                        shape((SLOTS, D), jnp.bfloat16), so)


@pytest.mark.parametrize("wdtype", ["bf16", "int8"])
def test_ffn_norm_compiles(shape, wdtype):
    wg, sg = _weight(shape, D, F, wdtype)
    wu, su = _weight(shape, D, F, wdtype)

    def fn(x, norm, wg, wu, sg, su):
        return ffn_norm_fused(x, norm, wg, wu, eps=CFG.rms_norm_eps,
                              wg_scale=sg, wu_scale=su)

    _compiles_to_mosaic(fn, shape((SLOTS, D), jnp.bfloat16),
                        shape((D,), jnp.bfloat16), wg, wu, sg, su)


# ---------------------------------------------------------------------------
# GEMMs: decode M, the largest M the committed plan routes to flat_gemm,
# and the chunked-prefill M the fused FFN sees
# ---------------------------------------------------------------------------


def _largest_flat_gemm_route():
    """(M, K, N) of the largest M any committed-plan entry sends to
    flat_gemm (``m1 <= M < m2``)."""
    with open(PLAN) as f:
        entries = json.load(f)["ops"]["matmul"]["entries"]
    best = None
    for key, e in entries.items():
        k, n = (int(x) for x in key.split(","))
        if e["m2"] > e["m1"] and (best is None or e["m2"] - 1 > best[0]):
            best = (e["m2"] - 1, k, n)
    assert best is not None, "the plan routes no shape to flat_gemm"
    return best


@pytest.mark.parametrize("wdtype", ["bf16", "int8"])
@pytest.mark.parametrize("case", [
    ("gemv", 8, D, HQ * DH),
    ("gemv", 8, F, D),
    ("gemv", 8, D, VOCAB),
    ("flat_gemm", 8, D, F),
    ("flat_gemm", 8, D, VOCAB),
    ("flat_gemm", "plan", None, None),
], ids=["gemv-qproj", "gemv-down", "gemv-lmhead", "flat-up", "flat-lmhead",
        "flat-plan-max"])
def test_gemm_compiles(shape, case, wdtype):
    kernel, m, k, n = case
    if m == "plan":
        m, k, n = _largest_flat_gemm_route()
    fn_kernel = gemv if kernel == "gemv" else flat_gemm
    w, s = _weight(shape, k, n, wdtype)

    def fn(x, w, s):
        return fn_kernel(x, w, w_scale=s)

    _compiles_to_mosaic(fn, shape((m, k), jnp.bfloat16), w, s)


@pytest.mark.parametrize("wdtype", ["bf16", "int8"])
@pytest.mark.parametrize("m", [SLOTS, SLOTS * CHUNK], ids=["decode", "chunk"])
def test_fused_ffn_compiles(shape, m, wdtype):
    wg, sg = _weight(shape, D, F, wdtype)
    wu, su = _weight(shape, D, F, wdtype)

    def fn(x, wg, wu, sg, su):
        return fused_ffn_up(x, wg, wu, wg_scale=sg, wu_scale=su)

    _compiles_to_mosaic(fn, shape((m, D), jnp.bfloat16), wg, wu, sg, su)


# ---------------------------------------------------------------------------
# Whole step programs: each writes only its new positions into the donated
# pool, and the kernels read the stacked pool in place
# ---------------------------------------------------------------------------

# (config, pages): MHA 32 x 96 at the phi3 cell's 128 pages and GQA 14/2 x
# 64; four layers each, so every pool is larger than the chip's VMEM, as a
# served pool is
STEP_CASES = {"phi3": ("phi3-mini-3.8b", 128), "qwen": ("qwen2-0.5b", 2048)}
# the steps also at a second page count each whose stored pool is
# row-major: the 224 pages phi3's pool could hold on one chip, and the
# qwen cell's 1024
STEP_POOLS = {**STEP_CASES, "phi3-224": ("phi3-mini-3.8b", 224),
              "qwen-1024": ("qwen2-0.5b", 1024)}
STEP_LAYERS = 4
# copies, slices, transposes: any of them on a pool or a slab moves it all
MOVES = re.compile(r"copy|slice|transpose")
PAGED_KERNELS = ("paged_decode_attention", "paged_chunk_attention",
                 "grouped_decode_")


def _program_hlo(topo, shape, monkeypatch, arch, num_pages, program):
    """Optimized HLO of one program that takes the pool donated, built as
    ``Engine`` builds it — a step (``decode``, ``grouped``, ``chunk``) or
    a page copy (``copy_page``, ``promote_upload``) — for a
    ``STEP_LAYERS``-layer stack at ``arch``'s widths, on the pool in the
    shapes the engine stores it in on a v5e."""
    monkeypatch.setattr(ops, "kernel_target",
                        lambda: (False, hardware.TPU_V5E))
    cfg = dataclasses.replace(configs.get(arch), num_layers=STEP_LAYERS)
    api = get_model(cfg)
    plan = make_plan("pallas", gather_chunk="fused", decode_group="grouped")
    ctx = LayerCtx(cfg=cfg, plan=plan)
    spec = api.cache_spec(PagedLayout(num_pages, PAGE))
    cache = jax.tree.map(lambda s: shape(s.shape, s.dtype),
                         PagedLayout.device_storage(spec, topo.devices[0]))
    params = jax.tree.map(lambda s: shape(s.shape, s.dtype),
                          jax.eval_shape(api.init_params,
                                         jax.random.PRNGKey(0)))
    i32 = jnp.int32
    rows, table = shape((SLOTS,), i32), shape((SLOTS, NB), i32)
    if program == "decode":
        def fn(p, t, c, bt, le, po):
            return api.decode_step(ctx, p, t, c, le, block_tables=bt,
                                   positions=po)
        args, donate = (params, rows, cache, table, rows, rows), 2
    elif program == "grouped":
        def fn(p, t, c, bt, le, gr, po):
            return api.decode_step(ctx, p, t, c, le, block_tables=bt,
                                   decode_groups=gr, positions=po)
        args = (params, rows, cache, table, rows,
                _groups(shape, 4, 4, SLOTS), rows)
        donate = 2
    elif program == "chunk":
        def fn(p, t, cl, c, bt, le):
            return api.prefill_chunk(ctx, p, t, cl, c, le, block_tables=bt)
        args = (params, shape((SLOTS, CHUNK), i32), rows, cache, table,
                rows)
        donate = 3
    elif program == "copy_page":
        fn, args, donate = copy_page, (cache, shape((), i32),
                                       shape((), i32)), 0
    else:
        n = 4
        slabs = jax.tree.map(
            lambda s: shape((s.shape[0], n, *s.shape[2:]), s.dtype), spec)
        fn, args, donate = promote_upload, (cache, slabs,
                                            shape((n,), i32)), 0
    low = jax.jit(fn, donate_argnums=(donate,)).lower(*args)
    return low.compile().as_text(), cfg, cache["k"].shape


def _assert_in_place(text, cfg, num_pages, stored):
    """No pool- or slab-sized move in ``text``, and both pools of the
    output aliased to the donated pool arguments."""
    hk, d = cfg.num_kv_heads, cfg.head_dim
    # a pool, a flat pool view or a slab: (..., HK, D) with at least a
    # layer's pages' rows in front, pages and rows merged or not
    pooled = re.compile(rf"bf16\[([\d,]+),{hk},{d}\]")

    def is_pool(dims):
        lead = [int(x) for x in dims.split(",")]
        return int(np.prod(lead)) >= num_pages * PAGE

    moved = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (.*?) ([\w\-]+)\(",
                     line)
        if m and MOVES.search(m[1] + " " + m[3]) and any(
                is_pool(dims) for dims in pooled.findall(m[2])):
            moved.append(line.strip()[:160])
    assert not moved, "pool-sized moves:\n" + "\n".join(moved)

    # the cache leaves of the output alias the donated cache arguments
    entry = text[text.index("\nENTRY"):]
    args = {n: t for t, n in re.findall(
        r"= (bf16\[[\d,]+\])\S* parameter\((\d+)\)", entry)}
    header = text.splitlines()[0]          # HloModule ..., input_output_alias
    aliased = re.findall(r"\{(\d+)\}: \((\d+), \{\}", header)
    pool_shape = "bf16[" + ",".join(map(str, stored)) + "]"
    assert len({out for out, arg in aliased
                if args.get(arg) == pool_shape}) == 2


@pytest.mark.parametrize("program", ["decode", "grouped", "chunk"])
@pytest.mark.parametrize("case", sorted(STEP_POOLS))
def test_step_updates_pool_in_place(topo, shape, monkeypatch, case,
                                    program):
    arch, num_pages = STEP_POOLS[case]
    text, cfg, stored = _program_hlo(topo, shape, monkeypatch, arch,
                                     num_pages, program)
    _assert_in_place(text, cfg, num_pages, stored)

    # every paged kernel reads a 4-D (pages, PS, HK, D) pool operand, the
    # shape the benchmark's trace classes recognise decode attention by
    kernels = [line for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line
               and re.match(r"\s*%(" + "|".join(PAGED_KERNELS) + ")", line)]
    assert kernels
    operand = re.compile(rf"bf16\[\d+,{PAGE},{cfg.num_kv_heads},"
                         rf"{cfg.head_dim}\]")
    assert all(operand.search(line) for line in kernels)


@pytest.mark.parametrize("program", ["copy_page", "promote_upload"])
@pytest.mark.parametrize("case", sorted(STEP_CASES))
def test_page_copies_update_pool_in_place(topo, shape, monkeypatch, case,
                                          program):
    """The COW fork and the tier promotion write their pages into the
    donated pool as the steps do: no relay of the pool around them."""
    arch, num_pages = STEP_CASES[case]
    text, cfg, stored = _program_hlo(topo, shape, monkeypatch, arch,
                                     num_pages, program)
    _assert_in_place(text, cfg, num_pages, stored)
