"""T3 — Heuristic dataflow cost models and decision flows (paper §5).

The paper's observation: a transformer has only four GEMM ``[K, N]`` shapes
(QKV, O, FFN-up, FFN-down; MoE adds the per-expert pair), and only ``M``
varies at runtime (batch·tokens). So an *offline* profile over M per [K, N]
finds two inflection points

    M < M₁            → ImplA  (VPU GEMV — CUDA-core/FastGEMV analogue)
    M₁ ≤ M < M₂       → ImplB  (Pallas flat GEMM, minimal M-padding — T2)
    M₂ ≤ M            → ImplC  (XLA dot_general — cuBLAS/CUTLASS analogue)

and the runtime consults a zero-overhead lookup. This module holds the
*decision machinery*: the per-impl cost models (:func:`predict_time` for
GEMM, :func:`predict_decode_time` for the decode-attention KV grid), the
sweep flows (:func:`find_inflections`, :func:`find_block_k`,
:func:`find_chunk_threshold`), and the measurement backends. The tuned
decisions themselves live in :class:`repro.core.plan.ExecutionPlan` —
build one with :func:`repro.core.plan.tune`, which drives every flow here
and serializes the result with provenance.

Profiling backend: on a real TPU, pass ``measure="wallclock"`` to
``plan.tune`` and the GEMM inflection points come from timings. In this
CPU-only container the default backend is the analytical v5e roofline model
below — the decision *structure* is identical and unit-tested for the
invariants the paper relies on (piecewise dominance, monotone crossover).
"""
from __future__ import annotations

import dataclasses
import enum
from typing import Callable, Iterable

from repro import hardware
from repro.config import ModelConfig


class Impl(enum.Enum):
    GEMV = "ImplA"        # VPU broadcast-multiply-reduce
    FLAT_GEMM = "ImplB"   # Pallas minimal-pad MXU kernel
    XLA_DOT = "ImplC"     # XLA/Mosaic generic dot


@dataclasses.dataclass(frozen=True)
class GemmShape:
    """One [K, N] workload; ``count`` = occurrences per layer."""

    name: str
    k: int
    n: int
    count: int = 1


def model_gemm_shapes(cfg: ModelConfig) -> list[GemmShape]:
    """The paper's 'only four [K,N] shapes' — extracted per architecture."""
    d = cfg.d_model
    shapes = [
        GemmShape("qkv_proj", d, cfg.q_dim + 2 * cfg.kv_dim),
        GemmShape("o_proj", cfg.q_dim, d),
    ]
    gates = 2 if cfg.activation in ("swiglu", "geglu") else 1
    if cfg.family == "moe" and cfg.moe is not None:
        shapes += [
            GemmShape("router", d, cfg.moe.num_experts),
            GemmShape("expert_up", d, gates * cfg.d_ff, cfg.moe.num_experts),
            GemmShape("expert_down", cfg.d_ff, d, cfg.moe.num_experts),
        ]
    else:
        shapes += [
            GemmShape("ffn_up", d, gates * cfg.d_ff),
            GemmShape("ffn_down", cfg.d_ff, d),
        ]
    if cfg.family == "ssm":
        shapes += [GemmShape("rkvg_proj", d, 4 * d)]
    shapes += [GemmShape("lm_head", d, cfg.vocab_size)]
    return shapes


# ---------------------------------------------------------------------------
# Analytical cost model (v5e). All times in seconds for one GEMM call.
# ---------------------------------------------------------------------------

# one Mosaic pipeline fill: the fixed bubble any extra Pallas kernel stage
# pays before its grid streams at full rate (the ImplB GEMM model's
# launch constant — shared so every "extra kernel launch" term in this
# module prices launches identically)
_PIPELINE_FILL_S = 2e-6

# bytes per stored KV element by ``PagedPlan.kv_dtype`` — every KV-stream
# roofline term below scales by this, which is how quantized pages shift
# the fused/group/swap inflections (smaller pages, cheaper reads).
# Quantized pools also carry one f32 scale per (page, kv head);
# :func:`kv_page_bytes` accounts those exactly, the stream terms fold
# them in as negligible (4 bytes vs page_size*head_dim codes).
KV_DTYPE_BYTES = {"bf16": 2.0, "int8": 1.0, "fp8": 1.0}

# bytes per stored GEMM weight element by ``MatmulPlan.weight_dtype`` —
# the weight-stream twin of :data:`KV_DTYPE_BYTES`. Decode-phase GEMMs
# are flat (M = batch) and memory-bound on the K×N weight read, so this
# factor scales the dominant term of every decode roofline; quantized
# weights also carry one f32 scale per output channel, which
# :func:`param_bytes` and :func:`predict_flat_gemm_time` account exactly.
WEIGHT_DTYPE_BYTES = {"bf16": 2.0, "int8": 1.0, "fp8": 1.0}

# dtype-derived logits-closeness tolerance per weight_dtype — the
# plain-number mirror of ``repro.kernels.quant.logits_guard_tol`` over
# ``quant.spec_for`` (this module stays jax-free; a tier-1 test asserts
# the two stay in sync). ``"bf16"`` is the bitwise path: zero budget.
WEIGHT_GUARD_TOL = {
    "bf16": 0.0,
    "int8": 64 * (0.5 / 127.0),
    "fp8": 64 * 2.0 ** -4,
}


def _mem_time(m_eff: int, k: int, n: int, dtype_bytes: int,
              spec: hardware.HardwareSpec) -> float:
    """HBM traffic with the *effective* (padded) M — a padded layout reads
    and writes the padding too, which is exactly the paper's >50 %
    under-utilization argument restated as memory traffic."""
    bytes_moved = (m_eff * k + k * n + m_eff * n) * dtype_bytes
    return bytes_moved / spec.hbm_bw


def predict_time(
    impl: Impl, m: int, k: int, n: int, *,
    dtype_bytes: int = 2,
    spec: hardware.HardwareSpec = hardware.TPU_V5E,
) -> float:
    """Roofline-style time estimate for one (M,K,N) GEMM per implementation.

    The models encode the paper's Eq. 5 structure on TPU terms:
      * ImplA: VPU math, no M padding at all — wins only while the workload
        is so flat that HBM traffic dominates even the slow VPU.
      * ImplB: MXU with M padded to the 8-sublane atom ("pad to 8 not 64");
        both compute and traffic use M_pad=⌈M/8⌉·8. Mosaic's pipeline
        double-buffers the K stream, so overhead is one fill bubble, not
        per-tile.
      * ImplC: XLA's generic layout tiles M to 128; compute *and traffic*
        pay ⌈M/128⌉·128 — unbeatable once M fills the tile, >90 % wasted
        at M=8 (the paper's cuBLAS 'pad to 64' criticism, TPU version).
    """
    if impl is Impl.GEMV:
        mem = _mem_time(m, k, n, dtype_bytes, spec)
        compute = 2.0 * m * k * n / spec.peak_flops_vpu_f32
        return max(mem, compute)
    if impl is Impl.FLAT_GEMM:
        m_pad = max(8, -(-m // 8) * 8)
        mem = _mem_time(m_pad, k, n, dtype_bytes, spec)
        compute = 2.0 * m_pad * k * n / spec.peak_flops_bf16
        return max(mem, compute) + _PIPELINE_FILL_S
    if impl is Impl.XLA_DOT:
        m_pad = max(128, -(-m // 128) * 128)
        mem = _mem_time(m_pad, k, n, dtype_bytes, spec)
        compute = 2.0 * m_pad * k * n / spec.peak_flops_bf16
        return max(mem, compute) + 1e-6   # mature-library epilogue edge
    raise ValueError(impl)


def predict_flat_gemm_time(
    m: int, k: int, n: int, *,
    weight_dtype: str = "bf16",
    dtype_bytes: int = 2,
    spec: hardware.HardwareSpec = hardware.TPU_V5E,
) -> float:
    """ImplB roofline with the weight stream priced at its stored width.

    Equal to ``predict_time(Impl.FLAT_GEMM, ...)`` at
    ``weight_dtype="bf16"``. Quantized dtypes shrink only the K×N weight
    term (:data:`WEIGHT_DTYPE_BYTES`) and add the (N,) f32
    per-output-channel scale read — exactly the operands the quantized
    kernel streams; the activation read and output write keep
    ``dtype_bytes``. The compute term is unchanged: dequant rides the
    existing f32 accumulator epilogue and the codes feed the MXU at the
    activation dtype.
    """
    wb = WEIGHT_DTYPE_BYTES[weight_dtype]
    m_pad = max(8, -(-m // 8) * 8)
    scale_bytes = 0 if weight_dtype == "bf16" else n * 4
    bytes_moved = ((m_pad * k + m_pad * n) * dtype_bytes
                   + k * n * wb + scale_bytes)
    mem = bytes_moved / spec.hbm_bw
    compute = 2.0 * m_pad * k * n / spec.peak_flops_bf16
    return max(mem, compute) + _PIPELINE_FILL_S


MeasureFn = Callable[[Impl, int, int, int], float]


def wallclock_measure_factory(dtype="bfloat16", *, warmup: int = 3,
                              iters: int = 10) -> MeasureFn:
    """Real-hardware timing hook (used when running on an actual TPU).

    Discipline: independent PRNG keys for the two operands (a shared key
    would correlate x and w and flatter the reduction), ``warmup``
    post-compile calls to settle caches/autotuning, then ``iters`` timed
    dispatches each blocked to completion — timing N async dispatches
    against one trailing ``block_until_ready`` would measure queue depth,
    not kernel time.
    """
    import time

    import jax
    import jax.numpy as jnp

    from repro.kernels import flat_gemm as fg
    from repro.kernels import gemv as gv

    def measure(impl: Impl, m: int, k: int, n: int) -> float:
        kx, kw = jax.random.split(jax.random.PRNGKey(0))
        x = jax.random.normal(kx, (m, k), dtype=dtype)
        w = jax.random.normal(kw, (k, n), dtype=dtype)
        if impl is Impl.GEMV:
            f = jax.jit(lambda a, b: gv.gemv(a, b))
        elif impl is Impl.FLAT_GEMM:
            f = jax.jit(lambda a, b: fg.flat_gemm(a, b))
        else:
            f = jax.jit(lambda a, b: jnp.dot(a, b))
        f(x, w).block_until_ready()  # compile
        for _ in range(warmup):
            f(x, w).block_until_ready()
        total = 0.0
        for _ in range(iters):
            t0 = time.perf_counter()
            f(x, w).block_until_ready()
            total += time.perf_counter() - t0
        return total / iters

    return measure


# ---------------------------------------------------------------------------
# Offline decision flows (paper Fig. 9(b)) → plan entries
# ---------------------------------------------------------------------------

M_SWEEP = (1, 2, 4, 8, 16, 24, 32, 48, 64, 96, 128, 192, 256, 384, 512, 1024)


def pick_impl(m: int, m1: int, m2: int) -> Impl:
    """The piecewise routing ladder every GEMM decision reduces to."""
    if m < m1:
        return Impl.GEMV
    if m < m2:
        return Impl.FLAT_GEMM
    return Impl.XLA_DOT


@dataclasses.dataclass(frozen=True)
class DispatchEntry:
    """One tuned [K, N] inflection record (a matmul-plan entry)."""

    k: int
    n: int
    m1: int  # first M where ImplB beats ImplA
    m2: int  # first M where ImplC beats ImplB

    def pick(self, m: int) -> Impl:
        return pick_impl(m, self.m1, self.m2)


def find_inflections(
    k: int, n: int, *,
    measure: MeasureFn | None = None,
    m_sweep: Iterable[int] = M_SWEEP,
    spec: hardware.HardwareSpec = hardware.TPU_V5E,
) -> DispatchEntry:
    """The paper's decision flow: sweep M, find M₁ (A→B) and M₂ (B→C)."""
    measure = measure or (
        lambda impl, m, kk, nn: predict_time(impl, m, kk, nn, spec=spec)
    )
    ms = sorted(m_sweep)
    m1 = ms[-1] + 1
    m2 = ms[-1] + 1
    found1 = False
    for m in ms:
        ta = measure(Impl.GEMV, m, k, n)
        tb = measure(Impl.FLAT_GEMM, m, k, n)
        tc = measure(Impl.XLA_DOT, m, k, n)
        if not found1 and tb < ta:
            m1, found1 = m, True
        if found1 and tc < tb:
            m2 = m
            break
    return DispatchEntry(k=k, n=n, m1=m1, m2=max(m2, m1))


# ---------------------------------------------------------------------------
# Decode-attention block_k decision flow (find_inflections beyond GEMM)
# ---------------------------------------------------------------------------

BLOCK_K_CANDIDATES = (128, 256, 512, 1024, 2048)

# per-grid-step issue/bookkeeping bubble of the decode kernel's KV loop
_GRID_STEP_OVERHEAD_S = 5e-7


def predict_decode_time(
    block_k: int, s: int, kv_dim: int, *,
    dtype_bytes: int = 2,
    spec: hardware.HardwareSpec = hardware.TPU_V5E,
) -> float:
    """Roofline time for one decode-attention call at KV length ``s``.

    The grid loops over ``ceil(s / block_k)`` KV tiles; each tile streams
    K and V rows (padding included — a tile past ``s`` still DMAs), so a
    large ``block_k`` amortizes per-step overhead but pays padded traffic
    when ``s`` is short, and is capped by the double-buffered VMEM claim.
    """
    steps = -(-s // block_k)
    rows = steps * block_k
    mem = 2 * rows * kv_dim * dtype_bytes / spec.hbm_bw      # K + V streams
    vmem = 2 * 2 * block_k * kv_dim * dtype_bytes            # dbl-buffered K+V
    if vmem > spec.vmem_bytes // 2:
        return float("inf")
    return mem + steps * _GRID_STEP_OVERHEAD_S


def find_block_k(
    s: int, kv_dim: int, *,
    spec: hardware.HardwareSpec = hardware.TPU_V5E,
    candidates: Iterable[int] = BLOCK_K_CANDIDATES,
) -> int:
    """Pick the decode KV grid block for a representative KV length."""
    best, best_t = None, float("inf")
    for bk in sorted(candidates):
        t = predict_decode_time(bk, s, kv_dim, spec=spec)
        if t < best_t:
            best, best_t = bk, t
    if best is None:
        raise ValueError(f"no feasible block_k among {tuple(candidates)}")
    return best


# ---------------------------------------------------------------------------
# Prefill chunking-threshold decision flow
# ---------------------------------------------------------------------------

CHUNK_THRESHOLD_CANDIDATES = (1024, 2048, 4096, 8192, 16384, 32768)


def find_chunk_threshold(
    num_heads: int, *,
    dtype_bytes: int = 4,
    spec: hardware.HardwareSpec = hardware.TPU_V5E,
    budget_frac: float = 0.25,
) -> int:
    """Largest sequence length whose materialized per-sequence (H, S, S)
    f32 score tensor still fits a ``budget_frac`` slice of HBM; beyond it
    the blockwise T1 scheme must take over (live memory ≈ (H, Bq, S))."""
    budget = spec.hbm_bytes * budget_frac
    best = CHUNK_THRESHOLD_CANDIDATES[0]
    for s in CHUNK_THRESHOLD_CANDIDATES:
        if num_heads * s * s * dtype_bytes <= budget:
            best = s
    return best


# ---------------------------------------------------------------------------
# Paged chunked-prefill decision flows: dense-gather vs fused chunk kernel
# (find_inflections for the admission path)
# ---------------------------------------------------------------------------

CHUNK_BLOCK_CANDIDATES = (8, 16, 32, 64, 128, 256)

# per-admission-chunk-step dispatch/bookkeeping bubble (one jitted model
# call per chunk step — host sample + device dispatch)
_CHUNK_STEP_OVERHEAD_S = 2e-5


def predict_chunk_prefill_time(
    mode: str, prompt_len: int, table_positions: int, kv_dim: int, *,
    chunk: int = 64,
    page_size: int = 64,
    dtype_bytes: int = 2,
    kv_dtype: str = "bf16",
    spec: hardware.HardwareSpec = hardware.TPU_V5E,
) -> float:
    """Roofline time for the *KV side* of one whole chunked-prefill
    admission of a ``prompt_len`` prompt (the q-side GEMMs are identical
    across modes and cancel out of the decision).

    ``mode="dense"`` gathers the full ``(table_positions,)`` KV view per
    chunk step per K/V: each step reads the pool pages, writes the dense
    view, and reads it back for attention — 3x the table bytes, every
    step, regardless of how little of the table is resident. Under a
    quantized ``kv_dtype`` only the pool read shrinks; the materialized
    view is dequantized, so its write + readback stay full-precision —
    which is why quantization pushes the fused inflection *down*.

    ``mode="fused"`` reads only the pages covering ``resident + chunk``
    in place (scalar-prefetched block tables, no materialization, dequant
    in-kernel — all traffic at ``kv_dtype`` width), paying a per-page
    grid-step bubble instead — the Kernel Looping trade.
    """
    kvb = KV_DTYPE_BYTES[kv_dtype]
    steps = max(-(-prompt_len // chunk), 1)
    if mode == "dense":
        # K + V: pool read (stored width) + dense-view write + attention
        # read (dequantized width), per step
        bytes_per_step = (2 * table_positions * kv_dim
                          * (kvb + 2 * dtype_bytes))
        return steps * (bytes_per_step / spec.hbm_bw
                        + _CHUNK_STEP_OVERHEAD_S)
    if mode == "fused":
        total = 0.0
        for i in range(steps):
            resident = min((i + 1) * chunk, prompt_len)
            pages = -(-resident // page_size)
            bytes_step = 2 * pages * page_size * kv_dim * kvb
            total += (bytes_step / spec.hbm_bw
                      + pages * _GRID_STEP_OVERHEAD_S
                      + _CHUNK_STEP_OVERHEAD_S)
        return total
    raise ValueError(f"unknown chunk mode {mode!r}")


def find_fused_threshold(
    max_seq: int, kv_dim: int, *,
    chunk: int = 64,
    page_size: int = 64,
    kv_dtype: str = "bf16",
    spec: hardware.HardwareSpec = hardware.TPU_V5E,
) -> int:
    """Smallest prompt length at which the fused chunk path beats the
    dense gather (table provisioned at ``max_seq``); prompts below it keep
    the one-compile full-table gather. Returns ``max_seq + 1`` when the
    gather never loses (tiny tables). Quantized ``kv_dtype`` lowers the
    inflection: the fused path's traffic is all stored-width while the
    dense gather still pays full-precision view bytes."""
    p = chunk
    while p <= max_seq:
        t_dense = predict_chunk_prefill_time(
            "dense", p, max_seq, kv_dim, chunk=chunk, page_size=page_size,
            kv_dtype=kv_dtype, spec=spec)
        t_fused = predict_chunk_prefill_time(
            "fused", p, max_seq, kv_dim, chunk=chunk, page_size=page_size,
            kv_dtype=kv_dtype, spec=spec)
        if t_fused < t_dense:
            return p
        p *= 2
    return max_seq + 1


def find_chunk_block(
    max_seq: int, kv_dim: int, *,
    page_size: int = 64,
    kv_dtype: str = "bf16",
    spec: hardware.HardwareSpec = hardware.TPU_V5E,
    candidates: Iterable[int] = CHUNK_BLOCK_CANDIDATES,
) -> int:
    """Pick the prefill chunk size for the fused path at a representative
    (``max_seq``-long) admission: large chunks amortize the per-step
    dispatch bubble, small chunks keep early pages from re-streaming.
    Only sizes that divide the page size are eligible — prefix sharing
    needs every chunk boundary on the share-less page grid
    (``page_size % prefill_chunk == 0``, enforced by the engine)."""
    best, best_t = None, float("inf")
    for c in sorted(candidates):
        if page_size % c:
            continue
        t = predict_chunk_prefill_time(
            "fused", max_seq, max_seq, kv_dim, chunk=c,
            page_size=page_size, kv_dtype=kv_dtype, spec=spec)
        if t < best_t:
            best, best_t = c, t
    if best is None:
        raise ValueError(
            f"no chunk size among {tuple(candidates)} sits on the "
            f"page grid (page_size {page_size})")
    return best


# ---------------------------------------------------------------------------
# Grouped-decode decision flow: per-row prefix reads vs one read per group
# (find_inflections for the shared-prefix decode path)
# ---------------------------------------------------------------------------

def group_stage_overhead(
    spec: hardware.HardwareSpec = hardware.TPU_V5E, *,
    batch: int = 8, q_heads: int = 16, head_dim: int = 128,
) -> float:
    """Fixed cost of the extra grouped-attention stage per decode step,
    derived from the same calibration path as the GEMM cost model rather
    than guessed: one extra kernel launch (the ImplB pipeline-fill
    constant — stage 1 is a second Pallas dispatch the ungrouped path
    does not pay) plus the HBM round-trip of the merge partials the
    split introduces (stage 1 writes, stage 2 reads, one
    ``(batch, q_heads, head_dim + 2)`` f32 record per row — accumulator
    plus the unified-max merge's running (max, sum) pair)."""
    partial_bytes = batch * q_heads * (head_dim + 2) * 4
    return _PIPELINE_FILL_S + 2 * partial_bytes / spec.hbm_bw


# evaluated once at the defaults the group-threshold sweep targets
# (steady decode: full slot batch, qwen2-class head shape)
_GROUP_STAGE_OVERHEAD_S = group_stage_overhead(hardware.TPU_V5E)


def predict_group_decode_time(
    mode: str, members: int, prefix_pages: int, tail_pages: int,
    kv_dim: int, *,
    page_size: int = 64,
    dtype_bytes: int = 2,
    kv_dtype: str = "bf16",
    spec: hardware.HardwareSpec = hardware.TPU_V5E,
) -> float:
    """Roofline time for the KV side of one decode step over one
    shared-prefix group (the q-side work is identical across modes and
    cancels out of the decision). ``kv_dtype`` scales the page bytes both
    modes stream (both read stored-width pages and dequantize
    in-register), so quantization shrinks the absolute gap but leaves
    the fixed stage bubble — grouped needs more members/pages to win.

    ``mode="off"`` streams every member's full table: each of the
    ``members`` rows re-reads the ``prefix_pages`` it shares plus its own
    ``tail_pages``.

    ``mode="grouped"`` reads the shared prefix **once** (stage 1,
    one pass per group) and only the private tails per member (stage 2),
    paying the extra stage's fixed launch/merge bubble — the
    FlashDecoding++ unified-max merge is what makes the split free of a
    per-member rescale pass.
    """
    del dtype_bytes  # superseded by the kv_dtype stored-width scaling
    page_bytes = 2 * page_size * kv_dim * KV_DTYPE_BYTES[kv_dtype]  # K + V
    if mode == "off":
        pages = members * (prefix_pages + tail_pages)
        return (pages * page_bytes / spec.hbm_bw
                + pages * _GRID_STEP_OVERHEAD_S)
    if mode == "grouped":
        pages = prefix_pages + members * tail_pages
        return (pages * page_bytes / spec.hbm_bw
                + pages * _GRID_STEP_OVERHEAD_S
                + _GROUP_STAGE_OVERHEAD_S)
    raise ValueError(f"unknown group mode {mode!r}")


def find_group_threshold(
    kv_dim: int, *,
    page_size: int = 64,
    max_members: int = 64,
    max_prefix_pages: int = 64,
    tail_pages: int = 1,
    kv_dtype: str = "bf16",
    spec: hardware.HardwareSpec = hardware.TPU_V5E,
) -> int:
    """Smallest ``members * prefix_pages`` product at which grouped
    decode beats per-row prefix re-reads — the dispatch floor the slot
    manager's group plan applies per group each tick. Sweeps the
    (members, prefix pages) grid at one private tail page (the
    steady-decode shape); returns a sentinel above the sweep when the
    grouped path never wins (stage bubble dominates tiny pools)."""
    best = None
    for members in range(2, max_members + 1):
        pages = 1
        while pages <= max_prefix_pages:
            t_off = predict_group_decode_time(
                "off", members, pages, tail_pages, kv_dim,
                page_size=page_size, kv_dtype=kv_dtype, spec=spec)
            t_grp = predict_group_decode_time(
                "grouped", members, pages, tail_pages, kv_dim,
                page_size=page_size, kv_dtype=kv_dtype, spec=spec)
            if t_grp < t_off:
                work = members * pages
                if best is None or work < best:
                    best = work
            pages *= 2
    return best if best is not None else max_members * max_prefix_pages + 1


# ---------------------------------------------------------------------------
# Tiered-KV swap decision flow: promote demoted pages vs re-prefill them
# (find_inflections for the session-cache re-admission path)
# ---------------------------------------------------------------------------

# per-batch host-transfer setup: DMA programming + the host-side sync the
# engine's bulk gather/scatter pays once per promotion/demotion batch,
# matching the per-model-call dispatch bubble of the chunk loop (both are
# one host→device round trip of control)
_HOST_COPY_LATENCY_S = _CHUNK_STEP_OVERHEAD_S


def kv_page_bytes(cfg: ModelConfig, *, page_size: int = 64,
                  dtype_bytes: int = 2, kv_dtype: str = "bf16") -> int:
    """Bytes one KV page moves across the host link: K + V for every
    layer (the page id is shared across layers, so a demotion/promotion
    always moves the whole per-layer stack). Quantized dtypes store
    codes at stored width plus one f32 scale per (page, kv head, layer,
    K/V) — the exact slab a tier demotion carries."""
    del dtype_bytes  # superseded by the kv_dtype stored-width scaling
    kvb = KV_DTYPE_BYTES[kv_dtype]
    scale = 0 if kv_dtype == "bf16" else cfg.num_kv_heads * 4
    return int(2 * cfg.num_layers
               * (page_size * cfg.kv_dim * kvb + scale))


def param_bytes(cfg: ModelConfig, weight_dtype: str = "bf16", *,
                dtype_bytes: int = 2) -> int:
    """Resident bytes of the model's per-layer GEMM weight stream at a
    storage precision — the weight-side analog of :func:`kv_page_bytes`.

    Sums every per-layer [K, N] shape across the layer stack: codes at
    stored width (:data:`WEIGHT_DTYPE_BYTES`) plus the (N,) f32
    per-output-channel scales when quantized. ``lm_head`` (and the tied
    embedding) is excluded — it is not a per-layer stream and never
    quantizes — so this is both the resident GEMM weight footprint and
    the exact bytes one decode tick reads (each layer's weights once per
    tick).
    """
    wb = WEIGHT_DTYPE_BYTES[weight_dtype]
    total = 0.0
    for gs in model_gemm_shapes(cfg):
        if gs.name == "lm_head":
            continue
        if weight_dtype == "bf16":
            per = gs.k * gs.n * dtype_bytes
        else:
            per = gs.k * gs.n * wb + gs.n * 4
        total += per * gs.count
    return int(total * cfg.num_layers)


def predict_swap_time(
    pages: int, page_bytes: int, *,
    spec: hardware.HardwareSpec = hardware.TPU_V5E,
) -> float:
    """Roofline time to promote ``pages`` demoted KV pages back to the
    device pool: one bulk host→device copy over the PCIe-class link plus
    the fixed per-batch setup."""
    return _HOST_COPY_LATENCY_S + pages * page_bytes / spec.host_bw


def predict_reprefill_time(
    cfg: ModelConfig, positions: int, *,
    chunk: int = 64,
    page_size: int = 64,
    dtype_bytes: int = 2,
    kv_dtype: str = "bf16",
    weight_dtype: str = "bf16",
    spec: hardware.HardwareSpec = hardware.TPU_V5E,
) -> float:
    """Roofline time to *recompute* ``positions`` KV positions through
    the chunked-prefill path — the cost a re-admission pays for every
    span whose pages were not (or could not be) promoted.

    Sums the model's GEMM work per chunk step (best implementation per
    [K, N] shape, per layer; lm_head once per step) with the fused-path
    attention KV streaming per layer and the per-step dispatch bubble —
    the same per-term constants every other flow in this module uses, so
    the swap decision is commensurable with the chunk/group decisions.
    Under a quantized ``weight_dtype`` the per-layer GEMMs stream the
    smaller stored-width weights (:func:`predict_flat_gemm_time`; the
    lm_head stays bf16), so recompute gets cheaper and swapping needs a
    longer span to win.
    """
    steps = max(-(-positions // chunk), 1)
    gemm_step = 0.0
    for gs in model_gemm_shapes(cfg):
        if weight_dtype != "bf16" and gs.name != "lm_head":
            t = predict_flat_gemm_time(
                chunk, gs.k, gs.n, weight_dtype=weight_dtype,
                dtype_bytes=dtype_bytes, spec=spec)
        else:
            t = min(predict_time(impl, chunk, gs.k, gs.n,
                                 dtype_bytes=dtype_bytes, spec=spec)
                    for impl in Impl)
        layers = 1 if gs.name == "lm_head" else cfg.num_layers
        gemm_step += t * gs.count * layers
    kv = 0.0
    for i in range(steps):
        resident = min((i + 1) * chunk, positions)
        pages = -(-resident // page_size)
        kv += (2 * pages * page_size * cfg.kv_dim
               * KV_DTYPE_BYTES[kv_dtype]
               / spec.hbm_bw + pages * _GRID_STEP_OVERHEAD_S)
    return (steps * gemm_step + cfg.num_layers * kv
            + steps * _CHUNK_STEP_OVERHEAD_S)


def find_swap_threshold(
    cfg: ModelConfig, *,
    chunk: int = 64,
    page_size: int = 64,
    max_pages: int = 64,
    kv_dtype: str = "bf16",
    weight_dtype: str = "bf16",
    spec: hardware.HardwareSpec = hardware.TPU_V5E,
) -> int:
    """Smallest demoted-span page count at which promoting (bulk
    host→device copy) beats re-prefilling the same span — the
    per-admission decision the slot manager applies to a prefix match
    that extends into the tiered store (``PagedPlan.swap_threshold``).
    Re-prefill cost grows superlinearly (attention re-streams resident
    KV per chunk step) while the copy is linear, so the first crossover
    is the inflection. Returns ``max_pages + 1`` when the copy never
    wins inside the sweep (tiny models on a fat link the other way).
    Quantized ``kv_dtype`` moves *both* sides (smaller slabs over the
    link, cheaper KV re-streaming) but the link side scales fully while
    re-prefill keeps its bf16 GEMM term, so swapping wins earlier; a
    quantized ``weight_dtype`` pushes the other way (recompute streams
    the smaller weight slab, so re-prefill gets cheaper)."""
    page_bytes = kv_page_bytes(cfg, page_size=page_size, kv_dtype=kv_dtype)
    for pages in range(1, max_pages + 1):
        t_swap = predict_swap_time(pages, page_bytes, spec=spec)
        t_pre = predict_reprefill_time(
            cfg, pages * page_size, chunk=chunk, page_size=page_size,
            kv_dtype=kv_dtype, weight_dtype=weight_dtype, spec=spec)
        if t_swap < t_pre:
            return pages
    return max_pages + 1


# ---------------------------------------------------------------------------
# Weight-precision decision flow (MatmulPlan.weight_dtype)
# ---------------------------------------------------------------------------

WEIGHT_DTYPE_CANDIDATES = ("bf16", "int8", "fp8")


def find_weight_dtype(
    cfg: ModelConfig, *,
    m: int = 1,
    tol_budget: float | None = None,
    spec: hardware.HardwareSpec = hardware.TPU_V5E,
    candidates: Iterable[str] = WEIGHT_DTYPE_CANDIDATES,
) -> str:
    """Fastest GEMM weight storage precision under the accuracy guard.

    Candidates whose dtype-derived logits tolerance
    (:data:`WEIGHT_GUARD_TOL`) exceeds ``tol_budget`` are excluded
    (``None`` = any tolerance; ``0.0`` admits only the bitwise bf16
    path). Survivors are ranked by one decode tick's flat-GEMM roofline
    summed over the model's [K, N] shapes at decode M
    (:func:`predict_flat_gemm_time`; the lm_head prices at bf16 — it
    never quantizes). Decode is weight-bandwidth-bound, so the smaller
    stream wins whenever it is admissible; the strict ``<`` keeps int8
    ahead of fp8 on their byte-for-byte tie (same stored width, tighter
    analytic round-trip bound).
    """
    best, best_t = "bf16", None
    for wd in candidates:
        if wd not in WEIGHT_DTYPE_BYTES:
            raise ValueError(f"unknown weight_dtype {wd!r}")
        if tol_budget is not None and WEIGHT_GUARD_TOL[wd] > tol_budget:
            continue
        t = 0.0
        for gs in model_gemm_shapes(cfg):
            shape_wd = "bf16" if gs.name == "lm_head" else wd
            layers = 1 if gs.name == "lm_head" else cfg.num_layers
            t += (predict_flat_gemm_time(
                      m, gs.k, gs.n, weight_dtype=shape_wd, spec=spec)
                  * gs.count * layers)
        if best_t is None or t < best_t:
            best, best_t = wd, t
    return best
