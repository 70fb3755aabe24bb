"""ExecutionPlan — one tuned, serializable kernel-dispatch surface (T3
generalized to every op).

The paper's heuristic dataflow (§5) profiles implementations *offline* and
consults a zero-overhead lookup at runtime. The original reproduction did
this for GEMM only; every other implementation decision — sync vs.
unified-max softmax, the overflow-recompute branch, decode ``block_k``,
the chunked-prefill threshold, fused-FFN on/off, Pallas vs. XLA ref — was
a per-call-site flag. :class:`ExecutionPlan` makes implementation
selection a first-class tunable surface spanning the whole graph:

  * a registry of per-op decisions (``matmul`` inflections per [K, N],
    ``attention_decode`` scheme + ``block_k`` + fallback,
    ``attention_prefill`` chunking threshold + φ policy, ``fused_ffn``
    fused/unfused, the paged-path knobs — decode backend/scheme plus
    the chunked-prefill ``gather_chunk`` mode with its tuned
    ``fused_threshold`` / ``chunk_block`` companions — and the
    ``decode_fusion`` stage granularity: split op chain vs. fused
    ingest/epilogue stage kernels vs. the looped whole-depth dispatch);
  * one offline :func:`tune` flow (``measure="analytical"`` roofline
    models in this CPU container, ``measure="wallclock"`` on real
    hardware) that generalizes ``find_inflections`` beyond GEMM;
  * versioned JSON serialization (``plans/<arch>-<hw>.json``) carrying
    provenance — backend, hardware-spec hash, config hash — with
    staleness rejection on load: a plan tuned for different hardware or a
    different architecture refuses to drive a run.

``Ctx``, ``ops.*``, ``Engine`` and the launch CLIs all take exactly one
``plan=`` operand; plans may change *which* kernel runs, never the math
(enforced by the greedy-identity tests).
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
from typing import Callable, Dict, Optional, Tuple, Union

from repro import hardware
from repro.config import ModelConfig
from repro.core import dispatch

PLAN_VERSION = 1

BACKENDS = ("xla", "pallas")
SCHEMES = ("sync", "unified_max")
GATHER_MODES = ("dense", "fused")  # chunk-path page access discipline
GROUP_MODES = ("off", "grouped")   # decode-path shared-prefix discipline
KV_DTYPES = ("bf16", "int8", "fp8")  # paged KV page storage precision
WEIGHT_DTYPES = ("bf16", "int8", "fp8")  # GEMM weight storage precision
FUSION_MODES = ("split", "fused", "looped")  # decode-layer stage granularity


class PlanError(ValueError):
    """Malformed plan document (bad JSON shape, unknown knob value)."""


class StalePlanError(PlanError):
    """Plan provenance does not match the requested run (wrong plan
    version, hardware spec, or model config) — retune instead of serving
    decisions profiled for a different world."""


# ---------------------------------------------------------------------------
# Per-op decision records
# ---------------------------------------------------------------------------


def _check(value: str, allowed: Tuple[str, ...], what: str) -> None:
    if value not in allowed:
        raise PlanError(f"{what} must be one of {allowed}, got {value!r}")


def _check_pos(value: int, what: str) -> None:
    if not isinstance(value, int) or isinstance(value, bool) or value < 1:
        raise PlanError(f"{what} must be a positive int, got {value!r}")


@dataclasses.dataclass(frozen=True)
class MatmulPlan:
    """GEMM routing: tuned [K, N] inflection entries + the default policy
    for unseen shapes (single source of truth for the static ladder that
    used to be duplicated in ``DispatchTable.pick`` and ``ops.matmul``).

    ``weight_dtype`` is the GEMM weight storage precision
    (:data:`WEIGHT_DTYPES`) — the weight-side twin of
    ``PagedPlan.kv_dtype``:

      * ``"bf16"`` — full-precision weight slabs, the legacy bitwise
        path.
      * ``"int8"`` / ``"fp8"`` — the engine's quantize-at-load pass
        (:mod:`repro.models.wquant`) converts every GEMM weight leaf to
        codes plus one f32 step per output channel; the GEMM kernels and
        their jnp oracles dequantize on the f32 accumulator in-register
        (``codes * step`` factored out of the K sum), so every decode
        tick streams ~half the weight bytes and the bf16 slab never
        materializes in HBM. Bias/norm/embedding/lm-head leaves stay
        full precision.

    The precision scales the weight-byte term of the dispatch rooflines
    (:data:`repro.core.dispatch.WEIGHT_DTYPE_BYTES` via
    :func:`repro.core.dispatch.param_bytes`) and is auto-picked by
    :func:`repro.core.dispatch.find_weight_dtype` under the dtype-derived
    logits-closeness guard (``quant.logits_guard_tol`` — the same
    accuracy contract as the KV axis). Quantization changes logits only
    within that tolerance; the bf16 path stays bitwise.
    """

    backend: str = "xla"
    # unseen-shape policy: ImplA below m1, ImplB below m2, ImplC above —
    # the conservative static ladder (GEMV only at M<=2, XLA from M=128)
    default_m1: int = 3
    default_m2: int = 128
    weight_dtype: str = "bf16"
    entries: Dict[Tuple[int, int], dispatch.DispatchEntry] = \
        dataclasses.field(default_factory=dict)

    def __post_init__(self):
        _check(self.backend, BACKENDS, "matmul.backend")
        _check(self.weight_dtype, WEIGHT_DTYPES, "matmul.weight_dtype")
        _check_pos(self.default_m1, "matmul.default_m1")
        _check_pos(self.default_m2, "matmul.default_m2")
        if self.default_m2 < self.default_m1:
            raise PlanError(
                f"matmul default ladder inverted: m1={self.default_m1} > "
                f"m2={self.default_m2}")
        for (k, n), e in self.entries.items():
            if e.m2 < e.m1:
                raise PlanError(
                    f"matmul entry [{k}, {n}] inverted: m1={e.m1} > "
                    f"m2={e.m2}")

    def pick(self, m: int, k: int, n: int) -> dispatch.Impl:
        e = self.entries.get((k, n))
        if e is None:
            return dispatch.pick_impl(m, self.default_m1, self.default_m2)
        return e.pick(m)


@dataclasses.dataclass(frozen=True)
class AttentionDecodePlan:
    """Decode-phase attention: softmax scheme, KV grid block, overflow
    recompute. ``scheme="unified_max"`` is effective only when the model's
    φ config is active (T1 needs a calibrated φ); ``fallback=False`` drops
    the ``lax.cond`` recompute branch (dry-run cost-analysis hygiene)."""

    backend: str = "xla"
    scheme: str = "unified_max"
    block_k: int = 512
    fallback: bool = True

    def __post_init__(self):
        _check(self.backend, BACKENDS, "attention_decode.backend")
        _check(self.scheme, SCHEMES, "attention_decode.scheme")
        _check_pos(self.block_k, "attention_decode.block_k")


@dataclasses.dataclass(frozen=True)
class AttentionPrefillPlan:
    """Prefill-phase attention: softmax scheme, overflow recompute, and
    the sequence threshold above which the XLA path switches from the
    materialized (S, S) scores to the blockwise chunked scheme."""

    backend: str = "xla"
    scheme: str = "unified_max"
    fallback: bool = True
    chunk_threshold: int = 2048

    def __post_init__(self):
        _check(self.backend, BACKENDS, "attention_prefill.backend")
        _check(self.scheme, SCHEMES, "attention_prefill.scheme")
        _check_pos(self.chunk_threshold, "attention_prefill.chunk_threshold")


@dataclasses.dataclass(frozen=True)
class FusedFFNPlan:
    """Gate+up epilogue fusion (T2 extension): ``fused=True`` routes the
    gated MLP through the single fused kernel instead of two dispatched
    GEMMs. Only meaningful on the Pallas backend."""

    backend: str = "xla"
    fused: bool = False

    def __post_init__(self):
        _check(self.backend, BACKENDS, "fused_ffn.backend")


@dataclasses.dataclass(frozen=True)
class PagedPlan:
    """Block-paged KV path knobs: Pallas scalar-prefetch kernels vs. the
    XLA gather view for paged decode, and the chunked-prefill page-access
    discipline.

    ``gather_chunk`` names how chunked prefill reads resident KV:

      * ``"dense"`` — gather the full ``(B, NB*PS)`` per-sequence view
        per layer per chunk step (one compiled shape, but O(table width)
        materialized bytes every step — the pre-fused path).
      * ``"fused"`` — no full-width materialization. On the Pallas
        backend the fused chunk kernel
        (:mod:`repro.kernels.chunk_attention`) reads K/V pages in place
        via scalar-prefetched block tables; on the XLA backend the
        engine bounds the block-table operand to a bucketed
        O(resident pages) width (bitwise identical — trailing masked
        pages contribute exact zeros) so the remaining gather is
        O(resident KV), not O(max_seq).

    ``fused_threshold`` is the tuned gather-vs-fused inflection: prompts
    shorter than it keep the one-compile dense gather (the fused path's
    per-wave shape changes and per-page grid bubbles only pay off once
    enough of the table is *not* resident); prompts at/above it run the
    fused discipline. ``chunk_block`` is the tuned prefill chunk size
    (``Engine(prefill_chunk=None)`` adopts it); it must divide the page
    size so prefix-sharing chunk boundaries stay on the share-less grid.
    ``decode_group`` names how decode attention treats sequences whose
    block tables share refcounted prefix pages:

      * ``"off"`` — every row re-reads its full table (shared pages are
        deduplicated in *storage* only).
      * ``"grouped"`` — the engine hands the attention op a per-tick
        group plan; the shared prefix's attention is computed **once per
        group** and merged into each member's private tail via the
        unified-max combine (no per-member rescale), so N-way sharing
        reads the prefix KV once instead of N times.

    ``group_threshold`` is the tuned dispatch floor: a group is only
    worth the extra kernel stage when ``members * prefix_pages`` reaches
    it (below that the stage overhead beats the saved KV reads). Tuned
    by :func:`repro.core.dispatch.find_group_threshold`; the other knobs
    by :func:`repro.core.dispatch.find_fused_threshold` /
    :func:`repro.core.dispatch.find_chunk_block`.

    ``swap_threshold`` is the tiered-KV swap-vs-re-prefill inflection:
    at re-admission, a prefix match that extends into demoted (host/disk)
    pages is promoted — one bulk host→device copy — only when the
    demoted span reaches this many pages; below it the match truncates
    at the first demoted entry and those positions re-prefill (the
    PCIe-class copy's fixed setup beats recompute only past the
    crossover). Tuned by :func:`repro.core.dispatch.find_swap_threshold`.

    ``kv_dtype`` is the page storage precision (:data:`KV_DTYPES`):

      * ``"bf16"`` — full-precision pages, the legacy bit-identical path.
      * ``"int8"`` / ``"fp8"`` — pages store quantized codes plus one
        f32 scale per (page, kv head) in a parallel scale pool
        (:mod:`repro.serving.kvquant`); the decode / chunk / group
        kernels dequantize in place, so every KV read moves ~half the
        bytes and the same pool budget holds ~2x the resident tokens.

    The precision scales every KV-byte term in the dispatch rooflines
    (:data:`repro.core.dispatch.KV_DTYPE_BYTES`): smaller pages shift
    ``fused_threshold`` (the gather's O(resident-KV) bytes shrink),
    ``group_threshold`` (the prefix re-read a group saves is cheaper, so
    the stage overhead needs more members/pages to pay off) and
    ``swap_threshold`` (a demoted span moves fewer bytes over the host
    link, so swapping wins earlier). Quantization changes logits only
    within a dtype-derived tolerance, enforced by the logits-closeness
    guard tests — never which tokens a plan may legally produce beyond
    that tolerance.
    """

    backend: str = "xla"
    scheme: str = "unified_max"
    fallback: bool = True
    gather_chunk: str = "dense"
    fused_threshold: int = 256
    chunk_block: int = 64
    decode_group: str = "off"
    group_threshold: int = 2
    swap_threshold: int = 1
    kv_dtype: str = "bf16"

    def __post_init__(self):
        _check(self.backend, BACKENDS, "paged.backend")
        _check(self.scheme, SCHEMES, "paged.scheme")
        _check(self.gather_chunk, GATHER_MODES, "paged.gather_chunk")
        _check_pos(self.fused_threshold, "paged.fused_threshold")
        _check_pos(self.chunk_block, "paged.chunk_block")
        _check(self.decode_group, GROUP_MODES, "paged.decode_group")
        _check_pos(self.group_threshold, "paged.group_threshold")
        _check_pos(self.swap_threshold, "paged.swap_threshold")
        _check(self.kv_dtype, KV_DTYPES, "paged.kv_dtype")


@dataclasses.dataclass(frozen=True)
class DecodeFusionPlan:
    """Decode-layer fusion granularity (the kernel-looping axis).

    Per-token decode is a chain of many small memory-bound ops per layer;
    past the paged/quantized KV work the dominant small-batch cost is the
    dispatch + synchronization boundary *between* them. ``granularity``
    names how much of the per-layer chain one dispatch claims
    (:data:`FUSION_MODES`):

      * ``"split"`` — today's op chain: every stage (norm, QKV, rope,
        scatter, attention, o_proj, residual, FFN) is its own dispatch,
        the whole depth under one ``lax.scan``. The reference path,
        bit-identical by definition.
      * ``"fused"`` — the memory-bound seams collapse into fused stage
        kernels (``ops.decode_ingest`` = norm→QKV→bias→rope,
        ``ops.oproj_residual`` = GEMM-into-residual, serving both the
        o_proj and FFN down-projection epilogues, and ``ops.ffn_norm``
        = mlp_norm→gate/up→activation), with the layer loop
        python-unrolled — L traced layer bodies, each a short fused
        chain.
      * ``"looped"`` — the same fused stage dispatch with the stacked-L
        params run under one ``lax.scan`` (:mod:`repro.models.stack`):
        the layer body is traced once and the whole depth is a single
        host-visible looped dispatch — the Kernel Looping shape.

    The fused stage *kernels* are Pallas-only; on the XLA backend the
    ``fused``/``looped`` granularities dispatch the jnp oracles
    (``ref.decode_ingest_ref`` / ``ref.oproj_residual_ref`` /
    ``ref.ffn_norm_ref``), which compose exactly the split chain's
    expressions in the same order.
    ``split`` and ``looped`` therefore produce bit-identical logits on
    XLA (same scan, same jaxpr per stage — tier-1 enforced). ``fused``
    is the one documented reassociated seam: python-unrolling the L
    layer bodies lets XLA place bf16 rounding at different fusion
    boundaries than the scan body, so it is held to the scheme-swap
    dtype-eps value-closeness bound instead (greedy tokens still agree
    wherever the argmax is decisive). The Pallas kernels additionally
    reassociate the K-streamed GEMM accumulation (f32 tile
    accumulators), so kernel-vs-oracle equality is bounded by the same
    dtype-eps closeness tests, like every other Pallas GEMM in the
    repo. Tuned by
    :func:`repro.core.dispatch.find_decode_fusion` from the
    :func:`repro.core.dispatch.predict_fusion_time` roofline (per-layer
    stage-dispatch count × pipeline fill vs. the scan's one-time loop
    setup).
    """

    backend: str = "xla"
    granularity: str = "split"

    def __post_init__(self):
        _check(self.backend, BACKENDS, "decode_fusion.backend")
        _check(self.granularity, FUSION_MODES, "decode_fusion.granularity")


# ---------------------------------------------------------------------------
# Provenance
# ---------------------------------------------------------------------------


def _digest(obj) -> str:
    return hashlib.sha256(
        json.dumps(obj, sort_keys=True, default=str).encode()
    ).hexdigest()[:12]


def hardware_hash(spec: hardware.HardwareSpec) -> str:
    return _digest(dataclasses.asdict(spec))


def config_hash(cfg: ModelConfig) -> str:
    return _digest(dataclasses.asdict(cfg))


@dataclasses.dataclass(frozen=True)
class PlanProvenance:
    """Where a tuned plan came from — checked on load."""

    backend: str
    hardware: str        # hardware_hash(spec)
    hardware_name: str
    config: str          # config_hash(cfg)
    config_name: str
    measure: str         # analytical | wallclock | custom
    version: int = PLAN_VERSION


# ---------------------------------------------------------------------------
# The plan
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
    matmul: MatmulPlan = dataclasses.field(default_factory=MatmulPlan)
    attention_decode: AttentionDecodePlan = dataclasses.field(
        default_factory=AttentionDecodePlan)
    attention_prefill: AttentionPrefillPlan = dataclasses.field(
        default_factory=AttentionPrefillPlan)
    fused_ffn: FusedFFNPlan = dataclasses.field(default_factory=FusedFFNPlan)
    paged: PagedPlan = dataclasses.field(default_factory=PagedPlan)
    decode_fusion: DecodeFusionPlan = dataclasses.field(
        default_factory=DecodeFusionPlan)
    provenance: Optional[PlanProvenance] = None

    # -- bulk knob overrides -------------------------------------------------

    def with_overrides(
        self,
        *,
        backend: Optional[str] = None,
        scheme: Optional[str] = None,
        fallback: Optional[bool] = None,
        block_k: Optional[int] = None,
    ) -> "ExecutionPlan":
        """Return a copy with shared knobs overridden across every op that
        carries them (``None`` keeps the existing decision). Used by hosts
        with hard constraints — e.g. the dry-run forces ``backend="xla"``
        (Mosaic does not lower on CPU) and ``fallback=False`` (no
        ``lax.cond`` double-count in cost analysis)."""
        def sub(p, **fields):
            fields = {k: v for k, v in fields.items() if v is not None}
            return dataclasses.replace(p, **fields) if fields else p

        fused = None
        if backend is not None and backend != "pallas":
            fused = False   # the fused epilogue kernel is Pallas-only
        return dataclasses.replace(
            self,
            matmul=sub(self.matmul, backend=backend),
            attention_decode=sub(self.attention_decode, backend=backend,
                                 scheme=scheme, fallback=fallback,
                                 block_k=block_k),
            attention_prefill=sub(self.attention_prefill, backend=backend,
                                  scheme=scheme, fallback=fallback),
            fused_ffn=sub(self.fused_ffn, backend=backend, fused=fused),
            paged=sub(self.paged, backend=backend, scheme=scheme,
                      fallback=fallback),
            # granularity survives a backend override: on XLA the fused
            # stages dispatch their bit-identical jnp oracles
            decode_fusion=sub(self.decode_fusion, backend=backend),
        )

    def describe(self) -> str:
        d, p = self.attention_decode, self.attention_prefill
        return (f"matmul[{len(self.matmul.entries)} entries, "
                f"{self.matmul.backend}"
                + (f", w={self.matmul.weight_dtype}"
                   if self.matmul.weight_dtype != "bf16" else "")
                + "] "
                f"decode[{d.scheme}, block_k={d.block_k}, "
                f"fallback={d.fallback}] "
                f"prefill[{p.scheme}, chunk>={p.chunk_threshold}] "
                f"ffn[{'fused' if self.fused_ffn.fused else 'unfused'}] "
                f"paged[{self.paged.backend}/{self.paged.gather_chunk}"
                + (f">={self.paged.fused_threshold}"
                   if self.paged.gather_chunk == "fused" else "")
                + f", chunk={self.paged.chunk_block}"
                + (f", group>={self.paged.group_threshold}"
                   if self.paged.decode_group == "grouped" else "")
                + f", swap>={self.paged.swap_threshold}"
                + (f", kv={self.paged.kv_dtype}"
                   if self.paged.kv_dtype != "bf16" else "")
                + "] "
                f"fusion[{self.decode_fusion.granularity}]")

    # -- serialization -------------------------------------------------------

    def to_json(self) -> str:
        doc = {
            "version": PLAN_VERSION,
            "ops": {
                "matmul": {
                    "backend": self.matmul.backend,
                    "weight_dtype": self.matmul.weight_dtype,
                    "default": {"m1": self.matmul.default_m1,
                                "m2": self.matmul.default_m2},
                    "entries": {
                        f"{k},{n}": {"m1": e.m1, "m2": e.m2}
                        for (k, n), e in sorted(self.matmul.entries.items())
                    },
                },
                "attention_decode": dataclasses.asdict(self.attention_decode),
                "attention_prefill": dataclasses.asdict(
                    self.attention_prefill),
                "fused_ffn": dataclasses.asdict(self.fused_ffn),
                "paged": dataclasses.asdict(self.paged),
                "decode_fusion": dataclasses.asdict(self.decode_fusion),
            },
        }
        if self.provenance is not None:
            doc["provenance"] = dataclasses.asdict(self.provenance)
        return json.dumps(doc, indent=2)

    @staticmethod
    def from_json(s: str) -> "ExecutionPlan":
        try:
            doc = json.loads(s)
        except json.JSONDecodeError as e:
            raise PlanError(f"plan is not valid JSON: {e}") from e
        if not isinstance(doc, dict) or "ops" not in doc:
            raise PlanError("plan document has no 'ops' registry")
        version = doc.get("version")
        if version != PLAN_VERSION:
            raise StalePlanError(
                f"plan version {version!r} != supported {PLAN_VERSION}")
        ops = doc["ops"]
        try:
            mm = ops["matmul"]
            entries = {}
            for key, d in mm.get("entries", {}).items():
                k, n = (int(x) for x in key.split(","))
                entries[(k, n)] = dispatch.DispatchEntry(
                    k=k, n=n, m1=int(d["m1"]), m2=int(d["m2"]))
            matmul = MatmulPlan(
                backend=mm["backend"],
                # pre-wquant plans load with the bf16 default
                weight_dtype=mm.get("weight_dtype", "bf16"),
                default_m1=int(mm["default"]["m1"]),
                default_m2=int(mm["default"]["m2"]),
                entries=entries,
            )
            plan = ExecutionPlan(
                matmul=matmul,
                attention_decode=AttentionDecodePlan(
                    **ops["attention_decode"]),
                attention_prefill=AttentionPrefillPlan(
                    **ops["attention_prefill"]),
                fused_ffn=FusedFFNPlan(**ops["fused_ffn"]),
                paged=PagedPlan(**ops["paged"]),
                # pre-fusion plans load with the split default
                decode_fusion=DecodeFusionPlan(
                    **ops.get("decode_fusion", {})),
            )
        except (KeyError, TypeError, ValueError) as e:
            if isinstance(e, PlanError):
                raise
            raise PlanError(f"malformed plan ops registry: {e!r}") from e
        prov = doc.get("provenance")
        if prov is not None:
            try:
                plan = dataclasses.replace(
                    plan, provenance=PlanProvenance(**prov))
            except TypeError as e:
                raise PlanError(
                    f"malformed plan provenance: {e!r}") from e
        return plan

    def save(self, path: str) -> str:
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            f.write(self.to_json())
        return path

    @staticmethod
    def load(
        path: str,
        *,
        cfg: Optional[ModelConfig] = None,
        spec: Optional[hardware.HardwareSpec] = hardware.TPU_V5E,
        strict: bool = True,
    ) -> "ExecutionPlan":
        """Load a plan, rejecting stale artifacts.

        ``strict=True`` (the default) refuses plans without provenance and
        plans whose recorded hardware/config hash differs from the
        requested ``spec``/``cfg`` (pass ``cfg=None``/``spec=None`` to
        skip that axis). ``strict=False`` loads anything structurally
        valid — for inspection tooling, never for serving.
        """
        with open(path) as f:
            plan = ExecutionPlan.from_json(f.read())
        if not strict:
            return plan
        prov = plan.provenance
        if prov is None:
            raise StalePlanError(
                f"{path}: plan has no provenance; tune one with "
                "repro.core.plan.tune (or load with strict=False)")
        if spec is not None and prov.hardware != hardware_hash(spec):
            raise StalePlanError(
                f"{path}: tuned for hardware {prov.hardware_name} "
                f"[{prov.hardware}], run targets {spec.name} "
                f"[{hardware_hash(spec)}] — retune")
        if cfg is not None and prov.config != config_hash(cfg):
            raise StalePlanError(
                f"{path}: tuned for config {prov.config_name} "
                f"[{prov.config}], run uses {cfg.name} "
                f"[{config_hash(cfg)}] — retune")
        return plan


DEFAULT_PLAN = ExecutionPlan()


def make_plan(
    backend: str = "xla",
    *,
    scheme: str = "unified_max",
    fallback: bool = True,
    block_k: int = 512,
    chunk_threshold: int = 2048,
    fused_ffn: Optional[bool] = None,
    gather_chunk: str = "dense",
    fused_threshold: int = 256,
    chunk_block: int = 64,
    decode_group: str = "off",
    group_threshold: int = 2,
    swap_threshold: int = 1,
    kv_dtype: str = "bf16",
    weight_dtype: str = "bf16",
    decode_fusion: str = "split",
) -> ExecutionPlan:
    """Build an untuned plan with uniform knobs — the hand-rolled
    counterpart of :func:`tune` for hosts that only need to pin backends
    or drop fallbacks (benchmarks, the dry-run, tests)."""
    if fused_ffn is None:
        fused_ffn = backend == "pallas"
    return ExecutionPlan(
        matmul=MatmulPlan(backend=backend, weight_dtype=weight_dtype),
        attention_decode=AttentionDecodePlan(
            backend=backend, scheme=scheme, fallback=fallback,
            block_k=block_k),
        attention_prefill=AttentionPrefillPlan(
            backend=backend, scheme=scheme, fallback=fallback,
            chunk_threshold=chunk_threshold),
        fused_ffn=FusedFFNPlan(backend=backend, fused=fused_ffn),
        paged=PagedPlan(backend=backend, scheme=scheme, fallback=fallback,
                        gather_chunk=gather_chunk,
                        fused_threshold=fused_threshold,
                        chunk_block=chunk_block,
                        decode_group=decode_group,
                        group_threshold=group_threshold,
                        swap_threshold=swap_threshold,
                        kv_dtype=kv_dtype),
        decode_fusion=DecodeFusionPlan(backend=backend,
                                       granularity=decode_fusion),
    )


# ---------------------------------------------------------------------------
# Offline tuning flow (generalizes find_inflections beyond GEMM)
# ---------------------------------------------------------------------------


MeasureLike = Union[str, dispatch.MeasureFn, None]


def _resolve_measure(measure: MeasureLike):
    """-> (gemm measure fn | None, provenance label)."""
    if measure is None or measure == "analytical":
        return None, "analytical"
    if measure == "wallclock":
        return dispatch.wallclock_measure_factory(), "wallclock"
    if callable(measure):
        return measure, "custom"
    raise PlanError(
        f"measure must be 'analytical', 'wallclock', or a callable; "
        f"got {measure!r}")


def tune(
    cfg: ModelConfig,
    spec: hardware.HardwareSpec = hardware.TPU_V5E,
    *,
    measure: MeasureLike = "analytical",
    backend: str = "xla",
    decode_seq: int = 32768,
    page_size: int = 64,
    kv_dtype: str = "bf16",
    weight_dtype: Optional[str] = "bf16",
) -> ExecutionPlan:
    """Profile every op decision offline and emit a provenanced plan.

    GEMM inflections come from ``measure`` (the paper's Fig. 9(b) flow —
    analytical roofline here, wallclock on real hardware; attention/FFN
    decisions always use the analytical models, which is what the
    wallclock backend can't reach without a device anyway). ``decode_seq``
    is the representative decode KV length the ``block_k`` sweep
    optimizes for; ``page_size`` anchors the paged chunked-prefill
    decisions (``chunk_block`` and the dense-gather vs fused-kernel
    ``fused_threshold`` inflection). ``kv_dtype`` selects the page
    precision and rescales every KV-byte roofline term the paged
    thresholds come from (see :class:`PagedPlan`). ``weight_dtype``
    selects the GEMM weight storage precision (see :class:`MatmulPlan`);
    pass ``None`` to let :func:`repro.core.dispatch.find_weight_dtype`
    pick the fastest candidate whose dtype-derived guard tolerance the
    run accepts — the resolved value rescales the weight-byte terms of
    the swap and fusion rooflines via
    :func:`repro.core.dispatch.param_bytes`.
    """
    _check(backend, BACKENDS, "backend")
    _check(kv_dtype, KV_DTYPES, "kv_dtype")
    if weight_dtype is None:
        weight_dtype = dispatch.find_weight_dtype(cfg, spec=spec)
    _check(weight_dtype, WEIGHT_DTYPES, "weight_dtype")
    gemm_measure, measure_name = _resolve_measure(measure)

    entries: Dict[Tuple[int, int], dispatch.DispatchEntry] = {}
    for gs in dispatch.model_gemm_shapes(cfg):
        if (gs.k, gs.n) not in entries:
            entries[(gs.k, gs.n)] = dispatch.find_inflections(
                gs.k, gs.n, measure=gemm_measure, spec=spec)
    # the unseen-shape policy is itself tuned: a representative square
    # [d_model, d_model] workload stands in for shapes the sweep missed
    default = dispatch.find_inflections(
        cfg.d_model, cfg.d_model, measure=gemm_measure, spec=spec)

    scheme = "unified_max" if cfg.softmax_phi.active else "sync"
    block_k = dispatch.find_block_k(
        min(decode_seq, cfg.max_seq_len), cfg.kv_dim, spec=spec)
    threshold = dispatch.find_chunk_threshold(cfg.num_heads, spec=spec)
    rep_seq = min(decode_seq, cfg.max_seq_len)
    chunk_block = dispatch.find_chunk_block(
        rep_seq, cfg.kv_dim, page_size=page_size, spec=spec,
        kv_dtype=kv_dtype)
    fused_threshold = dispatch.find_fused_threshold(
        rep_seq, cfg.kv_dim, chunk=chunk_block, page_size=page_size,
        spec=spec, kv_dtype=kv_dtype)
    group_threshold = dispatch.find_group_threshold(
        cfg.kv_dim, page_size=page_size, spec=spec, kv_dtype=kv_dtype)
    swap_threshold = dispatch.find_swap_threshold(
        cfg, chunk=chunk_block, page_size=page_size, spec=spec,
        kv_dtype=kv_dtype, weight_dtype=weight_dtype)
    granularity = dispatch.find_decode_fusion(cfg, spec=spec,
                                              weight_dtype=weight_dtype)

    plan = ExecutionPlan(
        matmul=MatmulPlan(backend=backend, default_m1=default.m1,
                          default_m2=default.m2, entries=entries,
                          weight_dtype=weight_dtype),
        attention_decode=AttentionDecodePlan(
            backend=backend, scheme=scheme, block_k=block_k),
        attention_prefill=AttentionPrefillPlan(
            backend=backend, scheme=scheme, chunk_threshold=threshold),
        fused_ffn=FusedFFNPlan(
            backend=backend,
            fused=backend == "pallas"
            and cfg.activation in ("swiglu", "geglu")),
        paged=PagedPlan(backend=backend, scheme=scheme,
                        gather_chunk="fused",
                        fused_threshold=fused_threshold,
                        chunk_block=chunk_block,
                        decode_group="grouped",
                        group_threshold=group_threshold,
                        swap_threshold=swap_threshold,
                        kv_dtype=kv_dtype),
        decode_fusion=DecodeFusionPlan(backend=backend,
                                       granularity=granularity),
        provenance=PlanProvenance(
            backend=backend,
            hardware=hardware_hash(spec), hardware_name=spec.name,
            config=config_hash(cfg), config_name=cfg.name,
            measure=measure_name),
    )
    return plan


def default_plan_path(
    cfg: ModelConfig,
    spec: hardware.HardwareSpec = hardware.TPU_V5E,
    root: str = "plans",
) -> str:
    """The versioned artifact location: ``plans/<arch>-<hw>.json``."""
    return os.path.join(root, f"{cfg.name}-{spec.name}.json")
