"""Three-term roofline from dry-run artifacts (TPU v5e target).

  compute    = HLO_FLOPs        / (chips x peak_FLOP/s)
  memory     = HLO_bytes        / (chips x HBM_bw)
  collective = collective_bytes / (chips x link_bw)

FLOPs/bytes come from ``compiled.cost_analysis()``; because XLA reports a
``lax.scan`` body once, the dry-run lowers two small *unrolled probes*
(L=1 and L=3) per cell and this module linearly decomposes

  total(L) = outside + L x per_layer

which is exact since every layer is identical. Collective bytes come from
:mod:`repro.analysis.hlo` over the probe HLO (flat, no while loops), scaled
the same way. The full-depth scan model is separately compiled as the
fit/shard proof (memory_analysis).
"""
from __future__ import annotations

import dataclasses
import json
from typing import Optional

from repro import hardware


@dataclasses.dataclass
class ProbeCost:
    """cost_analysis + collective bytes of one lowered probe."""

    num_layers: int
    flops: float
    bytes_accessed: float
    collective_bytes: float
    # measured on the sharded program; all values are *global* (all chips)


@dataclasses.dataclass
class RooflineTerms:
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_flops: float
    hlo_bytes: float
    collective_bytes: float
    compute_s: float
    memory_s: float
    collective_s: float
    model_flops: float
    useful_ratio: float       # MODEL_FLOPS / HLO_FLOPs
    bottleneck: str
    bound_s: float            # max of the three terms
    per_device_bytes: Optional[int] = None  # from memory_analysis

    def to_dict(self):
        return dataclasses.asdict(self)


def extrapolate(probes: list[ProbeCost], num_layers: int) -> ProbeCost:
    """Linear L-decomposition from two probes; exact for identical layers.

    FLOPs/bytes come from the pre-SPMD module and are exactly linear.
    Collective bytes come from the *compiled* per-device module, where
    GSPMD occasionally flips strategy between probe depths — both the
    per-layer slope and the depth-0 intercept are clamped at 0 so a
    strategy flip can never produce a negative projection.
    """
    assert len(probes) >= 2
    a, b = probes[0], probes[-1]
    dl = b.num_layers - a.num_layers
    assert dl > 0

    def project(va: float, vb: float, *, clamp: bool) -> float:
        per_layer = (vb - va) / dl
        if clamp:
            per_layer = max(per_layer, 0.0)
        out = va - a.num_layers * per_layer
        if clamp:
            out = max(out, 0.0)
        return out + num_layers * per_layer

    return ProbeCost(
        num_layers=num_layers,
        flops=project(a.flops, b.flops, clamp=False),
        bytes_accessed=project(a.bytes_accessed, b.bytes_accessed,
                               clamp=False),
        collective_bytes=project(a.collective_bytes, b.collective_bytes,
                                 clamp=True),
    )


def model_flops_estimate(
    *, params_active: int, tokens: int, kind: str,
    kv_len: int = 0, num_layers: int = 0, d_model: int = 0,
    num_kv_heads: int = 0, head_dim: int = 0, num_q_heads: int = 0,
    seq_len: int = 0,
) -> float:
    """MODEL_FLOPS: 6·N·D for training, 2·N·D for fwd-only (+ attention).

    Attention score/value FLOPs are added explicitly since 6ND ignores them
    (they matter at 32k+ context).
    """
    base = (6.0 if kind == "train" else 2.0) * params_active * tokens
    attn = 0.0
    if num_layers and num_q_heads:
        if kind == "decode":
            # one new token vs kv_len cache
            attn = (
                num_layers * tokens * num_q_heads * head_dim * kv_len * 2 * 2.0
            )
        else:
            # causal prefill/train: S^2/2 per head pair, x2 matmuls
            attn = (
                num_layers * tokens * num_q_heads * head_dim * seq_len * 0.5
                * 2 * 2.0
            )
            if kind == "train":
                attn *= 3  # fwd + 2x bwd
    return base + attn


def terms_from(
    *, arch: str, shape: str, mesh: str, chips: int,
    cost: ProbeCost, model_flops: float,
    per_device_bytes: Optional[int] = None,
    spec: hardware.HardwareSpec = hardware.TPU_V5E,
) -> RooflineTerms:
    compute_s = cost.flops / (chips * spec.peak_flops_bf16)
    memory_s = cost.bytes_accessed / (chips * spec.hbm_bw)
    collective_s = cost.collective_bytes / (chips * spec.ici_bw_per_link)
    terms = {
        "compute": compute_s, "memory": memory_s, "collective": collective_s
    }
    bottleneck = max(terms, key=terms.get)
    return RooflineTerms(
        arch=arch, shape=shape, mesh=mesh, chips=chips,
        hlo_flops=cost.flops, hlo_bytes=cost.bytes_accessed,
        collective_bytes=cost.collective_bytes,
        compute_s=compute_s, memory_s=memory_s, collective_s=collective_s,
        model_flops=model_flops,
        useful_ratio=model_flops / cost.flops if cost.flops else 0.0,
        bottleneck=bottleneck, bound_s=terms[bottleneck],
        per_device_bytes=per_device_bytes,
    )


def save_report(path: str, rows: list[RooflineTerms]) -> None:
    with open(path, "w") as f:
        json.dump([r.to_dict() for r in rows], f, indent=2)


def load_report(path: str) -> list[dict]:
    with open(path) as f:
        return json.load(f)


def format_table(rows: list[dict]) -> str:
    hdr = (
        f"{'arch':<16}{'shape':<13}{'mesh':<10}{'compute_s':>12}"
        f"{'memory_s':>12}{'collect_s':>12}{'bottleneck':>12}"
        f"{'useful':>8}{'GB/dev':>8}"
    )
    lines = [hdr, "-" * len(hdr)]
    for r in rows:
        gb = (r.get("per_device_bytes") or 0) / 2**30
        lines.append(
            f"{r['arch']:<16}{r['shape']:<13}{r['mesh']:<10}"
            f"{r['compute_s']:>12.4e}{r['memory_s']:>12.4e}"
            f"{r['collective_s']:>12.4e}{r['bottleneck']:>12}"
            f"{r['useful_ratio']:>8.2f}{gb:>8.2f}"
        )
    return "\n".join(lines)
