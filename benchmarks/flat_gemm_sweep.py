"""Flat-GEMM B_N trade-off (paper Fig. 7 + Eq. 5), on TPU-v5e terms.

For M=8 and the paper's spread of N sizes, sweep the N-tile B_N and report
the Eq.-5 compute/memory ratio, the grid parallelism N/B_N, the kernel's
double-buffered VMEM claim, and the roofline-model time. The Fig.-7 shape
reproduces: small N is parallelism-bound (best B_N keeps N/B_N near the
pipeline depth), large N becomes memory-bound (bigger B_N amortizes the
A-tile reload until VMEM caps it). The chosen tile of `pick_bn` is marked.
"""
from __future__ import annotations

from benchmarks.common import fmt_row
from repro import hardware
from repro.kernels.flat_gemm import pick_bk, pick_bn

SPEC = hardware.TPU_V5E


def eq5_ratio(m: int, k: int, bn: int) -> float:
    """Paper Eq. 5: compute/memory ratio of the tiled flat GEMM."""
    return 2.0 * m * k / (k + m * k / bn + m)


def model_time(m: int, n: int, k: int, bn: int, bk: int,
               dtype_bytes: int = 2) -> float:
    """HBM-roofline time of one flat GEMM with tiles (bn, bk) + pipeline
    fill bubble per N-stripe (the Mosaic grid analogue of Fig. 7)."""
    m_pad = max(8, -(-m // 8) * 8)
    bytes_moved = (m * k + k * n + m * n) * dtype_bytes
    mem = bytes_moved / SPEC.hbm_bw
    compute = 2 * m_pad * n * k / SPEC.peak_flops_bf16
    n_stripes = max(n // bn, 1)
    bubble = 2e-6 * max(1.0, 8.0 / n_stripes)  # under-filled pipeline
    return max(mem, compute) + bubble


def run(quick: bool = False) -> list[dict]:
    print("\n== flat_gemm_sweep: Eq.-5 trade-off, M=8, K=4096 (Fig. 7) ==")
    rows = []
    m, k = 8, 4096
    ns = (4096, 11008) if quick else (1024, 4096, 11008, 28672)
    bns = (128, 256, 512, 1024, 2048)
    hdr = ["N \\ B_N"] + [str(b) for b in bns] + ["pick_bn"]
    print(fmt_row(*hdr, widths=[10] + [11] * len(bns) + [9]))
    for n in ns:
        cells = []
        for bn in bns:
            if n % bn:
                cells.append("-")
                continue
            bk = pick_bk(m, bn, k)
            t = model_time(m, n, k, bn, bk)
            vmem = (2 * (8 * bk + bk * bn) * 2 + 8 * bn * 4) / 2**20
            cells.append(f"{t*1e6:.1f}us/{vmem:.0f}M")
            rows.append(dict(n=n, bn=bn, bk=bk, time_us=t * 1e6,
                             vmem_mb=vmem, ratio=eq5_ratio(m, k, bn)))
        chosen = pick_bn(m, n, k)
        print(fmt_row(n, *cells, chosen, widths=[10] + [11] * len(bns) + [9]))
    print("  (cell = modeled time / double-buffered VMEM claim; "
          "'-' = B_N does not divide N)")

    # the "pad to 8 not 64" accounting (the headline T2 claim)
    print("\n  M-padding waste, M=8 flat GEMM:")
    for pad_to in (8, 64, 128):
        waste = (pad_to - m) / pad_to * 100
        print(f"    pad M->{pad_to:<4} wasted MXU issue slots: {waste:.0f}%")
    rows.append(dict(pad8_waste=0.0, pad64_waste=87.5, pad128_waste=93.75))
    return rows


if __name__ == "__main__":
    run()
