"""Plan-tuning sweep: the heuristic dataflow generalized to every op.

For each assigned architecture this runs the offline :func:`repro.core.
plan.tune` flow (paper Fig. 9 for GEMM, plus the decode ``block_k`` and
prefill chunk-threshold decision flows) on the v5e analytical backend —
the real-TPU wallclock backend plugs into the same flow — printing the
[K, N] inflection points M1 (ImplA->ImplB) and M2 (ImplB->ImplC) and the
per-op decisions, and asserting the serialization round-trip is identity.

Writes ``BENCH_dispatch.json`` at the repo root so later PRs can track
the trajectory (schema: {"rows": [...], "plans": {...}, "config": {...}},
matching BENCH_paged/BENCH_sched).
"""
from __future__ import annotations

import json
import os

from benchmarks.common import fmt_row, write_artifact
from repro import configs, hardware
from repro.core import dispatch as dsp
from repro.core import plan as plan_mod

OUT_PATH = os.path.join(os.path.dirname(__file__), "..",
                        "BENCH_dispatch.json")


def run(quick: bool = False) -> dict:
    print("\n== dispatch_table: plan tuning sweep (T3, Fig. 9) ==")
    rows = []
    plans = {}
    archs = ["llama2-7b"] if quick else [
        "llama2-7b", "qwen2-0.5b", "dbrx-132b", "rwkv6-1.6b"]
    for arch in archs:
        cfg = configs.get(arch)
        plan = plan_mod.tune(cfg)
        # serialization must be identity — a tuned plan is an artifact
        assert plan_mod.ExecutionPlan.from_json(plan.to_json()) == plan
        print(f"  {arch}: {plan.describe()}")
        print(fmt_row("    workload", "[K, N]", "M1(A->B)", "M2(B->C)",
                      widths=[18, 18, 10, 10]))
        seen = set()
        for gs in dsp.model_gemm_shapes(cfg):
            if (gs.k, gs.n) in seen:
                continue
            seen.add((gs.k, gs.n))
            e = plan.matmul.entries[(gs.k, gs.n)]
            print(fmt_row(f"    {gs.name}", f"[{gs.k}, {gs.n}]", e.m1, e.m2,
                          widths=[18, 18, 10, 10]))
            rows.append(dict(arch=arch, name=gs.name, k=gs.k, n=gs.n,
                             m1=e.m1, m2=e.m2))
        plans[arch] = dict(
            default_m1=plan.matmul.default_m1,
            default_m2=plan.matmul.default_m2,
            decode_scheme=plan.attention_decode.scheme,
            decode_block_k=plan.attention_decode.block_k,
            prefill_chunk_threshold=plan.attention_prefill.chunk_threshold,
            fused_ffn=plan.fused_ffn.fused,
            provenance=plan.provenance.config,
        )

    result = {
        "config": dict(spec=hardware.TPU_V5E.name,
                       hardware=plan_mod.hardware_hash(hardware.TPU_V5E),
                       measure="analytical", archs=archs),
        "rows": rows,
        "plans": plans,
    }
    path = write_artifact(OUT_PATH, result, quick)
    print(f"  [dispatch_table -> {os.path.normpath(path)}]")
    return result


if __name__ == "__main__":
    run()
