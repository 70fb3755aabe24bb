"""Run every benchmark: ``PYTHONPATH=src python -m benchmarks.run``.

``--quick`` trims sweep sizes (used by CI-style smoke checks). Quick runs
write ``BENCH_*.quick.json`` sidecars and an ``artifacts/
bench_results.quick.json`` aggregate — they never overwrite the committed
full-mode ``BENCH_*.json`` artifacts, and every artifact carries a
``"mode"`` field recording which sweep produced it. A module that raises
is recorded in the aggregate and makes the run exit non-zero.
"""
from __future__ import annotations

import argparse
import json
import os
import time
import traceback


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default=None,
                    help="aggregate results path (default: artifacts/"
                         "bench_results.json, or the .quick.json sidecar "
                         "under --quick)")
    args = ap.parse_args()

    from benchmarks.common import artifact_path

    # --quick always lands in a .quick.json sidecar, even for an explicit
    # --out: quick aggregates must never clobber a committed full artifact
    out = artifact_path(args.out or "artifacts/bench_results.json",
                        args.quick)

    from benchmarks import (attention_softmax, chunk_prefill, decode_engine,
                            decode_fusion, dispatch_table, flat_gemm_sweep,
                            group_decode, kv_quant, kv_tiers, paged_decode,
                            prefill_engine, prefix_sharing, roofline_report,
                            scheduler_sweep, weight_quant)

    results = {}
    failed = []
    for name, mod in [
        ("attention_softmax", attention_softmax),
        ("flat_gemm_sweep", flat_gemm_sweep),
        ("dispatch_table", dispatch_table),
        ("decode_engine", decode_engine),
        ("decode_fusion", decode_fusion),
        ("paged_decode", paged_decode),
        ("chunk_prefill", chunk_prefill),
        ("scheduler_sweep", scheduler_sweep),
        ("prefix_sharing", prefix_sharing),
        ("group_decode", group_decode),
        ("kv_tiers", kv_tiers),
        ("kv_quant", kv_quant),
        ("weight_quant", weight_quant),
        ("prefill_engine", prefill_engine),
        ("roofline_report", roofline_report),
    ]:
        t0 = time.time()
        try:
            results[name] = mod.run(quick=args.quick)
        except Exception as e:  # noqa: BLE001 - record, run the rest
            traceback.print_exc()
            print(f"[FAIL] {name}: {e!r}")
            results[name] = {"error": repr(e)}
            failed.append(name)
        print(f"  [{name} done in {time.time()-t0:.1f}s]")

    results["mode"] = "quick" if args.quick else "full"
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(results, f, indent=2, default=str)
    print(f"\nall benchmarks done -> {out}")
    if failed:
        print(f"{len(failed)} benchmark module(s) failed: {', '.join(failed)}")
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
