"""End-to-end serving driver (continuous batching over synthetic requests).

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --smoke \
        --requests 16 --slots 4 --max-new 12

Pool pressure and preemption are drivable from the CLI: ``--cache-kind
paged --overcommit 0.5`` provisions half the worst-case page pool (or set
``--num-pages`` exactly), and ``--scheduler`` picks the admission/victim
policy. ``--prefix-sharing`` (with ``--shared-prefix N`` to synthesize a
common system prompt) maps identical page-aligned prompt prefixes onto
refcounted copy-on-write pages. The summary line reports per-phase
throughput plus preemption, page-utilization, and prefix-sharing counters
— the scheduler-policy numbers the paper's heuristic-dataflow argument
cares about.

Kernel dispatch is plan-driven: ``--tune`` runs the offline T3 decision
flow for the arch and saves a provenanced ``plans/<arch>-<hw>.json``;
``--plan PATH`` serves with a previously tuned plan (stale plans — wrong
hardware or config hash — are rejected at load). ``--gather-chunk
dense|fused`` overrides the plan's chunked-prefill page-access mode
(fused = the chunk-attention kernel over the pool / resident-bounded
tables on XLA — see ``repro.kernels.chunk_attention``). ``--decode-group
grouped`` overrides the plan's prefix-shared decode mode: with
``--prefix-sharing`` on a paged cache, requests decoding behind the same
refcounted prefix pages attend to the shared prefix once per group and
unified-max-merge their private tails (see
``repro.kernels.group_attention``); the summary then reports grouped
decode counts and prefix KV bytes the dedup saved.

The tiered KV hierarchy rides on ``--host-pages N`` (host-RAM page store
behind the device pool) and ``--session-cache`` (retain finished
conversations' KV pages — demoted host-ward under pool pressure, promoted
back when the conversation returns): preemption and retirement demote
pages instead of discarding them, and the summary grows
demoted/promoted/session-hit counters. ``--rounds R`` resubmits the same
prompts R times (returning-conversation workload — the second round hits
the session cache instead of re-prefilling).

``--kv-dtype int8|fp8`` stores KV pages quantized (paged cache only):
each page carries per-(page, head) symmetric scales in a parallel f32
pool and the decode/chunk kernels dequantize in-registers, so the
full-precision slab never exists in HBM. int8 halves KV bytes per decode
step and doubles resident-page capacity at greedy-equivalent accuracy;
fp8 (e4m3) matches the footprint with cheaper dequant but coarser
mantissa. The summary reports bytes/page and total decode-read KV bytes
so the savings are directly visible against a ``bf16`` run.

``--weight-dtype int8|fp8`` is the weight-side twin: GEMM weight leaves
are quantized once at load to int8/fp8 codes plus one f32 scale per
output channel, and every GEMM kernel dequantizes on the f32 accumulator
in-register, so the bf16 weight slab never exists in HBM. At decode's
tiny M the weight stream dominates the tick, so int8 halves it (the
summary's ``weights=`` segment reports stored bytes per tick and total
decode-read weight bytes against a ``bf16`` run); accuracy is held to
the same dtype-derived logits guard as ``--kv-dtype``.

``--decode-fusion split|fused|looped`` overrides the plan's decode-layer
stage granularity (``DecodeFusionPlan``): ``fused`` collapses
norm→QKV→rope and o_proj→residual into the fused stage kernels,
``looped`` additionally runs the whole depth under one ``lax.scan``. The
summary line reports the effective granularity as ``fusion=...``.
"""
import argparse
import sys
import time


def _parse():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=512)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--prompt-len", type=int, default=48)
    ap.add_argument("--cache-kind", choices=["dense", "paged"],
                    default="dense",
                    help="dense slot cache or block-paged pool")
    ap.add_argument("--page-size", type=int, default=64)
    ap.add_argument("--num-pages", type=int, default=None,
                    help="exact pool size in pages (default: worst-case "
                         "slots*max_seq footprint scaled by --overcommit)")
    ap.add_argument("--overcommit", type=float, default=1.0,
                    help="fraction of the worst-case page footprint to "
                         "provision; <1 forces lazy-growth preemption")
    ap.add_argument("--scheduler", default="fcfs",
                    choices=["fcfs", "sjf", "pagefair"],
                    help="admission/preemption policy")
    ap.add_argument("--prefix-sharing", action="store_true",
                    help="map identical page-aligned prompt prefixes onto "
                         "shared refcounted pages (copy-on-write; paged "
                         "cache only)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="prepend this many identical tokens to every "
                         "synthetic prompt (system-prompt workload — makes "
                         "--prefix-sharing visible in the summary)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="chunked-prefill chunk size (dense-KV families); "
                         "default: the plan's tuned paged.chunk_block")
    ap.add_argument("--gather-chunk", choices=["dense", "fused"],
                    default=None,
                    help="override the plan's chunked-prefill page access "
                         "mode: 'dense' gathers the full (B, NB*PS) KV "
                         "view per chunk step, 'fused' reads pages in "
                         "place (fused kernel on the Pallas backend, "
                         "resident-bounded tables on XLA)")
    ap.add_argument("--decode-group", choices=["off", "grouped"],
                    default=None,
                    help="override the plan's prefix-shared decode mode: "
                         "'grouped' computes shared-prefix attention once "
                         "per group and unified-max-merges per-request "
                         "private tails (paged cache + --prefix-sharing "
                         "only)")
    ap.add_argument("--host-pages", type=int, default=None,
                    help="host-RAM tier capacity in KV pages: preemption "
                         "and retirement demote pages here instead of "
                         "discarding them (paged cache + --prefix-sharing "
                         "only); returning prompts promote them back")
    ap.add_argument("--session-cache", action="store_true",
                    help="retain finished conversations' KV pages in the "
                         "tiered session cache (implied by --host-pages; "
                         "alone it enables tier-0 retention only)")
    ap.add_argument("--kv-dtype", choices=["bf16", "int8", "fp8"],
                    default=None,
                    help="KV-cache page storage dtype (paged cache only): "
                         "int8/fp8 pages carry per-(page, head) scales and "
                         "are dequantized inside the attention kernels; "
                         "default: the plan's paged.kv_dtype")
    ap.add_argument("--weight-dtype", choices=["bf16", "int8", "fp8"],
                    default=None,
                    help="GEMM weight storage dtype: int8/fp8 weights are "
                         "quantized at load to codes + per-output-channel "
                         "f32 scales and dequantized on the kernels' f32 "
                         "accumulators; default: the plan's "
                         "matmul.weight_dtype")
    ap.add_argument("--decode-fusion", choices=["split", "fused", "looped"],
                    default=None,
                    help="decode-layer stage granularity: split = the "
                         "per-op chain, fused = fused ingest/epilogue "
                         "stage kernels per layer, looped = fused stages "
                         "under one depth scan; default: the plan's "
                         "decode_fusion.granularity")
    ap.add_argument("--rounds", type=int, default=1,
                    help="resubmit every prompt this many times — round "
                         ">= 2 models returning conversations hitting the "
                         "session cache")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--plan", default=None, metavar="PATH",
                    help="ExecutionPlan JSON to dispatch kernels by; "
                         "rejected if its provenance (hardware/config "
                         "hash) does not match this run")
    ap.add_argument("--tune", action="store_true",
                    help="tune a plan offline for this arch (T3 decision "
                         "flow over every op), save it to --plan (default "
                         "plans/<arch>-<hw>.json), and serve with it")
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args()


def main() -> int:
    args = _parse()
    import jax
    import numpy as np

    from repro import configs
    from repro.core import plan as plan_mod
    from repro.kernels import ops
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models.api import get_model
    from repro.models.kvlayout import pages_for
    from repro.serving.engine import Engine
    from repro.serving.request import SamplingParams

    enable_compile_cache()
    cfg = configs.get(args.arch)
    if args.smoke:
        cfg = configs.smoke(cfg)
    api = get_model(cfg)
    params = api.init_params(jax.random.PRNGKey(args.seed))

    # plans are tuned for, and checked against, the chip the kernels run
    # on (the v5e that interpret mode emulates on the CPU)
    _, spec = ops.kernel_target()
    plan = None
    if args.tune:
        plan = plan_mod.tune(cfg, spec, page_size=args.page_size)
        path = args.plan or plan_mod.default_plan_path(cfg, spec)
        plan.save(path)
        print(f"tuned plan -> {path}\n  {plan.describe()}")
    elif args.plan:
        plan = plan_mod.ExecutionPlan.load(args.plan, cfg=cfg, spec=spec)
        print(f"loaded plan {args.plan}\n  {plan.describe()}")

    if args.gather_chunk is not None or args.decode_group is not None:
        import dataclasses
        base = plan if plan is not None else plan_mod.DEFAULT_PLAN
        over = {}
        if args.gather_chunk is not None:
            over["gather_chunk"] = args.gather_chunk
        if args.decode_group is not None:
            over["decode_group"] = args.decode_group
        plan = dataclasses.replace(
            base, paged=dataclasses.replace(base.paged, **over))

    num_pages = args.num_pages
    if num_pages is None and args.cache_kind == "paged":
        worst = args.slots * pages_for(args.max_seq, args.page_size)
        num_pages = max(int(worst * args.overcommit), 1)

    eng = Engine(cfg, params, num_slots=args.slots, max_seq=args.max_seq,
                 cache_kind=args.cache_kind, page_size=args.page_size,
                 num_pages=num_pages, prefill_chunk=args.prefill_chunk,
                 scheduler=args.scheduler, plan=plan,
                 prefix_sharing=args.prefix_sharing,
                 host_pages=args.host_pages,
                 session_cache=args.session_cache or None,
                 kv_dtype=args.kv_dtype,
                 weight_dtype=args.weight_dtype,
                 decode_fusion=args.decode_fusion,
                 seed=args.seed)
    rng = np.random.default_rng(args.seed)
    sp = SamplingParams(max_new_tokens=args.max_new,
                        temperature=args.temperature, top_p=args.top_p)
    header = rng.integers(1, cfg.vocab_size,
                          size=args.shared_prefix).astype(np.int32)
    reqs = [
        (np.concatenate([header, rng.integers(
            1, cfg.vocab_size, size=args.prompt_len).astype(np.int32)]), sp)
        for _ in range(args.requests)
    ]

    t0 = time.perf_counter()
    out = {}
    for rnd in range(max(args.rounds, 1)):
        out = eng.run(reqs)
        if rnd + 1 < args.rounds:
            eng.evict_finished()   # KV stays in the session cache
    dt = time.perf_counter() - t0
    total_tokens = sum(len(v) for v in out.values())
    line = (f"served {len(out)} requests, {total_tokens} tokens in {dt:.2f}s "
            f"({total_tokens / dt:.1f} tok/s, {eng.ticks} decode ticks, "
            f"{eng.scheduler.name} scheduler, "
            f"fusion={eng.decode_fusion}, "
            f"weights={eng.weight_dtype} "
            f"({eng._weight_bytes_per_tick} B/tick, "
            f"{eng.stats.weight_bytes_decode_read} decode weight bytes "
            f"read), "
            f"{eng.stats.preemptions} preemptions")
    if eng.pool is not None:
        util = eng.stats.peak_pages_used / eng.pool.num_pages
        line += (f", peak pages {eng.stats.peak_pages_used}"
                 f"/{eng.pool.num_pages} = {util:.0%}")
        line += (f", kv={eng.kv_dtype} "
                 f"({eng.stats.kv_page_bytes} B/page, "
                 f"{eng.stats.kv_bytes_decode_read} decode KV bytes read)")
    if args.prefix_sharing:
        line += (f", {eng.stats.shared_prefix_pages} shared pages, "
                 f"{eng.stats.saved_prefill_tokens} prefill tokens saved, "
                 f"{eng.stats.cow_forks} COW forks")
    if eng.stats.grouped_requests:
        line += (f", {eng.stats.grouped_requests} grouped decodes, "
                 f"{eng.stats.prefix_kv_bytes_saved} prefix KV bytes saved")
    if eng.tiers is not None:
        line += (f", {eng.stats.demoted_pages} pages demoted, "
                 f"{eng.stats.promoted_pages} promoted, "
                 f"{eng.stats.session_hits} session hits")
        if eng.stats.host_evicted_pages:
            line += f", {eng.stats.host_evicted_pages} evicted"
    print(line + ")")
    for rid in sorted(out)[:4]:
        print(f"  req {rid}: {out[rid]} "
              f"[{eng.finish_reason(rid)}]")
    return 0


if __name__ == "__main__":
    sys.exit(main())
