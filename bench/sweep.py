"""Find an open-loop cell's knee: the highest offered rate the engine
sustains without a growing backlog.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates 1,2,3,...

One process on the chip: set-up and warm-up once, then for each rate the
cell's mix at that rate for ``--seconds`` after its pre-roll, with the
requests still in flight aborted before the next rate. For each rate it
prints one JSON line: the rate, requests due, completed output tokens
per second, time to first token (median, 90th percentile) over the
requests due in the first and in the second half of the window, and
how many requests due in the window had no first token when it closed.
A backlog that grows shows as a second half slower than the first and
as requests left waiting. The benchmark's runs never call this; a cell's
rate is set once, from its output, in the mix file.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()

    from bench import harness, stats
    from bench.traffic import gen
    cell = harness.load_cell(args.workload, False)
    if cell.mix["loop"] != "open":
        raise SystemExit("a closed loop has no offered rate to sweep")
    device, _ = harness.chip(False)
    harness.compile_cache()
    eng, cfg, plan = harness.build(cell, args.seed, device, None)
    harness.warm(eng, cell, args.seed)
    print(f"set-up {time.perf_counter() - T_START:.1f} s; plan {plan}",
          file=sys.stderr, flush=True)
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        mix = dict(cell.mix, rate_rps=rate)
        reqs = gen.generate(mix, args.seed + k, mix["preroll_s"]
                            + args.seconds + 5, cfg.vocab_size)
        logs, steps, win, _, n_comp = harness.drive(
            eng, reqs, mix, args.seconds, drain_s=0.0)
        rec = stats.Record(slots=eng.num_slots, open=win[0], close=win[1],
                           requests=logs, steps=steps, counters={},
                           setup_s=0, dims={}, peaks={})
        mid = (win[0] + win[1]) / 2
        halves = []
        for lo, hi in ((win[0], mid), (mid, win[1])):
            t = [r.stamps[0] - r.due if r.stamps else math.inf
                 for r in logs if lo <= r.due < hi]
            halves.append([stats.percentile(t, 50) * 1e3,
                           stats.percentile(t, 90) * 1e3])
        waiting = sum(1 for r in logs if win[0] <= r.due < win[1]
                      and not any(t <= win[1] for t in r.stamps))
        print(json.dumps({
            "rate_rps": rate,
            "due": sum(1 for r in logs if win[0] <= r.due < win[1]),
            "output_tok_s": stats.tokens_in_window(rec) / rec.seconds,
            "ttft_ms_first_half": halves[0], "ttft_ms_second_half": halves[1],
            "no_token_at_close": waiting, "compiles": n_comp,
            "occupancy_pct": 100 * stats.occupancy(rec)}), flush=True)
        for rid, st in list(eng.requests.items()):
            if not st.finished:
                eng.abort(rid)
        eng.evict_finished()
    return 0


if __name__ == "__main__":
    sys.exit(main())
