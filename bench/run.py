"""Run one benchmark cell once on the chip and print its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, no children, one chip. It loads the cell's files (see
``bench/harness.py``), draws weights and traffic from ``--seed``, warms
up every program shape the traffic reaches, serves the traffic for
``--seconds`` seconds, checks what was served against the plain
reference, and prints one JSON object as the last line of standard
output: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics read
from a profiler trace of the window), ``device``, ``breakdown`` (traced
runs) and ``checks`` (each number compared, beside its limit). The same
numbers compared are the last lines of standard error.

Without a TPU, or on a chip ``bench/peaks.json`` does not list, it exits
non-zero and prints no result. ``--dry-run`` is for the CPU alone: it
serves the smoke configuration in ``bench/tests/data`` with the kernels
in interpret mode, prints what it measured, and never prints a result.

The controls, which the correctness check must refuse and the
benchmark's own runs never pass: ``--control int8`` serves with the
program's own int8 weights and int8 KV pages; ``--control ref-int8``
serves as usual and then judges, in the served tokens' place, the
tokens the reference computed one precision step lower puts first
(``bench/correct.py``), logging the served tokens' numbers beside them.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def _err(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=("int8", "ref-int8"))
    ap.add_argument("--dry-run", action="store_true")
    args = ap.parse_args(argv)

    from bench import harness
    if args.dry_run:
        cell = harness.dry_cell(bool(args.trace))
    elif not args.workload:
        ap.error("--workload is required")
    else:
        cell = harness.load_cell(args.workload, bool(args.trace))
    result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                         T_START, dry=args.dry_run, control=args.control,
                         log=_err)
    for name, c in result["checks"].items():
        _err(f"check {name}: {c['value']} (limit {c['limit']})")
    if args.dry_run:
        _err(f"dry run: correct={result['correct']} metrics="
             f"{json.dumps(result['metrics'])}")
        if result["device"]["platform"] != "cpu":
            raise SystemExit("dry run reached the result on a chip")
        return 0 if result["correct"] else 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
