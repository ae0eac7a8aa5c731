"""Run one benchmark cell once, on the chip:

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``--trace 0`` measures the cell's end-to-end metrics over ``--seconds`` of
``FedTrainer.run_block`` calls; ``--trace 1`` runs the cell's traced blocks
under the profiler and reports its per-layer metrics. Either way the run
then replays its first block of rounds with the plain reference and
reports ``correct``. The last line of standard output is the result as
one JSON object; the last lines of standard error give each compared
number beside its limit.

Exits nonzero, and prints no result, where JAX finds no TPU or fewer chips
than the cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")

    import harness

    harness.enable_cache()
    cell = harness.load_cell(args.workload)
    try:
        result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                             t_start=T_START)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    for name, c in result["compared"].items():
        print(f"compared {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
