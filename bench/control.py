"""Read the numbers ``correct`` compares, on the chip, at a cell's own size:
the program's sound runs over many seeds, the control (a lower precision
put in the program's place) and each planted fault, all in one process.
The limits in ``bench/limits/<cell>.json`` are set from these readings.

    python3 bench/control.py --workload <cell> --seeds 12 --control-seeds 3 \\
        --fault-seeds 3 [--out chiprun_out/control] [--record-trace FILE]

Each reading is one JSON line on standard output (and in ``--out``).
``--record-trace`` also writes one traced block of the cell's first seed,
gzipped, as the recorded trace the reduction's tests read.
"""
from __future__ import annotations

import argparse
import glob
import gzip
import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def record_trace(cell, seed: int, path: str):
    import jax

    import harness

    tr = harness.build(cell, seed)
    harness.advance(tr, cell.rounds)
    jax.block_until_ready(tr.flat)
    shutil.rmtree(harness.TRACE_DIR, ignore_errors=True)
    jax.profiler.start_trace(harness.TRACE_DIR)
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.run_block"):
            harness.advance(tr, cell.rounds)
        with jax.profiler.TraceAnnotation("bench.wait"):
            jax.block_until_ready(tr.flat)
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(harness.TRACE_DIR, "**", "*.xplane.pb"),
                    recursive=True)[0]
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(src, "rb") as f, gzip.open(path, "wb") as g:
        g.write(f.read())
    shutil.rmtree(harness.TRACE_DIR, ignore_errors=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--fault-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2 ** 31 + 1000)
    ap.add_argument("--out")
    ap.add_argument("--record-trace")
    args = ap.parse_args()

    import harness
    from faults import FAULTS

    harness.enable_cache()
    cell = harness.load_cell(args.workload)
    plan = [("sound", None, False, args.seeds),
            ("control", None, True, args.control_seeds)]
    # a state left unchanged reads 1 by the norm gap and needs no run; the
    # exchange between chips exists only on several
    plan += [(f, f, False, args.fault_seeds) for f in FAULTS
             if f != "unchanged_state" and (f != "no_exchange" or cell.chips > 1)]
    out = None
    if args.out:
        os.makedirs(args.out, exist_ok=True)
        out = open(os.path.join(args.out, f"{cell.name}.jsonl"), "a")
    try:
        if args.record_trace:
            record_trace(cell, args.first_seed, args.record_trace)
        for kind, fault, control, n in plan:
            for i in range(n):
                seed = args.first_seed + 7919 * i
                t = time.perf_counter()
                res = harness.run(cell, seed, 0.0, False, t_start=t,
                                  control=control, fault=fault, diagnose=True)
                leaves = res["diagnosis"]["leaf_mismatch"]
                worst = sorted(leaves.items(), key=lambda kv: -kv[1])[:6]
                line = json.dumps({
                    "cell": cell.name, "kind": kind, "seed": seed,
                    "correct": res["correct"],
                    "values": res["diagnosis"]["values"],
                    "worst_leaves": worst,
                    "memory_stats": res["diagnosis"]["memory_stats"],
                    "seconds": time.perf_counter() - t})
                print(line, flush=True)
                if out:
                    out.write(line + "\n")
                    out.flush()
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
