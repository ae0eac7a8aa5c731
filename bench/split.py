"""Split cells' rounds by the program's scopes and spans, on the chip.

    python3 bench/split.py --workload <cell> [--workload <cell> ...] \\
        --seed <n> [--seconds 10] [--record-trace FILE] [--out DIR]

For each cell, in one process: the harness's trainer from the seed and
its set-up blocks; a traced window of the cell's ``trace_blocks`` calls
(as a ``--trace 1`` run makes it); an untraced window of ``--seconds``
(as a ``--trace 0`` run); a second traced window of as many calls. Each
traced window is reduced twice, by ``trace_reduce`` (the benchmark's
per-layer metrics) and by ``scopes`` (device time per ``fed.*`` scope,
idle time per host span). One JSON line per cell on standard output (and
in ``--out``) gives, per round and chip, the device milliseconds of each
scope, the scopes' coverage of the device op time, idle time by span,
``host_bound_idle_share``, ``account_ms_per_block`` and the count of
exact epsilon evaluations, and the tracing cost: one minus the second
traced window's rounds per second over the untraced window's.

``--record-trace`` also writes one traced call of the first cell,
gzipped, right after its set-up, as the recorded trace the reduction's
tests read. Exits nonzero where JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import gc
import glob
import gzip
import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def traced(tr, rounds: int, blocks: int) -> bytes:
    """``blocks`` window calls under the profiler, in the harness's host
    spans; returns the serialized trace."""
    import jax

    import harness

    ann = jax.profiler.TraceAnnotation
    shutil.rmtree(harness.TRACE_DIR, ignore_errors=True)
    prev = None
    jax.profiler.start_trace(harness.TRACE_DIR)
    try:
        with ann("bench.window"):
            for _ in range(blocks):
                with ann("bench.run_block"):
                    harness.advance(tr, rounds)
                key = tr._key
                if prev is not None:
                    with ann("bench.wait"):
                        prev.block_until_ready()
                prev = key
            with ann("bench.wait"):
                jax.block_until_ready(tr.flat)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(harness.TRACE_DIR, "**", "*.xplane.pb"),
                     recursive=True)[0]
    with open(path, "rb") as f:
        data = f.read()
    shutil.rmtree(harness.TRACE_DIR, ignore_errors=True)
    return data


def split(data: bytes, rounds: int) -> dict:
    """Both reductions of one traced window, per round and chip."""
    import scopes
    import trace_reduce

    base = trace_reduce.reduce_bytes(data)
    sc = scopes.reduce_bytes(data)
    per = lambda s: s / sc["devices"] / rounds * 1e3
    total = sum(sc["scope_s"].values())
    spans = sc["spans"]
    account = spans.get("fed.account", {"seconds": 0.0})["seconds"]
    return {
        "rounds_per_s": rounds / sc["window_s"],
        "window_s": sc["window_s"],
        "busy_s": base["busy_s"],
        "scope_ms_per_round": {k: per(v) for k, v in sc["scope_s"].items()},
        "scoped_share": 1.0 - sc["scope_s"].get(scopes.UNSCOPED, 0.0) / total,
        "class_ms_per_round": {k: per(v) for k, v in base["class_s"].items()},
        "unscoped_ops": sc["unscoped_ops"],
        "top_ops": base["top_ops"],
        "idle_by_span_ms": {k: v * 1e3 / sc["devices"]
                            for k, v in sc["idle_by_span"].items()},
        "host_bound_idle_share": 100.0 * sc["host_bound_idle_s"]
        / sc["window_s"],
        "device_idle_share": 100.0 * (1.0 - base["busy_s"] / base["window_s"]),
        "account_ms_per_block": account / max(sc["blocks"], 1) * 1e3,
        "eps_eval": spans.get("fed.eps_eval", {"count": 0})["count"],
        "spans": spans,
        "idle_gaps": sc["idle_gaps"],
    }


def run_cell(name: str, seed: int, seconds: float, record: str | None):
    import jax

    import correct
    import harness

    devices = jax.devices()
    cell = harness.load_cell(name)
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        raise harness.NoChip(f"cell {name} needs {cell.chips} TPU chip(s)")
    t0 = time.perf_counter()
    tr = harness.build(cell, seed)
    for _ in range(-(-correct.CHECK_ROUNDS // cell.rounds)):
        harness.advance(tr, cell.rounds)
    jax.block_until_ready(tr.flat)
    setup_s = time.perf_counter() - t0
    if record:
        with gzip.open(record, "wb") as f:
            f.write(traced(tr, cell.rounds, 1))
    first = split(traced(tr, cell.rounds, cell.trace_blocks),
                  cell.rounds * cell.trace_blocks)
    blocks, elapsed = harness._timed_window(tr, cell.rounds, seconds)
    untraced = blocks * cell.rounds / elapsed
    again = split(traced(tr, cell.rounds, cell.trace_blocks),
                  cell.rounds * cell.trace_blocks)
    out = {"cell": name, "seed": seed, "device": devices[0].device_kind,
           "setup_s": setup_s, "untraced_rounds_per_s": untraced,
           "tracing_cost": 1.0 - again["rounds_per_s"] / untraced,
           "first_traced": first, "steady_traced": again,
           "timings": tr.timings.summary()}
    del tr
    gc.collect()
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", action="append", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--record-trace")
    ap.add_argument("--out")
    args = ap.parse_args()

    import harness

    harness.enable_cache()
    out = None
    if args.out:
        os.makedirs(args.out, exist_ok=True)
        out = open(os.path.join(args.out, "split.jsonl"), "a")
    try:
        for i, name in enumerate(args.workload):
            try:
                res = run_cell(name, args.seed, args.seconds,
                               args.record_trace if i == 0 else None)
            except harness.NoChip as e:
                print(f"split: {e}", file=sys.stderr)
                return 3
            line = json.dumps(res)
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
