"""Rehearse the benchmark without the chip, before spending chip time.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py            # every cell, tiny
    JAX_PLATFORMS=cpu python3 bench/rehearse.py --compile mamba2_370m.short_local

The first form runs each cell of ``BENCHMARK.json`` end to end on the CPU
at a tiny size (small population, cohort and local data; a model whose
file gives ``TINY`` also at those widths), kernels through their CPU path, past the harness's look
for a chip: the warm block, a one-second window and the comparison with
the reference, which must read ``correct``. Nothing it prints is a device
number.

``--compile`` builds the named cell at its real size on the CPU and
compiles its round block for the cell's chips of a described TPU v5e, as
the chip would: the Pallas kernels, no CPU unrolling, and for a cell on
several chips the shard engine's mesh over as many described chips (run
it with ``XLA_FLAGS=--xla_force_host_platform_device_count=4``, so that
the trainer is built for four). It prints the compiled program's memory
analysis, so that a cell that would not fit is found here.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

TINY_FED = {"num_clients": 12, "clients_per_round": 4, "scan_block": 2}
TINY_DATA = {"samples_per_client": 4, "seq_len": 16, "batch": 1}


def tiny(cell):
    cell.fed.update(TINY_FED)
    cell.data.update({k: v for k, v in TINY_DATA.items() if k in cell.data})
    cell.rounds, cell.trace_blocks = 2, 1
    cell.cfg.update(getattr(cell.model, "TINY", {}))
    return cell


def rehearse_all() -> int:
    import harness

    bad = 0
    bench = json.load(open(os.path.join(harness.ROOT, "BENCHMARK.json")))
    for wl in bench["workloads"]:
        cell = tiny(harness.load_cell(wl["name"], bench))
        t = time.perf_counter()
        out = harness.run(cell, 2 ** 31 + 12345, 1.0, False, t_start=t,
                          require_tpu=False)
        print(wl["name"], "correct" if out["correct"] else "NOT CORRECT",
              json.dumps(out["compared"]), flush=True)
        bad += not out["correct"]
    return 1 if bad else 0


@contextlib.contextmanager
def as_tpu():
    """Trace the program as it is traced on a TPU (it asks
    ``jax.default_backend()`` for its kernel path and scan unrolling)."""
    import jax

    saved = jax.default_backend
    jax.default_backend = lambda: "tpu"
    try:
        yield
    finally:
        jax.default_backend = saved


def compile_for_chip(name: str) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                              SingleDeviceSharding)

    import harness

    jax.config.update("jax_enable_compilation_cache", False)
    cell = harness.load_cell(name)
    if jax.device_count() < cell.chips:
        raise SystemExit(
            f"{name} spans {cell.chips} chips: run with XLA_FLAGS="
            f"--xla_force_host_platform_device_count={cell.chips}")
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    t = time.perf_counter()
    tr = harness.build(cell, 7)
    print(f"built on the CPU in {time.perf_counter() - t:.1f} s; dim "
          f"{tr.flat.size:,}", flush=True)
    rounds = cell.rounds
    if cell.chips > 1:
        # the shard engine's mesh, rebuilt over the described chips
        tr._mesh = Mesh(np.array(topo.devices[:cell.chips]),
                        tr._mesh.axis_names)
        place = NamedSharding(tr._mesh, PartitionSpec())
    else:
        place = SingleDeviceSharding(topo.devices[0])
    args = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=place),
        (tr.flat, tr.opt_state, tr._key, tr.client_data))
    with as_tpu():
        tr.engine.build()
        t = time.perf_counter()
        if cell.chips > 1:
            lowered = tr.engine._block_jit(rounds).lower(*args)
        else:
            lowered = tr.engine._block_jit.lower(*args, rounds)
        compiled = lowered.compile()
    text = compiled.as_text()
    print(f"compiled for a described v5e in {time.perf_counter() - t:.1f} s; "
          f"Pallas kernel in the block: {'tpu_custom_call' in text}; "
          f"all-reduce across chips: {' all-reduce' in text}")
    print(compiled.memory_analysis())
    return 0


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--compile", metavar="CELL")
    args = ap.parse_args()
    return compile_for_chip(args.compile) if args.compile else rehearse_all()


if __name__ == "__main__":
    sys.exit(main())
