"""The benchmark harness: one cell, one run, driven by data.

A cell (``BENCHMARK.json`` ``workloads``) names a configuration and a
traffic mix. Everything that belongs to one of them sits in files of its
own, found by name:

* ``bench/configs/<config>.json``: the configuration's sizes as run, its
  mechanism (``make_mechanism``'s dict form), its input kind and, under
  ``fed``, the ``FedConfig`` fields it states (``lr``, ``server_opt``);
* ``bench/configs/<model>.py`` (``model`` in the JSON, else the
  configuration's own name): ``init_weights``, ``program_loss`` (the system
  under test), ``control_loss``, ``reference_loss`` (the plain reference)
  and ``model_flops_per_round``; optionally ``Task``, a ``ClientTask``
  class taking ``(weights, data, loss)`` in place of the harness's own;
* ``bench/traffic/<traffic>.json``: under ``fed`` any ``FedConfig`` field
  but ``seed`` and ``task`` (engine, population, cohort, subsampling,
  staging, fused rounds, ...), passed through as given; under ``data`` the
  sizes the input kind's generator takes; ``rounds_per_block``, the rounds
  one call of the window advances; ``trace_blocks``, the calls a traced
  run makes;
* ``bench/limits/<cell>.json``: the limit of each number ``correct``
  compares;
* ``bench/metrics/<metric>.py``: one reader per per-layer metric;
* the reference's pieces, by the names the configuration and the traffic
  give (``bench/reference.py``).

A run builds the program's ``FedTrainer`` with the cell's task, weights
and data made from the seed, runs its first blocks of rounds (set-up),
then measures: with ``trace=0`` the window's calls back to back for
``seconds``, with ``trace=1`` a fixed number of calls under the profiler.
Then it frees the program's state and replays the first blocks with the
plain reference to decide ``correct``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import gc
import glob
import json
import os
import shutil
import sys
import time

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
for _p in (os.path.join(ROOT, "src"), BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax  # noqa: E402
import jax.flatten_util  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import correct  # noqa: E402
import datagen  # noqa: E402
import plugins  # noqa: E402
import reference  # noqa: E402
import trace_reduce  # noqa: E402
from peaks import peaks  # noqa: E402

# FedConfig fields the harness sets itself
HARNESS_FED = ("seed", "task")


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell needs."""


def _json(path: str):
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    cfg: dict
    model: object
    fed: dict  # FedConfig keywords, the configuration's and the traffic's
    data: dict  # the sizes of the input kind's generator
    rounds: int  # rounds one call of the window advances
    trace_blocks: int
    limits: dict
    end_to_end: list
    per_layer: list


def fed_keywords(cfg_fed: dict, traffic_fed: dict) -> dict:
    """The configuration's and the traffic's ``FedConfig`` keywords, merged;
    a key both give, one ``FedConfig`` does not take, or one the harness
    sets is refused."""
    from repro.fed import FedConfig

    both = sorted(set(cfg_fed) & set(traffic_fed))
    if both:
        raise ValueError(f"the configuration and the traffic both set {both}")
    fed = {**cfg_fed, **traffic_fed}
    allowed = {f.name for f in dataclasses.fields(FedConfig)}
    bad = sorted(set(fed) - allowed | set(fed) & set(HARNESS_FED))
    if bad:
        raise ValueError(f"not FedConfig keywords a cell may set: {bad}")
    return fed


def load_cell(name: str, benchmark: dict | None = None) -> Cell:
    bench = benchmark or _json(os.path.join(ROOT, "BENCHMARK.json"))
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r}; known: {sorted(by_name)}")
    wl = by_name[name]
    conf = {c["name"]: c for c in bench["configs"]}[wl["config"]]
    cfg_path = os.path.join(ROOT, conf["file"])
    cfg = _json(cfg_path)
    model = cfg.get("model", os.path.splitext(os.path.basename(cfg_path))[0])
    traffic = _json(os.path.join(BENCH, "traffic", wl["traffic"] + ".json"))

    def applies(metric):
        return name in metric.get("workloads", [name])

    return Cell(
        name=name, chips=wl["chips"], cfg=cfg,
        model=plugins.load_path(os.path.join(os.path.dirname(cfg_path),
                                             model + ".py")),
        fed=fed_keywords(cfg.get("fed", {}), traffic["fed"]),
        data=traffic.get("data", {}), rounds=traffic["rounds_per_block"],
        trace_blocks=traffic["trace_blocks"],
        limits=_json(os.path.join(BENCH, "limits", name + ".json")),
        end_to_end=[m for m in bench["end_to_end"] if applies(m)],
        per_layer=[m for m in bench["per_layer"] if applies(m)],
    )


def _task_class():
    from repro.fed.tasks import ClientTask

    class BenchTask(ClientTask):
        """The cell's client task: the program's loss over weights and
        client data the benchmark made from the seed."""

        name = "bench"

        def __init__(self, weights, data, loss):
            self.weights, self.data, self._loss = weights, data, loss

        def init_params(self, key):
            return self.weights

        def loss(self, params, batch):
            return self._loss(params, batch)

        def client_batch(self, cid: int):
            return {k: v[cid] for k, v in self.data.items()}

        def evaluate(self, flat, unravel):
            raise NotImplementedError("the benchmark never evaluates")

    return BenchTask


def seeds(seed: int):
    """The program's config seed (it derives its round key as
    ``key(seed + 11)``) and the benchmark's weight key, from any
    non-negative whole number."""
    cfg_seed = seed % (2 ** 31 - 16)
    key = jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF),
                             (seed >> 31) & 0xFFFFFFFF)
    return cfg_seed, key


def _inputs(cell: Cell, seed: int):
    """The weights and the population's data a seed makes."""
    data = datagen.client_data(cell.cfg, cell.fed["num_clients"], cell.data,
                               np.random.default_rng(seed))
    weights = jax.jit(functools.partial(cell.model.init_weights, cell.cfg))(
        seeds(seed)[1])
    return weights, data


def build(cell: Cell, seed: int, *, control: bool = False):
    """The program's trainer for one run, with its weights and data."""
    from repro.core.mechanisms import make_mechanism
    from repro.fed import FedConfig, FedTrainer

    weights, data = _inputs(cell, seed)
    loss = (cell.model.control_loss if control else cell.model.program_loss)
    # a model file may bring its own task class (a model axis's hooks)
    task_class = getattr(cell.model, "Task", None) or _task_class()
    task = task_class(weights, data, loss(cell.cfg))
    fed = FedConfig(**cell.fed, seed=seeds(seed)[0], task=task)
    return FedTrainer(make_mechanism(cell.cfg["mechanism"]), fed)


def advance(tr, rounds: int):
    """One call of the window: a block of ``rounds`` rounds on an engine
    that advances in blocks, else ``rounds`` single rounds."""
    if tr.engine.blocked:
        tr.run_block(rounds)
    else:
        for _ in range(rounds):
            tr.round()


# lowering events: a program traced anew and lowered (then compiled or read
# from the cache) adds one or more; a warm window adds none
_LOWERINGS = [0]


def _count_lowering(event: str, *args, **kwargs):
    if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
        _LOWERINGS[0] += 1


jax.monitoring.register_event_duration_secs_listener(_count_lowering)


def _timed_window(tr, rounds: int, seconds: float):
    """The window's calls back to back until ``seconds`` have passed, at
    most two in flight; ends when the device has finished."""
    blocks, prev = 0, None
    t0 = time.perf_counter()
    while True:
        advance(tr, rounds)
        blocks += 1
        key = tr._key  # the block's last output that is not donated
        if prev is not None:
            prev.block_until_ready()
        prev = key
        if time.perf_counter() - t0 >= seconds:
            break
    jax.block_until_ready(tr.flat)
    return blocks, time.perf_counter() - t0


def _traced_window(tr, rounds: int, blocks: int):
    """``blocks`` calls of the window under the profiler, in the harness's
    host spans; returns the reduced trace."""
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    ann = jax.profiler.TraceAnnotation
    prev = None
    jax.profiler.start_trace(TRACE_DIR)
    try:
        with ann("bench.window"):
            for _ in range(blocks):
                with ann("bench.run_block"):
                    advance(tr, rounds)
                key = tr._key
                if prev is not None:
                    with ann("bench.wait"):
                        prev.block_until_ready()
                prev = key
            with ann("bench.wait"):
                jax.block_until_ready(tr.flat)
    finally:
        jax.profiler.stop_trace()
    paths = glob.glob(os.path.join(TRACE_DIR, "**", "*.xplane.pb"),
                      recursive=True)
    reduced = trace_reduce.reduce_file(paths[0])
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    return reduced


def _peak_bytes(device) -> int:
    """The device's peak memory: its buffers at their peak plus the peak it
    reserved for the compiled programs' temporaries, which the TPU runtime
    counts apart from ``peak_bytes_in_use``. Read before the reference runs,
    both peaks fall in the program's own phase."""
    stats = device.memory_stats() or {}
    return (stats.get("peak_bytes_in_use", 0)
            + stats.get("peak_bytes_reserved", 0))


def _per_layer(cell: Cell, facts: dict) -> dict:
    out = {}
    for m in cell.per_layer:
        value = plugins.find("metrics", m["name"]).read(facts)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def check(cell: Cell, seed: int, prog_flats: list):
    """Replay the checked blocks with the plain reference and compare;
    returns ``(correct, compared, values, leaf_mismatch)``."""
    weights, data = _inputs(cell, seed)
    data = {k: jnp.asarray(v) for k, v in data.items()}
    flat0, unravel = jax.flatten_util.ravel_pytree(weights)
    rnd = reference.Round(cell.fed, cell.cfg["mechanism"])
    ref_flats = reference.replay(
        functools.partial(cell.model.reference_loss, cell.cfg), weights, data,
        rnd, rounds=cell.rounds * len(prog_flats), every=cell.rounds,
        key=jax.random.key(seeds(seed)[0] + 11))
    values, leaf_mismatch = correct.numbers(
        flat0, prog_flats, ref_flats, unravel,
        rnd.level_step(cell.fed["clients_per_round"]))
    ok, compared = correct.judge(values, cell.limits)
    return ok, compared, values, leaf_mismatch


def run(cell: Cell, seed: int, seconds: float, trace: bool, *, t_start: float,
        require_tpu: bool = True, control: bool = False, fault=None,
        diagnose: bool = False) -> dict:
    """One run of one cell; returns the result line as a dict (with
    ``diagnose``, also every number and the mismatch of each leaf)."""
    import faults

    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu"
                        or len(devices) < cell.chips):
        raise NoChip(f"cell {cell.name} needs {cell.chips} TPU chip(s); JAX "
                     f"found {len(devices)} {devices[0].platform} device(s)")
    rounds = cell.rounds
    with faults.planted(fault) if fault else contextlib.nullcontext():
        tr = build(cell, seed, control=control)
        prog_flats = []  # after each checked block; the first one warms up
        for _ in range(-(-correct.CHECK_ROUNDS // rounds)):
            advance(tr, rounds)
            prog_flats.append(np.asarray(tr.flat))
        setup_s = time.perf_counter() - t_start
        lowered = _LOWERINGS[0]
        if trace:
            reduced = _traced_window(tr, rounds, cell.trace_blocks)
            blocks, elapsed = cell.trace_blocks, reduced["window_s"]
        else:
            blocks, elapsed = _timed_window(tr, rounds, seconds)
        lowered = _LOWERINGS[0] - lowered
    peak = max(_peak_bytes(d) for d in devices[:cell.chips])
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": jax.device_count(), "memory_peak_bytes": int(peak)}
    facts = {"rounds": blocks * rounds, "blocks": blocks, "chips": cell.chips,
             "cohort": cell.fed["clients_per_round"],
             "dim": int(prog_flats[0].size), "window_lowerings": lowered}
    del tr
    gc.collect()
    if trace:
        facts.update(trace=reduced, peaks=peaks(devices[0].device_kind),
                     model_flops_per_round=cell.model.model_flops_per_round(
                         cell.cfg, cell.data, cell.fed["clients_per_round"]))
        metrics = _per_layer(cell, facts)
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
    else:
        e2e = {"rounds_per_s": blocks * rounds / elapsed,
               "peak_hbm_gib": peak / 2 ** 30, "setup_s": setup_s}
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    ok, compared, values, leaf_mismatch = check(cell, seed, prog_flats)
    del prog_flats
    result = {"correct": ok, "attempted": blocks * rounds, "failed": 0,
              "metrics": metrics, "device": device,
              "window_lowerings": lowered}
    if trace:
        result["breakdown"] = {"device_ops": reduced["top_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["compared"] = compared
    if diagnose:
        result["diagnosis"] = {"values": values, "leaf_mismatch": leaf_mismatch,
                               "memory_stats": devices[0].memory_stats()}
    return result


def enable_cache():
    """JAX's persistent compilation cache where the program keeps it (inside
    the checkout unless ``JAX_COMPILATION_CACHE_DIR`` says otherwise), for
    every program however quick to compile, so set-up repeats alike."""
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
