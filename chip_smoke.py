"""Chip smoke test: the paper's federated RQM round, end to end on a TPU.

    python chip_smoke.py              # one chip: phases (a), (b), (c)
    python chip_smoke.py --chips 4    # four chips: phase (d) only

(a) Device. Exits nonzero unless JAX's first device is a TPU; it never
    carries on on the CPU.
(b) Goldens. The pinned encoded sums of ``tests/golden/encoded_sums.json``
    (rqm, pbm, qmgeo; plain, weighted and offset) on the materialized and
    the fused path, every word exact.
(c) The round. ``FedTrainer`` on the EMNIST CNN at its real width (dim
    222,030), the paper cohort (40 of 3,400 clients) and
    ``rqm:c=0.02,m=16,q=0.42``, for two scan blocks of 8 rounds, once
    unfused and once with ``fused_rounds``: equal released sums round by
    round, finite loss and params, epsilon from the accountant, and a
    Pallas kernel (``tpu_custom_call``) in each compiled round block.
(d) Shard. The ``shard`` engine over a 4-chip mesh, staging ``full`` and
    ``stream``, against ``scan`` on one chip of the same process: equal
    released sums round by round, and state that spans all four chips.

Findings go to stdout line by line. The last line is the JSON result,
printed only when every check of every phase passed; otherwise the exit
code is 1. Everything runs in this one process (one process per chip).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), os.path.join(ROOT, "scripts")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from make_goldens import golden_sum_inputs  # noqa: E402
from repro.core import wire  # noqa: E402
from repro.core.mechanisms import make_mechanism  # noqa: E402
from repro.fed import FedConfig, FedTrainer, staging  # noqa: E402
from repro.fed.rounds import hot_path_pack_bits  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.kernels.fused_round_kernel import packed_kernel_engages  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

MECHANISM = "rqm:c=0.02,m=16,q=0.42"
ROUNDS = 8                # one scan block
GOLDEN = os.path.join(ROOT, "tests", "golden", "encoded_sums.json")


class Checks:
    """Prints each check's outcome as it is made and keeps the failures."""

    def __init__(self):
        self.failures: list[str] = []

    def __call__(self, ok: bool, what: str) -> bool:
        print(f"  {'ok  ' if ok else 'FAIL'} {what}", flush=True)
        if not ok:
            self.failures.append(what)
        return ok


def words_off(got, want) -> int:
    return int(np.sum(np.asarray(got) != np.asarray(want)))


def compiled_block_text(tr: FedTrainer) -> str:
    """The compiled program of the trainer's jitted round block."""
    if tr.cfg.engine == "scan":
        lowered = tr.engine._block_jit.lower(
            tr.flat, tr.opt_state, tr._key, tr.client_data, ROUNDS)
    else:
        data = (tr.client_data if tr.cfg.staging == "full" else
                staging.stage_stream_block(tr.task, tr.cfg, tr._mesh,
                                           tr.slate, tr._key, ROUNDS)[0])
        lowered = tr.engine._block_jit(ROUNDS).lower(
            tr.flat, tr.opt_state, tr._key, data)
    return lowered.compile().as_text()


# -- (b) goldens --------------------------------------------------------------


def phase_goldens(check: Checks):
    print("(b) golden encoded sums on the chip", flush=True)
    with open(GOLDEN) as f:
        golden = json.load(f)
    key = jax.random.key(golden["key_seed"])
    seed = int(np.asarray(ops.key_to_seed(key)))
    check(seed == golden["kernel_seed_u32"], f"kernel seed {seed}")
    off = golden["row_offset"]
    total = golden["rows"] + off
    for name, block in golden["mechanisms"].items():
        mech = make_mechanism({"name": name, **block["params"]})
        x, w = golden_sum_inputs(mech.clip)
        x, w = jnp.asarray(x), jnp.asarray(w)
        z = mech.quantize_batch(x, key)
        z_off = mech.quantize_batch(x, key, row_offset=off, total_rows=total)
        got = {
            ("materialized", "sum"): jnp.sum(z, axis=0, dtype=jnp.int32),
            ("materialized", "sum_weighted"): jnp.sum(
                z * w.astype(z.dtype)[:, None], axis=0, dtype=jnp.int32),
            ("materialized", "sum_offset"): jnp.sum(z_off, axis=0,
                                                    dtype=jnp.int32),
            ("fused", "sum"): mech.quantize_sum_batch(x, key),
            ("fused", "sum_weighted"): mech.quantize_sum_batch(x, key,
                                                               weights=w),
            ("fused", "sum_offset"): mech.quantize_sum_batch(
                x, key, row_offset=off, total_rows=total),
        }
        for (path, variant), value in got.items():
            want = block[variant]
            bad = words_off(value, want)
            check(bad == 0, f"{name} {path} {variant}: {bad}/{len(want)} "
                            f"words off")


# -- (c) the round ------------------------------------------------------------


def route(tr: FedTrainer) -> str:
    """Which hot path the trainer's round takes (dense or packed)."""
    where = (f" (cohort split over {tr.shards} chips, cross-chip psum)"
             if tr.shards > 1 else "")
    if not tr.cfg.fused_rounds:
        return ("materialized: Pallas encode kernel -> dense int32 SecAgg "
                f"sum{where} -> decode_sum + sgd")
    bits = hot_path_pack_bits(tr.mech, tr.cfg, tr.slate)
    if bits is None:
        return "fused dense: Pallas round-sum kernel -> decode_apply_sum"
    dim = int(tr.flat.size)
    words = wire.packed_words(dim, bits)
    kernels = ("Pallas packed round-sum kernel -> Pallas unpack_decode_apply"
               if packed_kernel_engages(dim, bits) else
               "Pallas dense round-sum kernel + jnp pack -> jnp "
               "unpack/decode/apply")
    return (f"fused packed, {bits}-bit wire, {words} words ({words % 128} "
            f"past a lane): {kernels}")


def train(label: str, **overrides) -> tuple[FedTrainer, float]:
    """A trainer on the paper's EMNIST setup, after one scan block;
    returns it with the held-out loss of its final evaluation."""
    cfg = FedConfig(rounds=ROUNDS, scan_block=ROUNDS, collect_sums=True,
                    **overrides)
    tr = FedTrainer(make_mechanism(MECHANISM), cfg)
    t0 = time.perf_counter()
    hist = tr.train(rounds=ROUNDS, eval_every=ROUNDS,
                    log=lambda m: print(f"  [{label}] {m}", flush=True))
    print(f"  [{label}] first block incl. compile: "
          f"{time.perf_counter() - t0} s", flush=True)
    return tr, hist[-1]["loss"]


def check_trainer(check: Checks, label: str, tr: FedTrainer, loss: float,
                  rounds: int):
    print(f"  [{label}] route: {route(tr)}", flush=True)
    check(np.isfinite(loss), f"{label}: loss {loss} finite")
    check(bool(np.all(np.isfinite(np.asarray(tr.flat)))),
          f"{label}: params finite")
    eps, alpha = tr.accountant.dp_epsilon(1e-5)
    check(tr.accountant.rounds == rounds and np.isfinite(eps) and eps > 0,
          f"{label}: accountant eps={eps} at delta=1e-5 (alpha={alpha}) "
          f"after {tr.accountant.rounds} rounds")
    check("tpu_custom_call" in compiled_block_text(tr),
          f"{label}: Pallas kernel in the compiled round block")


def compare_sums(check: Checks, label: str, a: FedTrainer, b: FedTrainer):
    ok = len(a.round_sums) == len(b.round_sums) > 0
    for r, (sa, sb) in enumerate(zip(a.round_sums, b.round_sums)):
        bad = words_off(sa, sb)
        if bad:
            print(f"  round {r}: {bad}/{sa.size} words differ", flush=True)
            ok = False
    check(ok, f"{label}: released sums equal in all "
              f"{len(a.round_sums)} rounds")


def phase_round(check: Checks):
    print(f"(c) FedTrainer, EMNIST CNN, {MECHANISM}", flush=True)
    trainers = {}
    for fused in (False, True):
        label = "fused" if fused else "unfused"
        tr, loss = train(label, fused_rounds=fused)
        t0 = time.perf_counter()
        tr.run_block(ROUNDS)
        jax.block_until_ready(tr.flat)
        dt = time.perf_counter() - t0
        print(f"  [{label}] smoke timing, not a metric: {ROUNDS} rounds in "
              f"{dt} s ({ROUNDS / dt} rounds/s, host clock)", flush=True)
        check_trainer(check, label, tr, loss, 2 * ROUNDS)
        trainers[label] = tr
    compare_sums(check, "fused vs unfused", trainers["unfused"],
                 trainers["fused"])
    drift = float(np.max(np.abs(np.asarray(trainers["unfused"].flat)
                                - np.asarray(trainers["fused"].flat))))
    print(f"  max |params unfused - fused| = {drift}", flush=True)


# -- (d) the shard engine on four chips ---------------------------------------


def phase_shard(check: Checks):
    devices = jax.devices()
    print(f"(d) shard engine on {len(devices)} chips vs scan on one",
          flush=True)
    if not check(len(devices) == 4, f"{len(devices)} devices visible"):
        return
    scan, _ = train("scan")
    check(scan.flat.sharding.device_set == {devices[0]},
          "scan: params on one chip")
    for mode in ("full", "stream"):
        label = f"shard/{mode}"
        tr, loss = train(label, engine="shard", staging=mode)
        check_trainer(check, label, tr, loss, ROUNDS)
        check(tr.shards == 4, f"{label}: {tr.shards} cohort shards")
        check(tr.flat.sharding.device_set == set(devices),
              f"{label}: params replicated on all four chips")
        text = compiled_block_text(tr)
        check("all-reduce" in text and "num_partitions=4" in text,
              f"{label}: one program over 4 partitions with a cross-chip "
              f"all-reduce (the SecAgg sum)")
        compare_sums(check, f"{label} vs scan", scan, tr)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only phase (d), the shard engine on a "
                         "4-chip mesh")
    args = ap.parse_args()
    enable_compile_cache()
    device = jax.devices()[0]
    print(f"(a) device: {device.platform} {device.device_kind}, "
          f"{jax.device_count()} visible", flush=True)
    if device.platform != "tpu":
        print("chip_smoke: no TPU found; this smoke test runs only on the "
              "chip", file=sys.stderr)
        return 1
    check = Checks()
    if args.chips == 4:
        phase_shard(check)
    else:
        phase_goldens(check)
        phase_round(check)
    if check.failures:
        print(f"chip_smoke: {len(check.failures)} check(s) failed:",
              file=sys.stderr)
        for f in check.failures:
            print(f"  {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": jax.device_count()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
