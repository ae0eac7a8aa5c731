"""The long-lived aggregator round-server: streamed client-update batches
in, privately-aggregated rounds out, health through the metrics plane.

``AggregatorServer`` is the service-shaped counterpart of ``FedTrainer``:
instead of synthesizing its own cohorts it ACCEPTS already-encoded client
updates (``submit``), continuous-batching style like
examples/serve_demo.py — a bounded queue applies backpressure (blocking
``submit`` waits for room; non-blocking submits are rejected and
counted), and an aggregation loop drains the queue on a cadence: every
``cohort`` buffered updates become one round — SecAgg sum in the encoded
integer domain, ``mech.decode_sum`` at the REALIZED count, one server-
optimizer step — accounted by the same exact Renyi accountant the
trainer uses and emitted through the same telemetry RoundEmitter, so a
service round's record is schema-identical to a training round's.

Intake is TYPED (``fed/updates.py``): ``submit`` takes ``ClientUpdate``
objects (client id, the model version fetched, {0,1} participation
weight, integer payload) — shape/dtype validation lives on the
dataclass, and the legacy bare ``(k, dim)`` array form still works
behind a ``DeprecationWarning`` shim. With ``engine="async:..."`` the
server runs the async engine's buffered-aggregation policy over the
real stream (docs/async.md): updates staler than ``max_staleness``
model versions are discarded (a remote client cannot be made to
refetch), weight-0 stragglers are masked out of the SecAgg sum with the
round accounted at the realized surviving count, and the staleness-
weight policy discounts the DECODED aggregate (post-processing of the
privatized release — the accounting is untouched).

The privacy budget is enforced BEFORE a round applies: the projected
(eps, delta)-DP spend of the candidate round is checked against
``budget_eps`` and the server halts exactly at exhaustion — the round
that would cross the budget is never aggregated, and further submits are
refused. Checkpoints ride PR 5's resumable-state machinery
(checkpoint/store.py): params + optimizer state + the accountant's
realized history, fingerprint-guarded, saved every ``ckpt_every``
rounds; ``resume()`` replays the accountant and re-anchors the tracker
series so eps/round continue without gaps. ``snapshot()`` is the
health/status surface (budget-remaining, queue depth, rounds served),
published through the tracker as well.

CLI (simulated client stream; docs/telemetry.md):

  PYTHONPATH=src python -m repro.launch.aggregator --smoke
  PYTHONPATH=src python -m repro.launch.aggregator --dim 512 --cohort 8 \\
      --batches 12 --budget-eps 60 --track json:runs/agg.json
"""
from __future__ import annotations

import argparse
import hashlib
import json
import queue
import threading
import time
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import store
from repro.core import wire
from repro.core.mechanisms import Mechanism, make_mechanism
from repro.core.renyi import RenyiAccountant
from repro.fed.engine import make_engine
from repro.fed.updates import (ClientUpdate, StalenessPolicy, UpdateBuffer,
                               as_updates)
from repro.launch.compile_cache import enable_compile_cache
from repro.optim import make_optimizer
from repro.telemetry import RoundEmitter, Timings, make_tracker

# the aggregation-policy knobs an "async:..." engine spec may set here:
# the rest of the async surface (arrival process, latency, timeout) is
# SIMULATION — this server receives real traffic and real lateness.
_POLICY_OPTIONS = ("cadence", "max_staleness", "staleness_weight")


def _resolve_policy(engine: Optional[str], cohort: int):
    """(engine_label, StalenessPolicy, cohort) for an engine spec.

    ``None`` (or "aggregator") keeps the legacy synchronous-cadence
    behavior: admit everything, no discount. An ``"async[:...]"`` spec
    adopts the async engine's buffered-aggregation policy, with
    ``cadence`` overriding the ``cohort`` constructor argument."""
    if engine is None or engine == "aggregator":
        return "aggregator", StalenessPolicy(), cohort
    espec = make_engine(engine)
    if espec.name != "async":
        raise ValueError(
            f"AggregatorServer aggregation policy must be 'async' (or "
            f"None for the legacy cadence), got engine {espec.name!r}"
        )
    opts = dict(espec.options)
    unknown = set(opts) - set(_POLICY_OPTIONS)
    if unknown:
        raise ValueError(
            f"aggregator engine spec accepts only {_POLICY_OPTIONS} "
            f"(arrival/latency/timeout options describe SIMULATED "
            f"traffic; this server receives real traffic), got "
            f"{sorted(unknown)}"
        )
    cohort = int(opts.get("cadence", cohort))
    max_staleness = opts.get("max_staleness")
    policy = StalenessPolicy(
        max_staleness=(int(max_staleness)
                       if max_staleness is not None else None),
        weight=str(opts.get("staleness_weight", "uniform")),
    )
    return "async", policy, cohort


class AggregatorServer:
    """One aggregation endpoint for a fixed (mechanism, dim) deployment."""

    def __init__(self, mech: Mechanism, dim: int, *, cohort: int = 8,
                 lr: float = 0.5, server_opt: str = "sgd",
                 server_opt_options: Optional[dict] = None,
                 queue_limit: int = 64,
                 budget_eps: Optional[float] = None,
                 budget_delta: float = 1e-5,
                 alphas: tuple = (2.0, 4.0, 8.0, 16.0, 32.0),
                 ckpt_dir: Optional[str] = None, ckpt_every: int = 0,
                 tracker=None, init_flat=None,
                 engine: Optional[str] = None):
        self.engine, self.policy, cohort = _resolve_policy(engine, cohort)
        if cohort < 1:
            raise ValueError(f"cohort must be >= 1, got {cohort}")
        if queue_limit < 1:
            raise ValueError(f"queue_limit must be >= 1, got {queue_limit}")
        if ckpt_every and not ckpt_dir:
            raise ValueError("ckpt_every requires ckpt_dir")
        self.mech = mech
        self.dim = int(dim)
        self.cohort = int(cohort)
        self.lr = float(lr)
        self.budget_eps = budget_eps
        self.budget_delta = float(budget_delta)
        self.ckpt_dir = ckpt_dir
        self.ckpt_every = int(ckpt_every)
        self.server_opt = make_optimizer(server_opt,
                                         **(server_opt_options or {}))
        self.flat = (jnp.zeros((self.dim,), jnp.float32)
                     if init_flat is None else jnp.asarray(init_flat))
        if self.flat.shape != (self.dim,):
            raise ValueError(
                f"init_flat shape {self.flat.shape} != ({self.dim},)"
            )
        self.opt_state = self.server_opt.init(self.flat)
        self.accountant = RenyiAccountant(alphas=tuple(alphas))
        self.realized_n: list = []
        # the bounded queue IS the backpressure: a blocking submit waits
        # for the aggregation loop to make room, a non-blocking one is
        # refused (and counted) — producers never grow server memory
        self.queue: queue.Queue = queue.Queue(maxsize=queue_limit)
        # drained updates awaiting a full cohort: a staleness-aware FIFO
        # of typed ClientUpdates (fed/updates.py) — the same buffer/policy
        # semantics as the async engine's simulated aggregations
        self.buffer = UpdateBuffer(self.policy)
        self._queued_updates = 0  # rows still inside the queue
        self.rounds_served = 0
        self.updates_aggregated = 0
        self.batches_accepted = 0
        self.batches_rejected = 0
        self.round_extras: list = []  # per-round staleness stats (tracker)
        self.halted = False
        self._eps_by_n: dict = {}
        self._t0 = time.time()
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.timings = Timings()
        self.tracker = make_tracker(tracker)
        self._emitter = RoundEmitter(
            self.tracker, engine=self.engine, mechanism=mech,
            alphas=self.accountant.alphas, delta=self.budget_delta,
            budget_eps=budget_eps, dim=self.dim,
        )
        self._decode = jax.jit(
            lambda z, n: self.mech.decode_sum(z, n), static_argnums=1
        )
        self.tracker.run_started(self._run_meta())

    # -- metadata / fingerprint ---------------------------------------------
    def _run_meta(self) -> dict:
        return {
            "kind": "aggregator",
            "fingerprint": bytes(self._fingerprint()).hex(),
            "engine": self.engine,
            "staleness_policy": self.policy.describe(),
            "mechanism": self.mech.describe(),
            "mechanism_spec": self.mech.spec(),
            "dim": self.dim,
            "cohort": self.cohort,
            "queue_limit": self.queue.maxsize,
            "server_opt": self.server_opt.name,
            "budget_eps": self.budget_eps,
            "budget_delta": self.budget_delta,
            "accountant_alphas": list(self.accountant.alphas),
            "backend": jax.default_backend(),
        }

    def _fingerprint(self) -> np.ndarray:
        """sha256 of the trajectory-defining service config — restoring a
        checkpoint written by a different mechanism/optimizer would replay
        an epsilon history that describes nothing real (same contract as
        fed/checkpointing.py)."""
        blob = json.dumps({
            "mechanism": self.mech.spec(), "dim": self.dim,
            "alphas": list(self.accountant.alphas), "lr": self.lr,
            "server_opt": self.server_opt.name,
        }, sort_keys=True, default=repr)
        return np.frombuffer(hashlib.sha256(blob.encode()).digest(), np.uint8)

    # -- intake --------------------------------------------------------------
    def current_version(self) -> int:
        """The model version a fetching client should stamp into its
        ``ClientUpdate.round_tag`` — one version per aggregation served."""
        return self.rounds_served

    def submit(self, updates, block: bool = True,
               timeout: Optional[float] = None) -> bool:
        """Enqueue one batch of already-encoded client updates: a
        ``ClientUpdate``, a sequence of them, or (DEPRECATED) a bare
        ``(k, dim)`` array — one row per client, upgraded to unversioned
        ``ClientUpdate``s behind a ``DeprecationWarning``. Shape/dtype
        validation lives on the dataclass (``ClientUpdate.validate``).
        Returns True when accepted. With ``block=True`` a full queue
        WAITS (backpressure) up to ``timeout``; otherwise the batch is
        refused immediately. A halted (budget-exhausted) server refuses
        everything."""
        if not (isinstance(updates, ClientUpdate)
                or (isinstance(updates, (list, tuple)) and updates
                    and all(isinstance(u, ClientUpdate) for u in updates))):
            warnings.warn(
                "bare-array AggregatorServer.submit() is deprecated; "
                "pass ClientUpdate objects (repro.fed.updates) so the "
                "server knows the model version each client fetched",
                DeprecationWarning, stacklevel=2,
            )
        updates = [u.validate(self.dim) for u in as_updates(updates)]
        if self.halted:
            self.batches_rejected += 1
            return False
        # count the rows before the (possibly blocking) put so a
        # concurrent drain can never observe a negative buffer
        self._queued_updates += len(updates)
        try:
            self.queue.put(updates, block=block, timeout=timeout)
        except queue.Full:
            self._queued_updates -= len(updates)
            self.batches_rejected += 1
            return False
        self.batches_accepted += 1
        return True

    def _drain_queue(self) -> None:
        while True:
            try:
                batch = self.queue.get_nowait()
            except queue.Empty:
                return
            self.buffer.extend(batch)
            self._queued_updates -= len(batch)

    # -- accounting ----------------------------------------------------------
    def _eps_vector(self, n: int) -> np.ndarray:
        n = int(n)
        if n not in self._eps_by_n:
            if n <= 0:
                # all-straggler aggregation: the all-zero SecAgg sum is
                # data-independent — nothing released, nothing spent
                v = np.zeros(len(self.accountant.alphas))
            else:
                v = np.asarray([
                    self.mech.per_round_epsilon(n, a)
                    for a in self.accountant.alphas
                ])
            self._eps_by_n[n] = v
        return self._eps_by_n[n]

    def budget_spent(self) -> tuple:
        """(eps spent at budget_delta, remaining eps or None)."""
        spent = float(self.accountant.dp_epsilon(self.budget_delta)[0])
        if self.budget_eps is None:
            return spent, None
        return spent, max(0.0, self.budget_eps - spent)

    def buffered_updates(self) -> int:
        """Client updates accepted but not yet aggregated (queued rows
        plus the drained partial cohort)."""
        return self._queued_updates + len(self.buffer)

    # -- the encoded-domain cohort sum ---------------------------------------
    def _secure_sum(self, take) -> np.ndarray:
        """The cohort's SecAgg sum over MIXED wire forms (fed/updates.py):
        dense payloads stack-and-sum as before; when the whole cohort
        arrived bit-packed at one width AND the cohort sum bound still
        fits a field (``wire.packable`` — true for small cohorts or wide
        payloads), the packed words are summed DIRECTLY (field-wise int32
        addition is exact below the bound) and unpacked once. Otherwise
        each packed payload unpacks at intake — either way the dense
        (dim,) sum is bit-identical (packing is exact)."""
        packed = [u for u in take if u.packed]
        if len(packed) == len(take) and take:
            bits = take[0].payload.bits
            if (all(u.payload.bits == bits for u in take)
                    and wire.packable(self.mech.sum_bound(len(take)), bits)):
                acc = np.zeros_like(take[0].payload.words, dtype=np.uint32)
                for u in take:
                    if u.weight:
                        acc = acc + u.payload.words.view(np.uint32)
                return wire.unpack_bits_np(
                    acc.view(np.int32), bits, self.dim
                )
        z = np.stack([u.payload_array() for u in take])
        w = np.asarray([u.weight for u in take], z.dtype)
        return (z * w[:, None]).sum(axis=0)

    # -- the aggregation cadence ---------------------------------------------
    def step(self) -> bool:
        """Aggregate ONE round if a full cohort is buffered: SecAgg sum
        of exactly ``cohort`` updates (FIFO), decode at the realized
        count, one server-optimizer step, exact accounting, one tracker
        record. Returns False when there is nothing to do — not enough
        updates, or the budget check halted the server (the crossing
        round is never applied)."""
        with self._lock:
            if self.halted:
                return False
            self._drain_queue()
            version = self.current_version()
            # the staleness policy prunes first (updates staler than
            # max_staleness model versions are discarded — a remote
            # client cannot be made to refetch), then the candidate
            # cohort is PEEKED so the budget check sees its realized
            # size before anything is committed
            candidates = self.buffer.peek(self.cohort, version)
            if len(candidates) < self.cohort:
                return False
            n_real = sum(u.weight for u in candidates)
            vec = self._eps_vector(n_real)
            if self.budget_eps is not None and n_real > 0:
                projected, _ = self.accountant.projected_dp_epsilon(
                    self.budget_delta, vec, rounds=1
                )
                if projected > self.budget_eps + 1e-12:
                    # exactly at exhaustion: this round never aggregates
                    self.halted = True
                    self.publish_snapshot()
                    self.tracker.flush()
                    return False
            take = self.buffer.take(self.cohort, version)
            t0 = time.perf_counter()
            with self.timings.scope("secure_sum"):
                # weight-0 stragglers are masked OUT of the SecAgg sum
                # ({0,1} weights only — fed/updates.py); the round is
                # accounted at the surviving count
                z_sum = jnp.asarray(self._secure_sum(take))
            if n_real > 0:
                with self.timings.scope("apply"):
                    g_hat = self._decode(z_sum, n_real)
                    disc = self.policy.discount(
                        [u.staleness for u in take if u.weight]
                    )
                    if disc != 1.0:
                        # scalar staleness discount: post-processing of
                        # the privatized release, accounting untouched
                        g_hat = g_hat * disc
                    self.flat, self.opt_state = self.server_opt.update(
                        g_hat, self.opt_state, self.flat, self.lr
                    )
                    jax.block_until_ready(self.flat)
            else:
                disc = 1.0
            self.realized_n.append(n_real)
            self.accountant.step(vec)
            self.rounds_served += 1
            self.updates_aggregated += n_real
            stal = [u.staleness for u in take]
            self.round_extras.append({
                "arrived": len(take),
                "delivered": n_real,
                "staleness_mean": float(np.mean(stal)) if stal else 0.0,
                "staleness_max": int(np.max(stal)) if stal else 0,
                "updates_discarded": self.buffer.discarded,
                # uplink realism: bytes this cohort's payloads occupied
                # AS SHIPPED (packed wire words vs dense int32 lanes)
                "uplink_bytes": int(sum(u.payload_nbytes for u in take)),
                "packed_payloads": int(sum(1 for u in take if u.packed)),
                **({"staleness_discount": float(disc)}
                   if self.engine == "async" else {}),
            })
            self._emitter.emit(self.accountant.history, self.realized_n,
                               time.perf_counter() - t0,
                               extras=self.round_extras)
            if (self.ckpt_dir and self.ckpt_every
                    and self.rounds_served % self.ckpt_every == 0):
                self.save_checkpoint()
            return True

    def drain(self, max_rounds: Optional[int] = None) -> int:
        """Aggregate rounds while full cohorts are available (bounded by
        ``max_rounds``); returns how many rounds were served."""
        served = 0
        while (max_rounds is None or served < max_rounds) and self.step():
            served += 1
        return served

    # -- long-lived service loop ---------------------------------------------
    def serve(self, poll: float = 0.005,
              idle_timeout: Optional[float] = None) -> None:
        """Run the aggregation loop in the calling thread until
        ``shutdown()``, budget exhaustion, or ``idle_timeout`` seconds
        without a full cohort arriving."""
        idle_since = time.time()
        while not self._stop.is_set() and not self.halted:
            if self.step():
                idle_since = time.time()
                continue
            if (idle_timeout is not None
                    and time.time() - idle_since > idle_timeout):
                return
            time.sleep(poll)

    def start(self, poll: float = 0.005) -> None:
        """Run ``serve`` on a background thread (producers call
        ``submit`` from their own threads; the bounded queue paces them)."""
        if self._thread is not None and self._thread.is_alive():
            raise RuntimeError("aggregator already started")
        self._stop.clear()
        self._thread = threading.Thread(
            target=self.serve, kwargs={"poll": poll}, daemon=True
        )
        self._thread.start()

    def shutdown(self, final_snapshot: bool = True) -> None:
        """Stop the service loop (if running), publish a final snapshot,
        and flush+close the tracker."""
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=30)
            self._thread = None
        if final_snapshot:
            self.publish_snapshot()
        self.tracker.log_timings(self.timings.summary())
        self.tracker.close()

    # -- health / status ------------------------------------------------------
    def snapshot(self) -> dict:
        """The health/status surface: budget-remaining, queue depth,
        rounds served (plus intake counters and uptime)."""
        spent, remaining = self.budget_spent()
        return {
            "engine": self.engine,
            "staleness_policy": self.policy.describe(),
            "rounds_served": self.rounds_served,
            "updates_aggregated": self.updates_aggregated,
            "updates_discarded": self.buffer.discarded,
            "queue_depth": self.queue.qsize(),
            "queue_limit": self.queue.maxsize,
            "pending_updates": self.buffered_updates(),
            "batches_accepted": self.batches_accepted,
            "batches_rejected": self.batches_rejected,
            "eps_spent": spent,
            "eps_remaining": remaining,
            "budget_eps": self.budget_eps,
            "halted": self.halted,
            "uptime_seconds": round(time.time() - self._t0, 3),
        }

    def publish_snapshot(self) -> dict:
        snap = self.snapshot()
        self.tracker.log_snapshot(snap)
        return snap

    # -- checkpoint / resume (PR 5's resumable-state machinery) ---------------
    def save_checkpoint(self) -> str:
        if not self.ckpt_dir:
            raise ValueError("no checkpoint directory configured (ckpt_dir)")
        hist = self.accountant.history
        alphas = self.accountant.alphas
        tree = {
            "flat": self.flat,
            "opt": self.opt_state,
            "eps_history": (np.stack(hist) if hist
                            else np.zeros((0, len(alphas)))),
            "realized_n": np.asarray(self.realized_n, np.int64),
            "fingerprint": self._fingerprint(),
        }
        return store.save(self.ckpt_dir, self.rounds_served, tree)

    def resume(self, step: Optional[int] = None) -> int:
        """Restore the latest (or given) checkpoint: params + optimizer
        state come back exactly, the accountant replays the realized
        history, and the tracker series re-anchors so eps/round continue
        without duplicate or missing indices."""
        if not self.ckpt_dir:
            raise ValueError("no checkpoint directory configured (ckpt_dir)")
        if step is None:
            step = store.latest_step(self.ckpt_dir)
            if step is None:
                raise FileNotFoundError(f"no checkpoints in {self.ckpt_dir}")
        fp = store.restore(self.ckpt_dir, step,
                           {"fingerprint": np.zeros(32, np.uint8)})
        if not np.array_equal(fp["fingerprint"], self._fingerprint()):
            raise ValueError(
                f"checkpoint step {step} in {self.ckpt_dir} was written by "
                f"a DIFFERENT mechanism/optimizer deployment (fingerprint "
                f"mismatch); its epsilon history does not describe this "
                f"server"
            )
        alphas = self.accountant.alphas
        data = store.restore(self.ckpt_dir, step, {
            "flat": self.flat,
            "opt": self.opt_state,
            "eps_history": np.zeros((step, len(alphas)), np.float64),
            "realized_n": np.zeros(step, np.int64),
        })
        self.flat = data["flat"]
        self.opt_state = data["opt"]
        self.accountant = RenyiAccountant(alphas=alphas)
        self.realized_n = []
        for n, vec in zip(data["realized_n"], data["eps_history"]):
            self.realized_n.append(int(n))
            self.accountant.step(vec)
        self.rounds_served = step
        self.updates_aggregated = sum(self.realized_n)
        self.halted = False
        self._emitter.sync(self.accountant.total_rdp(), step)
        return step


def simulate_client_batch(mech: Mechanism, dim: int, key, k: int):
    """k clients' encoded updates for the simulated stream: random
    bounded gradients through the mechanism's batched encoder — the same
    bytes a real client would submit."""
    k_g, k_e = jax.random.split(key)
    grads = jax.random.uniform(
        k_g, (k, dim), jnp.float32, -mech.clip, mech.clip
    )
    return np.asarray(mech.encode_batch(grads, k_e))


def simulate_client_updates(mech: Mechanism, dim: int, key, k: int, *,
                            round_tag: int, first_id: int = 0,
                            packed: bool = False) -> list:
    """The typed form of the simulated stream: the same encoded bytes,
    wrapped as ``ClientUpdate``s stamped with the model version the
    clients fetched — what a real (versioned) client deployment submits.
    ``packed=True`` ships each payload in the bit-packed wire form
    (``mech.encode_wire`` — ceil(log2(levels)) bits per coordinate
    instead of an int32 lane), the bandwidth-realistic uplink."""
    if packed:
        k_g, k_e = jax.random.split(key)
        grads = jax.random.uniform(
            k_g, (k, dim), jnp.float32, -mech.clip, mech.clip
        )
        keys = jax.random.split(k_e, k)
        return [
            ClientUpdate(payload=mech.encode_wire(g, kk),
                         client_id=first_id + i, round_tag=round_tag)
            for i, (g, kk) in enumerate(zip(grads, keys))
        ]
    rows = simulate_client_batch(mech, dim, key, k)
    return [
        ClientUpdate(payload=row, client_id=first_id + i,
                     round_tag=round_tag)
        for i, row in enumerate(rows)
    ]


def main():
    ap = argparse.ArgumentParser(
        description="Long-lived aggregator round-server over a simulated "
                    "client-update stream (docs/telemetry.md)")
    ap.add_argument("--mechanism", default="rqm:c=0.02,m=16,q=0.42",
                    help="mechanism spec string (the deployment's codec)")
    ap.add_argument("--dim", type=int, default=512)
    ap.add_argument("--cohort", type=int, default=8,
                    help="updates aggregated per round")
    ap.add_argument("--engine", default=None,
                    help="aggregation policy: None = legacy synchronous "
                         "cadence; an async engine spec adopts buffered-"
                         "async semantics, e.g. "
                         "'async:max_staleness=4,staleness_weight=poly:0.5'")
    ap.add_argument("--batch", type=int, default=4,
                    help="client updates per submitted batch")
    ap.add_argument("--batches", type=int, default=16,
                    help="batches the simulated clients stream")
    ap.add_argument("--queue-limit", type=int, default=8)
    ap.add_argument("--packed", action="store_true",
                    help="simulated clients upload bit-packed wire "
                         "payloads (mech.encode_wire) instead of dense "
                         "int32 lanes — the bandwidth-realistic uplink")
    ap.add_argument("--rate", type=float, default=0.0,
                    help="simulated batch arrivals/sec (0 = as fast as "
                         "backpressure allows)")
    ap.add_argument("--lr", type=float, default=0.5)
    ap.add_argument("--server-opt", default="sgd")
    ap.add_argument("--budget-eps", type=float, default=None)
    ap.add_argument("--budget-delta", type=float, default=1e-5)
    ap.add_argument("--track", default=None,
                    help="tracker spec, e.g. json:runs/agg.json or "
                         "csv:runs/agg.csv (docs/telemetry.md)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--snapshot-every", type=float, default=1.0,
                    help="seconds between printed health snapshots")
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke: tiny stream + a budget that exhausts "
                         "mid-stream; asserts drain/backpressure/halt "
                         "invariants and exits nonzero on violation")
    args = ap.parse_args()
    enable_compile_cache()
    if args.smoke:
        args.dim, args.cohort, args.batch = 64, 4, 4
        args.batches, args.queue_limit = 10, 4
        if args.budget_eps is None:
            args.budget_eps = 40.0

    mech = make_mechanism(args.mechanism)
    server = AggregatorServer(
        mech, args.dim, cohort=args.cohort, lr=args.lr,
        server_opt=args.server_opt, queue_limit=args.queue_limit,
        budget_eps=args.budget_eps, budget_delta=args.budget_delta,
        ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
        tracker=args.track, engine=args.engine,
    )
    if args.resume:
        step = server.resume()
        print(f"[aggregator] resumed at round {step}")

    def produce():
        key = jax.random.key(0)
        for i in range(args.batches):
            key, sub = jax.random.split(key)
            # typed intake: each simulated client stamps the model
            # version it fetched (the aggregation policy prunes/weights
            # by realized staleness)
            batch = simulate_client_updates(
                mech, args.dim, sub, args.batch,
                round_tag=server.current_version(),
                first_id=i * args.batch, packed=args.packed,
            )
            t0 = time.time()
            accepted = server.submit(batch, block=True, timeout=10.0)
            waited = time.time() - t0
            if not accepted:
                print(f"[client] batch {i} refused "
                      f"({'halted' if server.halted else 'queue full'})")
                if server.halted:
                    return
            elif waited > 0.05:
                print(f"[client] batch {i} backpressured {waited:.2f}s")
            if args.rate:
                time.sleep(1.0 / args.rate)

    producer = threading.Thread(target=produce, daemon=True)
    server.start()
    producer.start()
    t_last = 0.0
    while producer.is_alive():
        producer.join(timeout=0.05)
        if time.time() - t_last >= args.snapshot_every:
            t_last = time.time()
            print(f"[health] {server.publish_snapshot()}")
    # let the loop drain whatever a full cohort still covers
    deadline = time.time() + 10.0
    while (not server.halted and server.buffered_updates() >= server.cohort
           and time.time() < deadline):
        time.sleep(0.02)
    server.shutdown()
    snap = server.snapshot()
    print(f"[final] {snap}")

    if args.smoke:
        total = args.batches * args.batch
        ok = snap["rounds_served"] >= 1
        if server.halted:
            # budget-halted: spend stayed within budget, intake refused
            ok &= snap["eps_spent"] <= args.budget_eps + 1e-9
            ok &= not server.submit(
                np.zeros((args.batch, args.dim), np.int32), block=False
            )
        else:
            ok &= snap["rounds_served"] == total // args.cohort
        ok &= snap["pending_updates"] < server.cohort or server.halted
        # eps on the wire must equal the accountant's answer exactly
        ok &= snap["eps_spent"] == server.accountant.dp_epsilon(
            args.budget_delta)[0]
        if not ok:
            raise SystemExit(f"aggregator smoke FAILED: {snap}")
        print(f"aggregator smoke OK: {snap['rounds_served']} rounds, "
              f"halted={snap['halted']}, "
              f"eps_spent={snap['eps_spent']:.2f}")


if __name__ == "__main__":
    main()
