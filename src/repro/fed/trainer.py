"""FedTrainer — the thin orchestrator over engine + accountant + budget.

Owns the shared state every registered engine operates on (mechanism,
config, staged data, flat params, server-optimizer state, round RNG key,
Renyi accountant) plus the engine-independent services: exact per-round
accounting at the realized cohort size, privacy-budget halting, periodic
evaluation, and checkpoint/resume (params + optimizer state + accountant
history + the round RNG key save and restore to a BIT-IDENTICAL
continuation — a resumed run reproduces the uninterrupted run's params
and epsilon sequence exactly, on every engine). How rounds actually
execute lives in the registered engines (``repro.fed.engines``).
"""
from __future__ import annotations

import functools
import time
from typing import Optional

import jax
import jax.flatten_util
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.mechanisms import Mechanism
from repro.core.renyi import RenyiAccountant
from repro.fed import checkpointing, cohort, rounds, staging
from repro.fed.config import FedConfig, validate_config
from repro.fed.engine import get_engine, make_engine
from repro.fed import engines as _engines  # noqa: F401  (registers the four)
from repro.fed.tasks import make_task
from repro.optim import make_optimizer
from repro.telemetry import RoundEmitter, Timings, make_tracker


class FedTrainer:
    def __init__(self, mech: Mechanism, fed_cfg: FedConfig, tracker=None):
        # cfg.engine is a bare registered name OR a spec string
        # ("async:cadence=64,max_staleness=8"): make_engine parses and
        # validates it ("unknown engine" first), apply() normalizes the
        # field to the bare name with the spec's config overrides set —
        # on a COPY, never mutating the caller's config object.
        espec = make_engine(fed_cfg.engine)
        fed_cfg = espec.apply(fed_cfg)
        engine_cls = get_engine(espec.name)
        validate_config(fed_cfg)
        engine_cls.validate(fed_cfg, mech)
        self.mech = mech
        self.cfg = fed_cfg
        self._mesh = None
        self._task_ctx = None  # set by the shard engine on a 2-D mesh
        self.shards = 1
        # Heterogeneous cohorts (docs/privacy.md): Poisson subsampling and/or
        # dropout make the realized cohort size a per-round random variable.
        # The jitted engines keep static shapes by gradient-computing a
        # fixed-size cohort SLATE and masking non-participants out of the
        # SecAgg sum; the accountant then composes each round at its
        # realized size (trainer.realized_n).
        self._hetero = cohort.is_hetero(fed_cfg)
        self.slate = int(cohort.base_slate(fed_cfg))
        # Telemetry (docs/telemetry.md): the tracker argument wins over
        # the cfg.track spec; both accept make_tracker specs. The emitter
        # is built once `flat` exists (it needs the dimension for the
        # SecAgg sum-bits column) and run metadata is published at the
        # end of __init__, when the engine has claimed its mesh.
        self.tracker = make_tracker(
            tracker if tracker is not None else fed_cfg.track
        )
        self.timings = Timings()
        # The TASK — what a round trains (fed/tasks.py): model init, the
        # per-client loss over an opaque batch pytree, client data, eval.
        # Built before the engine so the engine can bind a model axis
        # (the shard engine's 2-D client x model mesh) onto it.
        self.task = make_task(fed_cfg.task, fed_cfg)
        # The engine may claim resources (shard: device mesh) and adjust
        # the slate before anything is staged or traced.
        self.engine = engine_cls(self)
        # collect_sums / streaming bookkeeping (see FedConfig)
        self.round_sums: list = []
        self.staged_bytes_total = 0
        self.staged_bytes_last_block = 0
        # realized cohort size per round (every engine appends here; for
        # fixed cohorts without dropout it is constantly clients_per_round)
        self.realized_n: list = []
        # per-round tracker extras (engines may append one dict per round
        # — e.g. the async engine's staleness/arrival stats — folded into
        # the round records' "extra" column, schema untouched)
        self.round_extras: list = []
        key = jax.random.key(fed_cfg.seed)
        self.params = self.task.init_params(key)
        self.flat, self.unravel = jax.flatten_util.ravel_pytree(self.params)
        # The pluggable server optimizer (decode-then-apply boundary of
        # every engine). "sgd" is the paper's w - lr*g_hat, bit-identical
        # to the optimizer-free engines; state rides the scan/shard carry.
        self.server_opt = make_optimizer(
            fed_cfg.server_opt, **(fed_cfg.server_opt_options or {})
        )
        self.opt_state = self.server_opt.init(self.flat)
        self._rng = np.random.default_rng(fed_cfg.seed + 7)  # host engine only
        self._key = jax.random.key(fed_cfg.seed + 11)
        self.accountant = RenyiAccountant(alphas=fed_cfg.accountant_alphas)
        self._last_ckpt: Optional[int] = None
        # Self-accounting: the mechanism carries its own parameters, so the
        # exact per-round aggregate-level eps vector comes straight from the
        # object that encodes — no second parameter hand-off to drift. With
        # fixed cohorts all rounds are identical, so the nominal vector is
        # computed once and composed additively; under subsampling/dropout
        # each round is composed at its REALIZED cohort size via
        # _eps_vector (memoized per size, backed by the privacy cache).
        # Under the shard engine the size is always the FULL cross-shard
        # cohort — the SecAgg sum spans every shard, so the mechanism's
        # amplification-by-aggregation sees all participants, never the
        # per-shard slice.
        self._per_round_eps = self._exact_eps(fed_cfg.clients_per_round)
        self._eps_by_n = {fed_cfg.clients_per_round: self._per_round_eps}
        if self.engine.stages_population and fed_cfg.staging != "stream":
            with self.timings.scope("stage"):
                self.client_data, nbytes = staging.stage_full(
                    self.task, fed_cfg, self._mesh
                )
            self.staged_bytes_total += nbytes
        self._build_shared_jits()
        self.engine.build()
        if self._mesh is not None:
            # Commit the carried state to the mesh (replicated) up front:
            # the first donated block call then compiles with the same
            # input shardings every later call has — one compile, not two.
            self._commit_to_mesh()
        self._emitter = RoundEmitter(
            self.tracker, engine=fed_cfg.engine, mechanism=mech,
            alphas=fed_cfg.accountant_alphas, delta=fed_cfg.budget_delta,
            budget_eps=fed_cfg.budget_eps, dim=int(self.flat.size),
            pack_bits=self._wire_pack_bits(),
        )
        self.tracker.run_started(self._run_meta())

    # -- telemetry (docs/telemetry.md) --------------------------------------
    def _wire_pack_bits(self) -> Optional[int]:
        """The run's effective wire width for the round records'
        wire_bits/pack_width columns: the fused hot path's b-bit codec
        when it engages (rounds.hot_path_pack_bits), else the shard
        engine's minimal-width packed cross-shard sum
        (core/secagg.secure_sum_bounded), else None (dense wire)."""
        from repro.core import wire

        cfg = self.cfg
        bits = rounds.hot_path_pack_bits(self.mech, cfg, self.slate)
        if bits is None and cfg.engine == "shard" and cfg.shard_packed is not False:
            bound = self.mech.sum_bound(self.slate)
            if wire.packable(bound):
                bits = wire.sum_bits(bound)
        return bits

    def _run_meta(self) -> dict:
        """Run-level tracker metadata: the trajectory fingerprint (same
        sha256 the checkpoints carry), mechanism + engine identity, and
        mesh geometry."""
        cfg = self.cfg
        mesh = None
        if self._mesh is not None:
            mesh = {"axes": {str(k): int(v)
                             for k, v in self._mesh.shape.items()},
                    "devices": len(self._mesh.devices.ravel())}
        return {
            "kind": "fed_train",
            "fingerprint": bytes(checkpointing.fingerprint(self)).hex(),
            "engine": cfg.engine,
            "task": self.task.spec(),
            "mechanism": self.mech.describe(),
            "mechanism_spec": self.mech.spec(),
            "num_clients": cfg.num_clients,
            "clients_per_round": cfg.clients_per_round,
            "subsampling": cfg.subsampling,
            "dropout": cfg.dropout,
            "server_opt": cfg.server_opt,
            "budget_eps": cfg.budget_eps,
            "budget_delta": cfg.budget_delta,
            "accountant_alphas": list(cfg.accountant_alphas),
            "dim": int(self.flat.size),
            "shards": self.shards,
            "mesh": mesh,
            "backend": jax.default_backend(),
        }

    def _advance_tracked(self, n_rounds: int):
        """THE decode-apply-boundary hook: every engine's rounds flow
        through here (round() and run_block() both do), so one advance ==
        one timed scope and one batch of per-round tracker records whose
        eps/realized_n series mirror the accountant bit-identically."""
        t0 = time.perf_counter()
        with self.timings.scope("round_block"):
            self.engine.advance(n_rounds)
        if self._emitter.enabled:
            # jax dispatch is async: without blocking, a "round" is just
            # its enqueue and rounds_per_sec would be fantasy. Only the
            # tracked path pays this sync — noop tracking stays free.
            jax.block_until_ready(self.flat)
            self._emitter.emit(
                self.accountant.history, self.realized_n,
                time.perf_counter() - t0, extras=self.round_extras,
            )
        else:
            self._emitter.emitted = self.accountant.rounds

    # -- shared jits (host engine pieces, every engine) ----------------------
    def _build_shared_jits(self):
        mech, unravel = self.mech, self.unravel
        # ctx carries the model axis ONLY on the shard engine's 2-D mesh:
        # the tensor-parallel client_grad contains model-axis collectives
        # and is valid only inside that engine's shard_map. Every other
        # engine (and the host-side _client_grads jit) gets the plain
        # single-shard gradient.
        ctx = self._task_ctx
        self._client_grad = rounds.make_client_grad(
            mech, unravel, self.cfg, self.task, ctx=ctx
        )
        if ctx is None:
            self._client_grads = jax.jit(functools.partial(
                rounds.cohort_grads, self._client_grad))
        self._encode = jax.jit(jax.vmap(mech.encode, in_axes=(0, 0)))
        self._quantize_batch = jax.jit(lambda g, k: mech.quantize_batch(g, k))
        self._decode = jax.jit(lambda zsum, n: mech.decode_sum(zsum, n))

    def _commit_to_mesh(self):
        repl = NamedSharding(self._mesh, P())
        put = lambda x: jax.device_put(x, repl)
        self.flat = put(self.flat)
        self._key = put(self._key)
        self.opt_state = jax.tree_util.tree_map(put, self.opt_state)

    def _finish_block(self, out):
        """Absorb one jitted block's outputs (blocked engines). Reading the
        collected sums or the realized counts back waits for the block."""
        with self.timings.scope("finish_block"):
            self.flat, self.opt_state, self._key, sums, ns = out
            if self.cfg.collect_sums:
                self.round_sums.extend(np.asarray(sums))
            if self._hetero:
                self._account_realized(np.asarray(ns))

    # -- privacy accounting -------------------------------------------------
    def _exact_eps(self, n: int) -> np.ndarray:
        """One exact evaluation of the per-round eps vector at cohort size
        n; the "eps_eval" count in timings is how many a run made."""
        with self.timings.scope("eps_eval"):
            return np.asarray([self.mech.per_round_epsilon(n, a)
                               for a in self.cfg.accountant_alphas])

    def _eps_vector(self, n: int) -> np.ndarray:
        """Exact per-round eps vector (over cfg.accountant_alphas) for a
        realized cohort of n clients. Memoized per size; each distinct size
        costs one exact accountant evaluation per alpha (served by the
        privacy cache across trainers/processes). n = 0 releases nothing
        (the all-zero SecAgg sum is data-independent) — eps 0."""
        n = int(n)
        if n not in self._eps_by_n:
            if n <= 0:
                v = np.zeros(len(self.cfg.accountant_alphas))
            else:
                v = self._exact_eps(n)
            self._eps_by_n[n] = v
        return self._eps_by_n[n]

    def _account(self, n_rounds: int):
        """Fixed-cohort composition: every round at clients_per_round."""
        with self.timings.scope("account"):
            for _ in range(n_rounds):
                self.realized_n.append(self.cfg.clients_per_round)
                self.accountant.step(self._per_round_eps)

    def _account_realized(self, ns) -> None:
        """Heterogeneous composition: each round at its REALIZED size."""
        with self.timings.scope("account"):
            for n in np.asarray(ns).reshape(-1):
                n = int(n)
                self.realized_n.append(n)
                self.accountant.step(self._eps_vector(n))

    def budget_spent(self) -> tuple:
        """(eps spent at cfg.budget_delta, remaining eps) — requires
        cfg.budget_eps to be set."""
        cfg = self.cfg
        if cfg.budget_eps is None:
            raise ValueError("no privacy budget configured (cfg.budget_eps)")
        spent, _ = self.accountant.dp_epsilon(cfg.budget_delta)
        return spent, max(0.0, cfg.budget_eps - spent)

    # -- checkpoint / resume (fed/checkpointing.py; docs/engines.md) --------
    def save_checkpoint(self) -> str:
        """Checkpoint the full resumable state at the current round count:
        params, server-optimizer state, the round RNG key, the host
        sampling RNG, and the accountant's realized per-round eps history."""
        path = checkpointing.save_checkpoint(self)
        self._last_ckpt = self.accountant.rounds
        return path

    def restore_checkpoint(self, step: Optional[int] = None) -> int:
        """Restore from cfg.ckpt_dir (the latest step by default) and
        return the restored round count. The continuation is bit-identical
        to the uninterrupted run on every engine: params, optimizer state,
        RNG streams, and the accounted epsilon sequence all resume
        exactly where the checkpoint left them."""
        step = checkpointing.restore_checkpoint(self, step)
        self._last_ckpt = step
        return step

    def _maybe_checkpoint(self):
        cfg = self.cfg
        if not cfg.ckpt_dir or not cfg.ckpt_every:
            return
        done = self.accountant.rounds
        if done and done % cfg.ckpt_every == 0 and done != self._last_ckpt:
            self.save_checkpoint()

    def _cap_to_ckpt(self, want: int) -> int:
        """Split block sizes so block boundaries land exactly on ckpt_every
        multiples (chunking is bit-invariant, so this never changes the
        trained parameters)."""
        if not self.cfg.ckpt_dir or not self.cfg.ckpt_every:
            return want
        to_boundary = self.cfg.ckpt_every - (
            self.accountant.rounds % self.cfg.ckpt_every
        )
        return min(want, to_boundary)

    # -- the loop -----------------------------------------------------------
    def round(self, t: int = 0):
        """Advance one round (any engine; for blocked engines this is a
        1-round block)."""
        self._advance_tracked(1)

    def run_block(self, n_rounds: int):
        """Advance ``n_rounds`` rounds inside jitted blocks (blocked
        engines: scan and shard): params + optimizer state are donated to
        each call, and blocks longer than cfg.scan_block are split into
        chunks (each distinct chunk length compiles once)."""
        if not self.engine.blocked:
            raise ValueError(
                f"run_block requires a blocked engine ('scan' or 'shard'), "
                f"got {self.cfg.engine!r}"
            )
        self._advance_tracked(n_rounds)

    def evaluate(self):
        """Held-out metrics from the task; always reports "loss"."""
        flat = self.flat
        if self._mesh is not None and self._task_ctx is None:
            # the shard engine leaves flat committed (replicated) on the
            # mesh; evaluate on an uncommitted host copy so the eval jit
            # never mixes device sets with the single-device eval arrays.
            # (With a model axis the task evaluates ON the mesh instead —
            # tensor-parallel eval needs the axis collectives.)
            flat = jnp.asarray(np.asarray(flat))
        return self.task.evaluate(flat, self.unravel)

    def train(self, rounds: Optional[int] = None, eval_every: int = 25,
              log=print):
        """Run up to ``rounds`` further rounds; with cfg.budget_eps set,
        log the remaining (eps, budget_delta)-DP budget at every eval
        point and halt at budget exhaustion — exactly at the last
        affordable round for fixed cohorts, at the first eval/block
        boundary whose realized spend crosses the budget under
        subsampling/dropout (docs/privacy.md). With cfg.ckpt_dir/
        ckpt_every set, checkpoints land exactly on ckpt_every multiples
        (blocked engines split blocks at the boundaries, recording an
        extra eval point at each split); after restore_checkpoint(),
        round numbers continue from the restored count."""
        rounds = self.cfg.rounds if rounds is None else rounds
        cfg = self.cfg
        budget = cfg.budget_eps
        history = []
        t0 = time.time()
        done0 = self.accountant.rounds  # nonzero after a resume

        def record(done):
            m = self.evaluate()
            m.update(round=done, seconds=round(time.time() - t0, 1))
            msg = (f"[{self.mech.name}] round {done:4d} "
                   f"loss={m['loss']:.4f}")
            if "accuracy" in m:
                msg += f" acc={m['accuracy']:.4f}"
            if "ppl" in m:
                msg += f" ppl={m['ppl']:.2f}"
            if budget is not None:
                spent, remaining = self.budget_spent()
                m.update(eps_spent=spent, eps_remaining=remaining)
                msg += (f" eps_spent={spent:.3f}/{budget:g} "
                        f"(delta={cfg.budget_delta:g})")
            history.append(m)
            self.tracker.log_eval(dict(m))
            log(msg)

        def affordable(want: int) -> int:
            """How many of the next ``want`` rounds the budget still buys:
            an exact projection with the constant per-round vector for
            fixed cohorts, a nominal-cohort lookahead (realized spend
            re-checked next call) under subsampling/dropout."""
            if budget is None:
                return want
            if self.budget_spent()[1] <= 0:
                return 0
            k = self.accountant.rounds_within_budget(
                budget, cfg.budget_delta, self._per_round_eps
            )
            return want if k > want else int(k)

        halted = False
        if self.engine.blocked:
            done = 0
            while done < rounds:
                want = self._cap_to_ckpt(min(eval_every, rounds - done))
                block = affordable(want)
                if block == 0:
                    halted = True
                    break
                if budget is not None and self._hetero:
                    # the realized spend is only known AFTER a round: advance
                    # one round at a time and stop at the first crossing
                    # (overshoot <= one round; the nominal lookahead above
                    # only caps the attempt)
                    ran = 0
                    while ran < block:
                        self.run_block(1)
                        ran += 1
                        if self.budget_spent()[1] <= 0:
                            halted = True
                            break
                    done += ran
                    self._maybe_checkpoint()
                    record(done0 + done)
                    if halted:
                        break
                else:
                    self.run_block(block)
                    done += block
                    self._maybe_checkpoint()
                    record(done0 + done)
        else:
            for t in range(rounds):
                # for hetero budget runs affordable() returns 0 at the first
                # call after the realized spend crosses — overshoot <= 1 round
                if affordable(1) == 0:
                    halted = True
                    break
                self.round(t)
                self._maybe_checkpoint()
                if (t + 1) % eval_every == 0 or t == rounds - 1:
                    record(done0 + t + 1)
        if halted:
            spent, _ = self.budget_spent()
            log(f"[{self.mech.name}] privacy budget exhausted after "
                f"{self.accountant.rounds} rounds: eps_spent={spent:.4f} of "
                f"{budget:g} at delta={cfg.budget_delta:g}; halting")
            if not history or history[-1]["round"] != self.accountant.rounds:
                record(self.accountant.rounds)
        self.tracker.log_timings(self.timings.summary())
        self.tracker.flush()
        return history
