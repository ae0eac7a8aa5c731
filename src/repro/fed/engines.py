"""The synchronous registered round engines: scan, perround, host, shard.
(The fifth, buffered-asynchronous ``"async"``, lives in
``fed/async_engine.py`` and registers itself via the import at the
bottom of this module, keeping registration order stable.)

Same Algorithm-1 semantics under every engine (see the package docstring
in ``repro/fed/__init__.py`` and docs/engines.md); what differs is HOW
rounds execute:

  * ``"scan"`` (default) — device-resident: the population is staged once,
    a whole block of rounds runs inside a single jitted ``lax.scan`` with
    the flat parameter buffer AND server-optimizer state donated.
  * ``"perround"`` — the identical round step, one jitted call per round.
    Exists to prove the scan engine correct (bit-for-bit parity).
  * ``"host"`` — the legacy loop: numpy client sampling, per-round host
    stacking, per-client vmapped encode. The benchmark baseline.
  * ``"shard"`` — the scan block inside ``shard_map`` over a 1-D
    ``('shard',)`` mesh: global cohort sampling from the replicated key,
    per-shard gradient+encode over the n/S cohort slice, one cross-shard
    encoded-domain ``secure_sum`` per round (docs/scaling.md).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import wire
from repro.data.federated import sample_clients
from repro.distributed.step import MeshPlan
from repro.fed import cohort, rounds, staging
from repro.fed.engine import Engine, register_engine
from repro.launch.mesh import make_fed_mesh, make_shard_mesh
from repro.models.common import ParallelCtx


@register_engine("scan")
class ScanEngine(Engine):
    """Blocks of rounds in one jitted ``lax.scan`` (unrolled on CPU), the
    flat parameter buffer and optimizer state donated: zero host<->device
    transfers and zero dispatch per round."""

    blocked = True
    spec_options = {"block": "scan_block", "unroll": "scan_unroll"}

    def build(self):
        tr = self.tr
        step = rounds.make_round_step(
            tr.mech, tr.cfg, tr.server_opt, tr.slate, tr._client_grad
        )
        block = rounds.make_block(step, tr.cfg)
        self._block_jit = jax.jit(
            block, static_argnums=(4,), donate_argnums=(0, 1)
        )

    def advance(self, n_rounds: int):
        tr = self.tr
        done = 0
        while done < n_rounds:
            step = min(tr.cfg.scan_block, n_rounds - done)
            out = self._block_jit(
                tr.flat, tr.opt_state, tr._key, tr.client_data, step,
            )
            tr._finish_block(out)
            done += step
        if not tr._hetero:
            tr._account(n_rounds)


@register_engine("perround")
class PerRoundEngine(Engine):
    """The scan engine's round step driven one jitted call per round from
    Python — both trace the same ``round_step``, so a fixed seed yields
    bit-identical parameters (asserted in tests/test_fed_engine.py)."""

    def build(self):
        tr = self.tr
        step = rounds.make_round_step(
            tr.mech, tr.cfg, tr.server_opt, tr.slate, tr._client_grad
        )
        self._round_jit = jax.jit(step)

    def advance(self, n_rounds: int):
        tr = self.tr
        for _ in range(n_rounds):
            tr.flat, tr.opt_state, tr._key, z_sum, n_real = self._round_jit(
                tr.flat, tr.opt_state, tr._key, tr.client_data,
            )
            if tr.cfg.collect_sums:
                tr.round_sums.append(np.asarray(z_sum))
            if tr._hetero:
                tr._account_realized([n_real])
            else:
                tr._account(1)


@register_engine("host")
class HostEngine(Engine):
    """The legacy loop: numpy client sampling (fixed cohorts) or a replay
    of the device key stream (heterogeneous cohorts — identical realized
    cohort and eps sequence to the jitted engines), per-round host
    stacking of client data, per-client vmapped encode. Kept as the
    baseline the rounds/sec benchmark measures the scan engine against."""

    stages_population = False

    @classmethod
    def validate(cls, cfg, mech):
        super().validate(cfg, mech)
        if cfg.fused_rounds:
            raise ValueError(
                "engine 'host' does not support fused_rounds=True: the "
                "legacy loop is the materialized-encode benchmark "
                "baseline; use the scan/perround/shard engines for the "
                "fused hot path"
            )

    def advance(self, n_rounds: int):
        for _ in range(n_rounds):
            if self.tr._hetero:
                self._hetero_round()
            else:
                self._fixed_round()

    def _stack(self, ids):
        # one client_batch call per id (it re-synthesizes deterministically
        # on every call — the monolith's two-comprehension stacking
        # generated every cohort dataset twice per round); stack each leaf
        # of the task's opaque batch pytree along a leading cohort axis
        batches = [self.tr.task.client_batch(int(i)) for i in ids]
        data = jax.tree_util.tree_map(lambda *ls: np.stack(ls), *batches)
        return jax.tree_util.tree_map(jnp.asarray, data)

    def _fixed_round(self):
        # the host loop's stages are separate dispatches, so it times the
        # fine-grained telemetry scopes (stage/grads/encode/secure_sum/
        # apply) the fused jitted engines cannot observe (docs/telemetry.md)
        tr, cfg = self.tr, self.tr.cfg
        ids = sample_clients(tr._rng, cfg.num_clients, cfg.clients_per_round)
        with tr.timings.scope("stage"):
            data = self._stack(ids)
        with tr.timings.scope("grads"):
            grads = tr._client_grads(tr.flat, data)
        tr._key, sub = jax.random.split(tr._key)
        keys = jax.random.split(sub, cfg.clients_per_round)
        with tr.timings.scope("encode"):
            z = tr._encode(grads, keys)  # (n, dim) int32 (float for 'none')
        with tr.timings.scope("secure_sum"):
            z_sum = jnp.sum(z, axis=0, dtype=z.dtype)  # SecAgg sum emulation
        with tr.timings.scope("apply"):
            g_hat = tr._decode(z_sum, cfg.clients_per_round)
            tr.flat, tr.opt_state = tr.server_opt.update(
                g_hat, tr.opt_state, tr.flat, cfg.lr
            )
        if cfg.collect_sums:
            tr.round_sums.append(np.asarray(z_sum))
        tr._account(1)

    def _hetero_round(self):
        """Host round under subsampling/dropout: the legacy per-round host
        data staging, but cohort/participation come from the SAME device
        key stream the jitted engines evolve (4 splits per round), so the
        realized cohort sequence — and hence the accounted eps sequence —
        is identical on every engine."""
        tr, cfg = self.tr, self.tr.cfg
        tr._key, k_sample, k_enc, k_drop = jax.random.split(tr._key, 4)
        ids, valid = cohort.sample_slate(cfg, tr.slate, k_sample)
        with tr.timings.scope("stage"):
            data = self._stack(np.asarray(ids))
        with tr.timings.scope("grads"):
            grads = tr._client_grads(tr.flat, data)
        with tr.timings.scope("encode"):
            z = tr._quantize_batch(grads, k_enc)  # full slate, like engines
        part = cohort.participation(cfg, valid, k_drop)
        with tr.timings.scope("secure_sum"):
            z = z * part.astype(z.dtype)[:, None]
            z_sum = jnp.sum(z, axis=0, dtype=z.dtype)
        n_real = int(np.asarray(jnp.sum(part, dtype=jnp.int32)))
        if n_real > 0:
            with tr.timings.scope("apply"):
                g_hat = tr._decode(z_sum, n_real)
                tr.flat, tr.opt_state = tr.server_opt.update(
                    g_hat, tr.opt_state, tr.flat, cfg.lr
                )
        if cfg.collect_sums:
            tr.round_sums.append(np.asarray(z_sum))
        tr._account_realized([n_real])


@register_engine("shard")
class ShardEngine(Engine):
    """The scan engine distributed over a 1-D ``('shard',)`` device mesh
    via shard_map; per-round aggregation is an encoded-domain cross-shard
    sum — integer level indices, lane-packed when safe (core/secagg.py) —
    exactly as the mechanism's ``decode_sum``/``sum_bound`` contract
    expects of a real SecAgg deployment. On a 1-shard mesh the engine is
    bit-identical to ``"scan"``. Privacy is accounted for the FULL
    cross-shard cohort, never the per-shard count. ``staging="stream"``
    bounds host memory to each block's active cohort."""

    blocked = True
    supports_streaming = True
    spec_options = {
        "shards": "shards", "staging": "staging", "packed": "shard_packed",
        "model": "model_shards",
    }

    def __init__(self, trainer):
        super().__init__(trainer)
        tr, cfg, mech = trainer, trainer.cfg, trainer.mech
        self.model_shards = int(cfg.model_shards or 1)
        if cfg.shards:
            self.shards = cfg.shards
        else:
            # span every visible device with whatever the model axis
            # doesn't claim
            self.shards = max(1, jax.device_count() // self.model_shards)
        tr.shards = self.shards
        if cfg.subsampling == "poisson":
            # round the slate up so it splits evenly across shards
            slate = -(-tr.slate // self.shards) * self.shards
            if slate > cfg.num_clients:
                raise ValueError(
                    f"poisson cohort slate {slate} (rounded to "
                    f"{self.shards} shards) exceeds the population "
                    f"{cfg.num_clients}; lower max_cohort or shards"
                )
            tr.slate = slate
        elif cfg.clients_per_round % self.shards:
            raise ValueError(
                f"clients_per_round={cfg.clients_per_round} must "
                f"divide across {self.shards} shards"
            )
        # the packing-safety bound covers the WORST-case participant
        # count — the full slate (== clients_per_round when fixed); one
        # shared gate (wire.check_packable) serves engine validation,
        # secure_sum_bounded, and the aggregator intake
        if cfg.shard_packed:
            wire.check_packable(mech.sum_bound(tr.slate),
                                where="shard_packed=True: ")
        if self.model_shards > 1:
            # 2-D client x model mesh: the 'shard' axis still carries
            # ONLY integer SecAgg traffic; per-layer tensor-parallel
            # psums run over the 'model' axis inside each client's loss.
            if not tr.task.supports_model_axis:
                raise ValueError(
                    f"model_shards={self.model_shards} needs a task with "
                    f"supports_model_axis; task "
                    f"{tr.task.name!r} is single-shard only"
                )
            tr._mesh = make_fed_mesh(self.shards, self.model_shards)
            tr._plan = MeshPlan(mesh=tr._mesh, client_axes=("shard",),
                                model_axis="model")
            assert tr._plan.tp == self.model_shards
            # no client axes on the task ctx: a client's loss must stay
            # local to its shard (client_grad is mapped over the cohort
            # slice WITHIN a shard — cross-client collectives would sum
            # across cohort members)
            tr._task_ctx = ParallelCtx(model_axis="model",
                                       tp=self.model_shards)
            tr.task.bind_model_axis(tr._task_ctx, tr._mesh)
        else:
            tr._mesh = make_shard_mesh(self.shards)
            # pure client-parallel plan: every shard a whole client group
            tr._plan = MeshPlan(mesh=tr._mesh, client_axes=("shard",),
                                model_axis=None)
            assert tr._plan.tp == 1 and tr._plan.n_clients == self.shards

    def build(self):
        tr = self.tr
        step = rounds.make_shard_round_step(
            tr.mech, tr.cfg, tr.server_opt, tr.slate, self.shards,
            tr._client_grad,
        )
        streamed = tr.cfg.staging == "stream"
        data_spec = P(None, "shard") if streamed else P()

        def make_block_jit(length):
            block = rounds.make_block(step, tr.cfg, streamed=streamed)

            def block_l(flat, opt_state, key, data):
                return block(flat, opt_state, key, data, length)

            # P() entries covering the None (not collected) outputs map no
            # leaves — harmless placeholders keeping the spec tree aligned.
            # data_spec broadcasts over the batch pytree's leaves. On the
            # 2-D mesh both specs leave the model axis unmentioned: data
            # and carried state are replicated across model shards (the
            # tensor-parallel slicing happens INSIDE client_grad).
            mapped = jax.shard_map(
                block_l,
                mesh=tr._mesh,
                in_specs=(P(), P(), P(), data_spec),
                out_specs=(P(), P(), P(), P(), P()),
                check_vma=False,
            )
            return jax.jit(mapped, donate_argnums=(0, 1))

        self._blocks: dict = {}
        self._make_block_jit = make_block_jit

    def _block_jit(self, length: int):
        if length not in self._blocks:
            self._blocks[length] = self._make_block_jit(length)
        return self._blocks[length]

    def advance(self, n_rounds: int):
        tr, cfg = self.tr, self.tr.cfg
        done = 0
        while done < n_rounds:
            step = min(cfg.scan_block, n_rounds - done)
            if cfg.staging == "stream":
                with tr.timings.scope("stage"):
                    data, nbytes = staging.stage_stream_block(
                        tr.task, cfg, tr._mesh, tr.slate, tr._key, step
                    )
                tr.staged_bytes_last_block = nbytes
                tr.staged_bytes_total += nbytes
            else:
                data = tr.client_data
            out = self._block_jit(step)(
                tr.flat, tr.opt_state, tr._key, data
            )
            tr._finish_block(out)
            done += step
        if not tr._hetero:
            tr._account(n_rounds)


# Fifth engine, registered LAST so engine_names() order stays
# (scan, perround, host, shard, async) — the order the registry tests pin.
from repro.fed import async_engine as _async_engine  # noqa: E402,F401
