"""The fifth registered engine: buffered asynchronous rounds under a
traffic-shaped arrival process.

Every other engine barriers a round on its whole cohort. ``"async"``
models production traffic instead (docs/async.md): clients *arrive*
under a seeded Poisson/diurnal process (``fed/arrivals.py``), compute
against the model version they fetched (integer staleness, bounded by a
refetch protocol at ``async_max_staleness``), stragglers whose compute
latency exceeds ``async_timeout`` miss the aggregation, and the server
aggregates on a cadence — FedBuff-style buffered aggregation draining
exactly ``async_cadence`` updates per aggregation — rather than on a
barrier.

What it REUSES is the point: the same integer SecAgg sum, the same
mechanism decode, the same server-optimizer apply, and the same
accountant as every synchronous engine. Each aggregation is accounted at
its REALIZED buffer size (``trainer._account_realized``) — a straggler
contributes nothing and the aggregation is composed at the surviving
count, which is strictly more epsilon, never less — so the tracked eps
series stays bit-identical to accountant queries (the parity test
replays the realized sizes through a fresh accountant).

Staleness enters the ROUND, never the accounting:

  * each buffered client's gradient is taken at the parameter version it
    fetched — a ring of the last ``max_staleness + 1`` parameter vectors
    rides the jitted carry, and each slate row gathers its own version;
  * the staleness-weight policy (``fed/updates.py``) discounts the
    DECODED aggregate by a scalar — post-processing of the privatized
    release, so the DP guarantee is untouched;
  * participation stays a {0, 1} mask inside the SecAgg sum (a float
    per-client weight would break the one-message sensitivity the
    accounting assumes).

The degenerate corner is load-bearing: with ``max_staleness == 0``, no
timeout, and full staging, the engine reuses ``rounds.make_round_step``
VERBATIM — the same traced program as the ``perround`` engine — so
``cadence == clients_per_round`` reduces bit-identically to synchronous
training by construction, not by luck (tests/test_async_engine.py).

At population scale the data plane streams: ``staging="stream"`` stages
only each aggregation's realized cohort, gathered host-side through a
bounded LRU over ``partition.client_data`` by replaying the device key
stream (the ``staging.stage_stream_block`` determinism contract) — host
+ device bytes are O(cadence) datasets, independent of ``num_clients``,
so N=1e6 simulated clients never exist in memory at once.
"""
from __future__ import annotations

from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np

from repro.fed import cohort, rounds
from repro.fed.arrivals import ArrivalSimulator, make_arrivals
from repro.fed.engine import Engine, register_engine
from repro.fed.updates import ClientUpdate, StalenessPolicy


def _cadence(cfg) -> int:
    return int(cfg.async_cadence or cfg.clients_per_round)


@register_engine("async")
class AsyncEngine(Engine):
    """Buffered asynchronous aggregation under seeded arrival traffic."""

    stages_population = True
    supports_streaming = True
    # engine spec options (make_engine("async:cadence=64,max_staleness=8"))
    # -> the FedConfig fields they set. Full arrival-process specs with
    # their own options ("diurnal:period=24,amplitude=0.5") don't fit the
    # comma-separated engine spec grammar — set cfg.async_arrivals
    # directly for those; the bare process name works here.
    spec_options = {
        "cadence": "async_cadence",
        "max_staleness": "async_max_staleness",
        "staleness_weight": "async_staleness_weight",
        "arrivals": "async_arrivals",
        "rate": "async_rate",
        "latency": "async_latency",
        "timeout": "async_timeout",
    }

    @classmethod
    def validate(cls, cfg, mech):
        super().validate(cfg, mech)
        cadence = _cadence(cfg)
        if cadence < 1 or cadence > cfg.num_clients:
            raise ValueError(
                f"async_cadence={cadence} must be in [1, num_clients="
                f"{cfg.num_clients}]"
            )
        if cfg.async_max_staleness < 0:
            raise ValueError(
                f"async_max_staleness must be >= 0, got "
                f"{cfg.async_max_staleness}"
            )
        if cfg.subsampling != "fixed":
            raise ValueError(
                "engine 'async' realizes its cohort from the arrival "
                "process (async_arrivals), not from Poisson subsampling; "
                "use subsampling='fixed'"
            )
        if cfg.dropout > 0:
            raise ValueError(
                "engine 'async' models stragglers with async_timeout "
                "(arrival-latency timeouts), not i.i.d. dropout; set "
                "dropout=0"
            )
        if cfg.async_rate is not None and cfg.async_rate <= 0:
            raise ValueError(
                f"async_rate must be > 0, got {cfg.async_rate}"
            )
        if cfg.async_timeout is not None and cfg.async_timeout <= 0:
            raise ValueError(
                f"async_timeout must be > 0, got {cfg.async_timeout}"
            )
        # fail fast on malformed policy / arrival specs (constructing
        # them validates)
        StalenessPolicy(max_staleness=cfg.async_max_staleness,
                        weight=cfg.async_staleness_weight)
        make_arrivals(cfg.async_arrivals, rate=float(cadence))

    def __init__(self, trainer):
        super().__init__(trainer)
        cfg = trainer.cfg
        self.cadence = _cadence(cfg)
        # the cohort slate IS the aggregation buffer: the server drains
        # exactly `cadence` updates per aggregation.
        trainer.slate = self.cadence
        self.max_staleness = int(cfg.async_max_staleness)
        self.policy = StalenessPolicy(
            max_staleness=self.max_staleness,
            weight=cfg.async_staleness_weight,
        )
        self._streamed = cfg.staging == "stream"
        # The synchronous corner reuses the perround/scan round step
        # verbatim: same traced program => bit-identical by construction.
        # (make_round_step decodes at clients_per_round, so the corner
        # requires cadence == clients_per_round.)
        self._plain = (
            self.max_staleness == 0
            and cfg.async_timeout is None
            and not self._streamed
            and self.cadence == cfg.clients_per_round
        )
        rate = (float(cfg.async_rate) if cfg.async_rate is not None
                else float(self.cadence))  # ~one aggregation per time unit
        self.sim = ArrivalSimulator(
            make_arrivals(cfg.async_arrivals, rate=rate),
            self.cadence,
            seed=cfg.seed,
            max_staleness=self.max_staleness,
            mean_latency=cfg.async_latency,
            timeout=cfg.async_timeout,
        )
        # most recent aggregation's buffer as typed metadata records —
        # the same ClientUpdate the AggregatorServer's intake validates
        # (payloads stay inside the SecAgg sum by design; only identity/
        # staleness/participation metadata exists server-side).
        self.last_buffer: list = []
        # bounded client-data LRU for streamed staging (capacity a few
        # cohorts: repeat arrivals within a neighborhood hit the cache,
        # memory stays O(cadence) datasets independent of num_clients)
        self._data_cache: OrderedDict = OrderedDict()
        self._cache_cap = max(4 * self.cadence, 256)

    # -- jit construction ---------------------------------------------------
    def build(self):
        tr, cfg = self.tr, self.tr.cfg
        if self._plain:
            step = rounds.make_round_step(
                tr.mech, cfg, tr.server_opt, tr.slate, tr._client_grad
            )
            self._round_jit = jax.jit(step)
            return
        self._discounted = self.policy._parse_weight()[0] != "uniform"
        step = self._make_async_round_step()
        self._round_jit = jax.jit(step)
        # parameter-version ring: hist[v] is the params v aggregations
        # ago, hist[0] current. All rows start at init (a row older than
        # the run is never selected: realized staleness <= buffer index).
        self._hist = jnp.tile(tr.flat[None, :], (self.max_staleness + 1, 1))

    def _make_async_round_step(self):
        """The buffered-aggregation round step: per-row stale parameter
        gather -> clipped gradient -> fused/materialized integer encode ->
        {0,1}-masked SecAgg sum -> decode at the realized count -> scalar
        staleness discount -> server-optimizer apply -> ring shift."""
        tr, cfg = self.tr, self.tr.cfg
        mech, opt, slate = tr.mech, tr.server_opt, tr.slate
        client_grad = tr._client_grad
        S = self.max_staleness
        streamed = self._streamed
        discounted = self._discounted
        fused = cfg.fused_rounds
        # timeout-straggled aggregations can realize empty: guard the
        # apply exactly like the heterogeneous engines do
        apply = rounds.make_server_apply(opt, cfg, hetero=True)

        def round_step(hist, opt_state, key, data, stale,
                       delivered, discount=None):
            # identical key evolution to the synchronous engines (3
            # splits/round) — the streamed stager replays it on the host
            key, k_sample, k_enc, _ = cohort.split_round_keys(cfg, key)
            if streamed:
                batch = data  # staged in slate order
            else:
                ids, _ = cohort.sample_slate(cfg, slate, k_sample)
                batch = rounds.index_batch(data, ids)
            if S == 0:
                grads = rounds.cohort_grads(client_grad, hist[0], batch)
            else:
                # each buffer member computed against the version it
                # fetched: gather per-row parameters from the ring
                grads = rounds.cohort_grads(client_grad, hist[stale], batch,
                                            per_client_params=True)
            part = delivered
            if fused:
                z_sum = mech.quantize_sum_batch(grads, k_enc, weights=part)
            else:
                z = mech.quantize_batch(grads, k_enc)
                z = z * part.astype(z.dtype)[:, None]  # stragglers: 0
                z_sum = jnp.sum(z, axis=0, dtype=z.dtype)
            n_real = jnp.sum(part, dtype=jnp.int32)
            n_dec = jnp.maximum(n_real, 1)  # empty: releases nothing
            g_hat = mech.decode_sum(z_sum, n_dec)
            if discounted:
                g_hat = g_hat * discount  # post-processing of the release
            new, new_state = apply(hist[0], opt_state, g_hat, n_real)
            if S == 0:
                new_hist = new[None, :]
            else:
                new_hist = jnp.concatenate([new[None, :], hist[:-1]], axis=0)
            new_hist, new_state = jax.lax.optimization_barrier(
                (new_hist, new_state)
            )
            return new_hist, new_state, key, z_sum, n_real

        return round_step

    # -- checkpoint state (fed/checkpointing.py engine hooks) ----------------
    # The async trajectory depends on state beyond (flat, opt_state, key):
    # the arrival simulator's RNG + aggregation-time trace (staleness is
    # computed by searchsorted against past aggregation times) and, when
    # staleness is live, the parameter-version ring. Serializing exactly
    # that makes a resumed run bit-identical to the uninterrupted one
    # (tests/test_fed_tasks.py::test_async_checkpoint_resume).

    def state(self):
        from repro.fed.checkpointing import pack_host_rng

        tree = {
            "sim_rng": pack_host_rng(self.sim._rng),
            "sim_times": np.asarray(self.sim._agg_times, np.float64),
        }
        if not self._plain:
            tree["hist"] = self._hist
        return tree

    def state_template(self, steps_done: int):
        # one aggregation time per accounted round: checkpoints land on
        # round boundaries, so len(_agg_times) == steps_done
        tree = {
            "sim_rng": np.zeros(6, np.uint64),
            "sim_times": np.zeros(steps_done, np.float64),
        }
        if not self._plain:
            tree["hist"] = self._hist
        return tree

    def load_state(self, tree) -> None:
        from repro.fed.checkpointing import unpack_host_rng

        self.sim._rng = unpack_host_rng(tree["sim_rng"])
        self.sim._agg_times = [float(t) for t in tree["sim_times"]]
        self.sim._next_index = len(self.sim._agg_times)
        if not self._plain:
            self._hist = jnp.asarray(tree["hist"])

    # -- streamed data plane ------------------------------------------------
    def _client_data_cached(self, cid: int):
        cache = self._data_cache
        if cid in cache:
            cache.move_to_end(cid)
            return cache[cid]
        data = self.tr.task.client_batch(cid)
        cache[cid] = data
        if len(cache) > self._cache_cap:
            cache.popitem(last=False)
        return data

    def _stage_cohort(self):
        """Stage ONE aggregation's cohort by replaying the device key
        stream on the host (jax.random is deterministic in or out of
        jit): bytes staged are O(cadence) datasets regardless of
        num_clients."""
        tr, cfg = self.tr, self.tr.cfg
        _, k_sample, _, _ = cohort.split_round_keys(cfg, tr._key)
        ids = np.asarray(cohort.sample_slate(cfg, tr.slate, k_sample)[0])
        leaves = treedef = None
        for u, cid in enumerate(ids):
            cl, cdef = jax.tree_util.tree_flatten(
                self._client_data_cached(int(cid))
            )
            if leaves is None:
                treedef = cdef
                leaves = [np.empty((tr.slate,) + l.shape, l.dtype)
                          for l in cl]
            for buf, l in zip(leaves, cl):
                buf[u] = l
        nbytes = sum(buf.nbytes for buf in leaves)
        tr.staged_bytes_last_block = nbytes
        tr.staged_bytes_total += nbytes
        data = jax.tree_util.tree_unflatten(
            treedef, [jnp.asarray(buf) for buf in leaves]
        )
        return data, ids

    # -- the loop -----------------------------------------------------------
    def advance(self, n_rounds: int):
        tr, cfg = self.tr, self.tr.cfg
        for _ in range(n_rounds):
            sched = self.sim.next_buffer()
            ids = None
            if self._streamed:
                with tr.timings.scope("stage"):
                    data, ids = self._stage_cohort()
            else:
                data = tr.client_data
                if not self._plain:
                    # replay the slate ids for the buffer metadata (the
                    # plain corner skips this: zero overhead vs perround)
                    _, k_sample, _, _ = cohort.split_round_keys(cfg, tr._key)
                    ids = np.asarray(
                        cohort.sample_slate(cfg, tr.slate, k_sample)[0]
                    )
            if self._plain:
                tr.flat, tr.opt_state, tr._key, z_sum, n_real = (
                    self._round_jit(tr.flat, tr.opt_state, tr._key, data)
                )
            else:
                stale = jnp.asarray(sched.staleness)
                delivered = jnp.asarray(sched.delivered)
                args = (self._hist, tr.opt_state, tr._key, data,
                        stale, delivered)
                disc = 1.0
                if self._discounted:
                    disc = self.policy.discount(
                        sched.staleness[sched.delivered]
                    )
                    args = args + (jnp.float32(disc),)
                self._hist, tr.opt_state, tr._key, z_sum, n_real = (
                    self._round_jit(*args)
                )
                tr.flat = self._hist[0]
            if cfg.collect_sums:
                tr.round_sums.append(np.asarray(z_sum))
            n_real = int(np.asarray(n_real))
            # every aggregation is accounted at its REALIZED buffer size
            # — the tracked eps series mirrors the accountant exactly
            tr._account_realized([n_real])
            self._record_buffer(sched, ids)
            tr.round_extras.append(self._buffer_extras(sched, n_real))

    def _record_buffer(self, sched, ids):
        """The aggregation's buffer as typed ClientUpdate metadata (the
        shared intake format — fed/updates.py). Payloads intentionally
        stay inside the SecAgg sum: per-client messages never exist
        server-side."""
        version = sched.index
        self.last_buffer = [
            ClientUpdate(
                payload=np.zeros(0),
                client_id=(int(ids[i]) if ids is not None else -1),
                round_tag=version - int(sched.staleness[i]),
                staleness=int(sched.staleness[i]),
                weight=int(sched.delivered[i]),
            )
            for i in range(self.cadence)
        ]

    def _buffer_extras(self, sched, n_real: int) -> dict:
        s = sched.staleness
        extras = {
            "arrived": int(self.cadence),
            "delivered": int(n_real),
            "staleness_mean": float(np.mean(s)),
            "staleness_max": int(np.max(s)),
            "sim_time": float(sched.time),
        }
        if not self._plain and self._discounted:
            extras["staleness_discount"] = float(
                self.policy.discount(s[sched.delivered])
            )
        return extras
