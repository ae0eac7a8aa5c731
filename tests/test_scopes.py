"""The round's stages named inside the program (docs/telemetry.md):

* the jitted round carries the device scopes ``fed.grads``,
  ``fed.client_grad``, ``fed.encode_sum``, ``fed.secure_sum`` and
  ``fed.decode_apply`` in its ops' metadata, on every path the engines
  build — the async engine's buffered round included (the shard engine
  on four devices: tests/shard_engine_checks.py);
* ``Timings.scope`` writes a ``fed.<name>`` span into a profiler trace's
  host plane, on the device ops' clock;
* the engines' host scopes (``dispatch``, ``finish_block``, ``account``)
  are counted, and ``eps_eval`` counts the exact epsilon evaluations.
"""
import glob
import os
import re

import jax
import jax.numpy as jnp
import pytest

from conftest import small_trainer
from repro.telemetry import Timings

FIVE = {"fed.grads", "fed.client_grad", "fed.encode_sum", "fed.secure_sum",
        "fed.decode_apply"}
QUIET = dict(eval_every=4, log=lambda *_: None)


def _scopes(lowered) -> set:
    return set(re.findall(r"fed\.[a-z_]+", lowered.as_text(debug_info=True)))


def _lower_block(tr):
    args = (tr.flat, tr.opt_state, tr._key, tr.client_data)
    if tr.cfg.engine == "perround":
        return tr.engine._round_jit.lower(*args)
    if tr.cfg.engine == "async":
        # the buffered round: parameter ring, per-row staleness, delivered
        stale = jnp.zeros(tr.slate, jnp.int32)
        delivered = jnp.ones(tr.slate, bool)
        return tr.engine._round_jit.lower(tr.engine._hist, *args[1:], stale,
                                          delivered)
    if tr.cfg.engine == "shard":
        return tr.engine._block_jit(2).lower(*args)
    return tr.engine._block_jit.lower(*args, 2)


@pytest.mark.parametrize("engine, overrides", [
    ("scan", {}),
    ("perround", {}),
    ("shard", {"shards": 1}),
    ("scan", {"subsampling": "poisson", "dropout": 0.3}),
    ("async", {"async_max_staleness": 1}),
], ids=["scan", "perround", "shard-1", "scan-poisson-dropout", "async-stale"])
def test_round_carries_the_five_scopes(engine, overrides):
    tr = small_trainer(engine, **overrides)
    assert _scopes(_lower_block(tr)) == FIVE


@pytest.mark.parametrize("overrides", [
    {"fused_rounds": True},
    {"fused_rounds": True, "wire_packed": True},
], ids=["fused", "fused-packed"])
def test_fused_round_sums_inside_encode_sum(overrides):
    """With fused rounds the encode and the cohort sum are one streamed
    kernel under ``fed.encode_sum``: a one-chip round has no separate
    SecAgg sum, so no ``fed.secure_sum``."""
    tr = small_trainer("scan", **overrides)
    assert _scopes(_lower_block(tr)) == FIVE - {"fed.secure_sum"}


def test_timings_scope_is_a_profiler_span(tmp_path):
    from jax.profiler import ProfileData

    timings = Timings()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with timings.scope("outer"):
            with timings.scope("inner"):
                jax.block_until_ready(jax.numpy.ones(4) + 1)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    host = [p for p in ProfileData.from_file(path).planes
            if p.name == "/host:CPU"]
    spans = {ev.name: (ev.start_ns, ev.start_ns + ev.duration_ns)
             for plane in host for line in plane.lines
             for ev in line.events if ev.name.startswith("fed.")}
    assert set(spans) == {"fed.outer", "fed.inner"}
    (o0, o1), (i0, i1) = spans["fed.outer"], spans["fed.inner"]
    assert o0 <= i0 <= i1 <= o1
    assert {k: v["count"] for k, v in timings.summary().items()} == {
        "outer": 1, "inner": 1}


@pytest.mark.parametrize("engine", ["scan", "perround", "shard"])
def test_engine_host_scopes(engine):
    tr = small_trainer(engine)
    tr.train(rounds=4, **QUIET)
    counts = {k: v["count"] for k, v in tr.timings.summary().items()}
    blocks = counts["round_block"]
    assert counts["dispatch"] == (4 if engine == "perround" else blocks)
    assert counts["account"] == counts["dispatch"]
    assert counts.get("finish_block", 0) == (0 if engine == "perround"
                                             else blocks)
    # a fixed cohort costs one exact evaluation, at construction
    assert counts["eps_eval"] == 1


def test_eps_eval_counts_exact_evaluations():
    """Under Poisson subsampling and dropout every realized cohort size
    not seen before costs one exact evaluation; the nominal size costs
    one at construction."""
    tr = small_trainer("scan", subsampling="poisson", dropout=0.3)
    tr.train(rounds=8, **QUIET)
    cpr = tr.cfg.clients_per_round
    new_sizes = {n for n in tr.realized_n if n > 0 and n != cpr}
    assert tr.timings.summary()["eps_eval"]["count"] == 1 + len(new_sizes)
