"""Timing scopes for the metrics plane: host spans on the device trace's
clock.

``Timings.scope(name)`` accumulates the scope's host wall-clock duration
and call count (``summary()`` is what trackers receive via
``log_timings``) and opens ``jax.profiler.TraceAnnotation("fed." +
name)`` over the same interval. Under a profiler trace every scope is then
a span on the host plane, on the same clock as the device's ops, so an
idle gap on the device can be named by the innermost span the host was
in. Outside a trace the annotation costs next to nothing.

The host scopes:

* ``stage``: staging client data (every engine);
* ``round_block``: one advance of any engine (``FedTrainer``);
* ``dispatch``: one jitted call of the scan, perround, shard or async
  engine. Dispatch is asynchronous, so this is the enqueue, not the
  device's work;
* ``finish_block``: absorbing a jitted block's outputs; with collected
  sums or heterogeneous cohorts it reads them back, which waits for the
  device;
* ``account``: composing rounds in the Renyi accountant, with
  ``eps_eval`` inside it once per exact epsilon evaluation (a realized
  cohort size not seen before), so the count of ``eps_eval`` in
  ``summary()`` is the number of exact evaluations a run made;
* ``grads``/``encode``/``secure_sum``/``apply``: the host engine's
  stages, each a dispatch of its own, and the aggregator's
  ``secure_sum``/``apply``.

Inside a jitted round the stages are device scopes: ``fed/rounds.py``
wraps them in ``jax.named_scope`` (``fed.grads``, ``fed.client_grad``,
``fed.encode_sum``, ``fed.secure_sum``, ``fed.decode_apply``). The
compiler keeps each op's scope in its ``op_name`` metadata, which a
device trace carries per op, so the device time of one round splits by
stage even where XLA fuses across them; the host scopes measure what the
host does around the round.
"""
from __future__ import annotations

import time
from contextlib import contextmanager

import jax

SPAN_PREFIX = "fed."


class Timings:
    """Accumulates named wall-clock scope durations; each scope is also a
    ``fed.<name>`` span in a profiler trace."""

    def __init__(self):
        self._seconds: dict = {}
        self._counts: dict = {}

    @contextmanager
    def scope(self, name: str):
        with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                dt = time.perf_counter() - t0
                self._seconds[name] = self._seconds.get(name, 0.0) + dt
                self._counts[name] = self._counts.get(name, 0) + 1

    def summary(self) -> dict:
        """{scope: {"seconds": total, "count": calls}} — the
        ``log_timings`` payload."""
        return {
            name: {"seconds": round(self._seconds[name], 6),
                   "count": self._counts[name]}
            for name in self._seconds
        }
