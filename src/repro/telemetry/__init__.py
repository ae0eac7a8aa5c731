"""Telemetry — the metrics plane for private federated rounds.

Three pieces (docs/telemetry.md has the full guide):

  * ``tracker`` — the ``@register_tracker`` registry + the four
    backends (``noop``/``json``/``csv``/``composite``), built from
    ``make_mechanism``-style spec strings (``"json:runs/a.json"``);
    ``write_bench_json`` is the one BENCH_*.json writer the benchmarks
    route through.
  * ``emit``    — ``RoundEmitter``, the single decode-apply-boundary
    hook: accounted rounds -> schema-stable records whose eps_spent /
    realized_n series are bit-identical to the accountant's history.
  * ``timing``  — ``Timings`` scopes: host wall-clock per scope, each
    also a ``fed.<scope>`` span in a profiler trace (stage / round_block /
    dispatch / finish_block / account / eps_eval, and the host engine's
    and aggregator's stages).

Every ``FedTrainer`` run emits through this plane (``FedConfig.track``
or the ``tracker=`` argument); ``launch/aggregator.py`` — the
long-lived round-server — additionally publishes health snapshots
(budget-remaining, queue depth, rounds served) through the same
tracker.
"""
from repro.telemetry.emit import RoundEmitter
from repro.telemetry.timing import Timings
from repro.telemetry.tracker import (
    CSV_COLUMNS,
    ROUND_FIELDS,
    SCHEMA_VERSION,
    CompositeTracker,
    CsvTracker,
    JsonTracker,
    NoopTracker,
    Tracker,
    get_tracker,
    make_tracker,
    parse_tracker_spec,
    register_tracker,
    tracker_names,
    write_bench_json,
)

__all__ = [
    "CSV_COLUMNS",
    "ROUND_FIELDS",
    "SCHEMA_VERSION",
    "CompositeTracker",
    "CsvTracker",
    "JsonTracker",
    "NoopTracker",
    "RoundEmitter",
    "Timings",
    "Tracker",
    "get_tracker",
    "make_tracker",
    "parse_tracker_spec",
    "register_tracker",
    "tracker_names",
    "write_bench_json",
]
