"""Find a piece of the benchmark by name: ``bench/<kind>/<name>.py``.

The kinds: ``configs`` (a model: its weights, the program's loss, the
reference's, FLOPs), ``inputs`` (a generator of client data),
``mechanisms`` (a plain reference of a mechanism), ``cohorts`` (the
reference's draw of one round's cohort), ``server_opts`` (the reference's
server step) and ``metrics`` (a per-layer reader). Adding one is adding a
file; nothing here names them.
"""
from __future__ import annotations

import importlib.util
import os

BENCH = os.path.dirname(os.path.abspath(__file__))
_LOADED: dict = {}


def load_path(path: str):
    """The module at ``path``, loaded once per process (so that its jitted
    functions keep their compiled programs)."""
    path = os.path.abspath(path)
    if path not in _LOADED:
        rel = os.path.relpath(os.path.splitext(path)[0], BENCH)
        name = "bench_" + "".join(c if c.isalnum() else "_" for c in rel)
        spec = importlib.util.spec_from_file_location(name, path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        _LOADED[path] = module
    return _LOADED[path]


def find(kind: str, name: str):
    """The module ``bench/<kind>/<name>.py``."""
    path = os.path.join(BENCH, kind, name + ".py")
    if not os.path.isfile(path):
        known = sorted(f[:-3] for f in os.listdir(os.path.join(BENCH, kind))
                       if f.endswith(".py"))
        raise KeyError(f"no {kind} {name!r} (bench/{kind}/{name}.py); "
                       f"known: {known}")
    return load_path(path)
