"""Reduce one profiler trace (``.xplane.pb``) to what the per-layer
metrics read.

On a TPU each event of a device plane's ``XLA Ops`` line is named by the
compiled HLO instruction itself (``%_flat.7 = s32[...] custom-call(...),
custom_call_target="tpu_custom_call", ...``), so every device op is
classed from the compiled program's own text:

* ``pallas``: a ``custom-call`` with ``custom_call_target="tpu_custom_call"``
  (every Pallas kernel);
* ``collective``: all-reduce, all-gather, reduce-scatter, all-to-all and
  collective-permute (and their async start/done halves);
* ``control``: while, conditional and call, whose events enclose the ops
  of their bodies and are left out of every time;
* ``other``: everything else XLA emitted (fusions, convolutions, copies,
  slices, sorts, ...).

The harness's own host spans (``jax.profiler.TraceAnnotation``) sit on the
host plane on the same clock. ``bench.window`` bounds the traced window;
each idle gap of a device is named by the innermost harness span that
covers its midpoint.
"""
from __future__ import annotations

import collections
import re

COLLECTIVES = frozenset((
    "all-reduce", "all-reduce-start", "all-reduce-done",
    "all-gather", "all-gather-start", "all-gather-done",
    "reduce-scatter", "all-to-all",
    "collective-permute", "collective-permute-start",
    "collective-permute-done",
))
CONTROL = frozenset(("while", "conditional", "call"))
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


def _skip_shape(rhs: str) -> str:
    """The text after an instruction's result shape (shapes may be tuples
    whose element layouts hold parentheses of their own)."""
    rhs = rhs.lstrip()
    if not rhs.startswith("("):
        return rhs.split(" ", 1)[1] if " " in rhs else ""
    depth = 0
    for i, ch in enumerate(rhs):
        depth += ch == "("
        depth -= ch == ")"
        if depth == 0:
            return rhs[i + 1:]
    return ""


def parse_op(text: str) -> tuple[str, str, str]:
    """``(instruction name, opcode, class)`` of one HLO instruction text."""
    name, sep, rhs = text.partition(" = ")
    name = name.strip().lstrip("%")
    if not sep:
        return name, "", "other"
    opcode = _skip_shape(rhs).strip().split("(", 1)[0].strip()
    if opcode == "custom-call" and 'custom_call_target="tpu_custom_call"' in rhs:
        cls = "pallas"
    elif opcode in COLLECTIVES:
        cls = "collective"
    elif opcode in CONTROL:
        cls = "control"
    else:
        cls = "other"
    return name, opcode, cls


def _union(intervals):
    """Merge (start, end) intervals; returns the sorted disjoint union."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _host_spans(plane):
    spans = []
    for line in plane.lines:
        for ev in line.events:
            if ev.name.startswith(SPAN_PREFIX):
                spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                              ev.name))
    return spans


def _covering(spans, t):
    """Name of the innermost (shortest) harness span covering time t."""
    best = None
    for s, e, name in spans:
        if s <= t <= e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return best[2] if best else "no harness span"


def reduce(profile, top: int = 10) -> dict:
    """Reduce a ``jax.profiler.ProfileData`` to seconds.

    Returns ``window_s`` (the ``bench.window`` span), per device the busy
    seconds (union of its non-control op intervals inside the window),
    ``busy_s`` averaged over devices, device seconds per class
    (``class_s``, summed over devices), the ``top`` ops by device time,
    and the ``top`` longest idle gaps with the span that covers each.
    """
    spans = []
    devices = {}
    for plane in profile.planes:
        if plane.name == "/host:CPU":
            spans = _host_spans(plane)
            continue
        m = _DEVICE_PLANE.match(plane.name)
        if m is None:
            continue
        for line in plane.lines:
            if line.name == "XLA Ops":
                devices[int(m.group(1))] = [
                    (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                    for ev in line.events]
    windows = [(s, e) for s, e, n in spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace holds no {WINDOW_SPAN!r} host span")
    if not devices:
        raise ValueError("trace holds no TPU device plane with XLA Ops")
    w0, w1 = windows[0]
    class_s = collections.Counter()
    op_s = collections.Counter()
    busy = {}
    gaps = []
    for dev, events in sorted(devices.items()):
        leaf = []
        for s, e, text in events:
            if e <= w0 or s >= w1:
                continue
            name, opcode, cls = parse_op(text)
            if cls == "control":
                continue
            s, e = max(s, w0), min(e, w1)
            leaf.append((s, e))
            class_s[cls] += (e - s) * 1e-9
            op_s[f"{name} [{opcode or '?'}]"] += (e - s) * 1e-9
        merged = _union(leaf)
        busy[dev] = sum(e - s for s, e in merged) * 1e-9
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append(((b - a) * 1e-9, _covering(spans, (a + b) / 2),
                             dev))
    gaps.sort(key=lambda g: -g[0])
    return {
        "window_s": (w1 - w0) * 1e-9,
        "devices": len(devices),
        "busy_by_device_s": busy,
        "busy_s": sum(busy.values()) / len(busy),
        "class_s": dict(class_s),
        "top_ops": [[n, s] for n, s in op_s.most_common(top)],
        "idle_gaps": [[f"{name} (TPU:{dev})" if len(devices) > 1 else name,
                       s] for s, name, dev in gaps[:top]],
        "spans": spans,
    }


def reduce_file(path: str, top: int = 10) -> dict:
    from jax.profiler import ProfileData

    return reduce(ProfileData.from_file(path), top)


def reduce_bytes(data: bytes, top: int = 10) -> dict:
    from jax.profiler import ProfileData

    return reduce(ProfileData.from_serialized_xspace(data), top)
