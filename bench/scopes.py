"""Split a profiler trace by the program's own scopes and spans.

The program names the stages of its round as device scopes
(``jax.named_scope`` in ``fed/rounds.py``: ``fed.grads``,
``fed.client_grad``, ``fed.encode_sum``, ``fed.secure_sum``,
``fed.decode_apply``) and what its host does as spans on the profiler's
host plane (``telemetry/timing.py``: ``fed.round_block``,
``fed.dispatch``, ``fed.finish_block``, ``fed.account``,
``fed.eps_eval``, ...).

Each device op is an instruction of a compiled program, and the
profiler keeps each program (its ``HloProto``) on the trace's
``/host:metadata`` plane. An instruction the program's code made carries
its scope path as ``op_name`` metadata (``jit(block)/while/body/.../
fed.grads/while/body/dynamic_update_slice``); one the compiler made (a
relayout copy, a loop it sank or widened) carries none and is given the
scope of what it serves (``attribute``). ``jax.profiler.ProfileData``
exposes neither, so this module reads both from the XSpace's protobuf
wire format itself: the formats are public
(``tsl/profiler/protobuf/xplane.proto``, ``xla/service/hlo.proto``), and
the reader needs no package beyond JAX. Times come from ``ProfileData``,
as in ``trace_reduce``.

``reduce`` gives, inside the ``bench.window`` span:

* ``scope_s``: device seconds (summed over devices) of the non-control
  ops under each innermost ``fed.*`` scope, ``UNSCOPED`` for the rest.
  An op's innermost scope is the last ``fed.*`` component of its path, so
  ``fed.grads`` holds only the map's own ops (the stacking of every
  client's gradient), not its clients' ``fed.client_grad``;
* ``unscoped_ops``: the costliest ops outside every scope;
* ``idle_by_span``: the devices' idle time, cut at host span boundaries
  and given to the innermost ``bench.*`` or ``fed.*`` span over it;
  ``host_bound_idle_s`` (averaged over devices) is the part whose
  innermost span is the program's (``fed.*``); ``idle_gaps``, the longest
  idle stretches so named;
* ``spans``: count and seconds of each ``fed.*`` host span, and
  ``blocks``, the number of ``bench.run_block`` calls.

    python3 bench/scopes.py TRACE.xplane.pb[.gz]   # prints the reduction
"""
from __future__ import annotations

import collections
import gzip
import json
import sys

import trace_reduce

SCOPE_PREFIX = "fed."
SPAN_PREFIXES = ("bench.", "fed.")
UNSCOPED = "unscoped"

# field numbers of tsl/profiler/protobuf/xplane.proto
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_EVENT_META, _PLANE_STAT_META = 2, 4, 5
_EVENT_META_NAME, _EVENT_META_STATS = 2, 5
_STAT_META_NAME = 2
_STAT_META_ID, _STAT_BYTES = 1, 6
_MAP_KEY, _MAP_VALUE = 1, 2
_HLO_PROTO = "Hlo Proto"
# and of xla/service/hlo.proto
_HLO_MODULE, _MODULE_COMPUTATIONS = 1, 3
_COMP_INSTRUCTIONS, _COMP_ID = 2, 5
_INSTR_NAME, _INSTR_OPCODE, _INSTR_METADATA, _INSTR_ID = 1, 2, 7, 35
_INSTR_OPERANDS, _INSTR_CALLED = 36, 38
_METADATA_OP_NAME = 2


def _varint(buf, i: int):
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of each field of one message: an int for
    a varint, a memoryview for a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, value


def _ints(value) -> list:
    """A repeated int64 field's values, packed or not."""
    if isinstance(value, int):
        return [value]
    out, i = [], 0
    while i < len(value):
        v, i = _varint(value, i)
        out.append(v)
    return out


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map_entry(entry):
    """``(key, value message)`` of one protobuf map entry."""
    fields = dict(_fields(entry))
    return fields.get(_MAP_KEY, 0), fields.get(_MAP_VALUE, b"")


def hlo_protos(data: bytes) -> dict:
    """``{program name: serialized HloProto}``: the compiled programs the
    profiler keeps on its ``/host:metadata`` plane, named as the device's
    ``XLA Modules`` events name them."""
    out = {}
    for num, plane in _fields(memoryview(data)):
        if num != _SPACE_PLANES:
            continue
        fields = list(_fields(plane))
        names = [_text(v) for n, v in fields if n == _PLANE_NAME]
        if names != ["/host:metadata"]:
            continue
        stat_names = {}
        for n, v in fields:
            if n == _PLANE_STAT_META:
                key, meta = _map_entry(v)
                stat_names[key] = _text(dict(_fields(meta)).get(
                    _STAT_META_NAME, b""))
        for n, v in fields:
            if n != _PLANE_EVENT_META:
                continue
            meta = list(_fields(_map_entry(v)[1]))
            name = "".join(_text(x) for a, x in meta if a == _EVENT_META_NAME)
            for a, stat in meta:
                if a != _EVENT_META_STATS:
                    continue
                st = dict(_fields(stat))
                if stat_names.get(st.get(_STAT_META_ID, 0)) == _HLO_PROTO:
                    out[name] = bytes(st.get(_STAT_BYTES, b""))
    return out


# instructions whose op_name says nothing of the work they serve: the
# compiler hoists and shares constants and parameters across scopes (and
# an op_name that is no path, such as "reduce_window_sum", lost its
# scopes where it was made)
_NO_WORK = ("constant", "parameter")
# instructions whose called computations are their own work (a fusion's
# fused ops, a loop's body); the others call a reducer or comparator
_HOLDS_WORK = ("fusion", "while", "call", "conditional")


def module_graph(hlo_proto: bytes):
    """The compiled module as ``(instrs, comp_of)``: per instruction id
    ``(name, opcode, op_name or None, operand ids, called computation
    ids)``, and the computation id of each instruction. Constants,
    parameters and op_names that are no path are read as no op_name."""
    module = dict(_fields(memoryview(hlo_proto))).get(_HLO_MODULE, b"")
    instrs, comp_of = {}, {}
    for num, comp in _fields(module):
        if num != _MODULE_COMPUTATIONS:
            continue
        fields = list(_fields(comp))
        cid = next((v for n, v in fields if n == _COMP_ID), 0)
        for n, ins in fields:
            if n != _COMP_INSTRUCTIONS:
                continue
            name, opcode, op_name, iid, operands, called = ("", "", None, 0,
                                                           [], [])
            for a, v in _fields(ins):
                if a == _INSTR_NAME:
                    name = _text(v)
                elif a == _INSTR_OPCODE:
                    opcode = _text(v)
                elif a == _INSTR_METADATA:
                    op_name = _text(dict(_fields(v)).get(_METADATA_OP_NAME,
                                                         b"")) or None
                elif a == _INSTR_ID:
                    iid = v
                elif a == _INSTR_OPERANDS:
                    operands += _ints(v)
                elif a == _INSTR_CALLED:
                    called += _ints(v)
            if opcode in _NO_WORK or "/" not in (op_name or ""):
                op_name = None
            instrs[iid] = (name, opcode, op_name, operands, called)
            comp_of[iid] = cid
    return instrs, comp_of


def _chain(op_name: str) -> tuple:
    return tuple(p for p in op_name.split("/") if p.startswith(SCOPE_PREFIX))


def innermost_scope(path: str) -> str:
    """The last ``fed.*`` component of an op's scope path."""
    chain = _chain(path or "")
    return chain[-1] if chain else UNSCOPED


def attribute(instrs: dict, comp_of: dict) -> dict:
    """``{instruction name: innermost scope}`` of one compiled module.

    An instruction with an ``op_name`` takes the innermost ``fed.*`` scope
    in it (``UNSCOPED`` where it has none). The compiler also makes
    instructions of its own, with no ``op_name``: loops it sinks or
    widens, relayout copies, slices. Such an instruction takes, in turn:
    the deepest scope common to the instructions inside the computations
    it holds (a fusion's fused ops, a loop's body; not the reducer a
    reduction calls); else the deepest scope common to the
    instructions that use its result (a copy made for the op that reads
    it); else the scope of the instruction that calls its computation (a
    loop body's ops, that of the loop).
    """
    parent = {}
    for _, _, op_name, _, _ in instrs.values():
        chain = _chain(op_name or "")
        for outer, inner in zip(chain, chain[1:]):
            parent.setdefault(inner, outer)

    def ancestry(scope):
        out = []
        while scope is not None and scope not in out:
            out.append(scope)
            scope = parent.get(scope)
        return out[::-1]

    def common(scopes):
        paths = [ancestry(s) if s != UNSCOPED else [] for s in scopes]
        first = paths[0]
        k = min(len(p) for p in paths)
        while k and any(p[:k] != first[:k] for p in paths):
            k -= 1
        return first[k - 1] if k else UNSCOPED

    users, caller, members = {}, {}, {}
    for iid, (_, _, _, operands, called) in instrs.items():
        members.setdefault(comp_of[iid], []).append(iid)
        for o in operands:
            users.setdefault(o, []).append(iid)
        for c in called:
            caller.setdefault(c, iid)

    inside_memo = {}

    def inside(cid):
        """Scopes of the instructions with an op_name in a computation and
        in the computations it holds."""
        if cid not in inside_memo:
            inside_memo[cid] = []
            out = []
            for iid in members.get(cid, []):
                _, opcode, op_name, _, called = instrs[iid]
                if op_name is not None:
                    out.append(innermost_scope(op_name))
                if opcode in _HOLDS_WORK:
                    for c in called:
                        out += inside(c)
            inside_memo[cid] = out
        return inside_memo[cid]

    memo = {}

    def scope(iid):
        if iid in memo:
            return memo[iid]
        memo[iid] = None  # a cycle resolves nothing
        _, opcode, op_name, _, called = instrs[iid]
        if op_name is not None:
            found = innermost_scope(op_name)
        else:
            found = None
            fused = [s for c in called for s in inside(c)
                     if opcode in _HOLDS_WORK]
            if fused:
                found = common(fused)
            if found is None:
                by_users = [s for s in map(scope, users.get(iid, []))
                            if s is not None]
                if by_users:
                    found = common(by_users)
            if found is None and comp_of[iid] in caller:
                found = scope(caller[comp_of[iid]])
        memo[iid] = found
        return found

    return {instrs[iid][0]: scope(iid) or UNSCOPED for iid in instrs}


def _named_idle(gap, spans):
    """Cut one idle gap at the host span edges inside it; yields
    ``(seconds, innermost span name)`` per piece (no span edge lies
    inside a piece, so the span over its midpoint is over all of it)."""
    a, b = gap
    cuts = sorted({a, b} | {t for s, e, _ in spans for t in (s, e)
                            if a < t < b})
    inside = [sp for sp in spans if sp[0] < b and sp[1] > a]
    for lo, hi in zip(cuts, cuts[1:]):
        yield (hi - lo) * 1e-9, trace_reduce._covering(inside, (lo + hi) / 2)


def program_scopes(data: bytes) -> dict:
    """``{program name: {instruction name: innermost scope}}`` of every
    compiled program a serialized trace holds."""
    return {name: attribute(*module_graph(proto))
            for name, proto in hlo_protos(data).items()}


def _programs(lines):
    """The ``XLA Modules`` line's programs as sorted ``(start, end,
    name)``."""
    return sorted((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                  for line in lines if line.name == "XLA Modules"
                  for ev in line.events)


def _program_at(programs, t):
    for s, e, name in programs:
        if s <= t < e:
            return name
    return None


def reduce(profile, scopes_by_program: dict, top: int = 10) -> dict:
    """Reduce a ``jax.profiler.ProfileData`` and its ``program_scopes``
    to the scope split (see the module docstring)."""
    spans, devices = [], {}
    for plane in profile.planes:
        if plane.name == "/host:CPU":
            spans = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                     for line in plane.lines for ev in line.events
                     if ev.name.startswith(SPAN_PREFIXES)]
        elif trace_reduce._DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices[plane.name] = (
                        [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                         for ev in line.events], _programs(plane.lines))
    windows = [(s, e) for s, e, n in spans if n == trace_reduce.WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace holds no {trace_reduce.WINDOW_SPAN!r} "
                         f"host span")
    if not devices:
        raise ValueError("trace holds no TPU device plane with XLA Ops")
    w0, w1 = windows[0]
    spans = [sp for sp in spans if sp[1] > w0 and sp[0] < w1]
    scope_s = collections.Counter()
    unscoped = collections.Counter()
    idle = collections.Counter()
    gaps = []
    for events, programs in devices.values():
        leaf = []
        for s, e, text in events:
            if e <= w0 or s >= w1:
                continue
            name, opcode, cls = trace_reduce.parse_op(text)
            if cls == "control":
                continue
            s, e = max(s, w0), min(e, w1)
            leaf.append((s, e))
            by_name = scopes_by_program.get(_program_at(programs, s), {})
            scope = by_name.get(name, UNSCOPED)
            scope_s[scope] += (e - s) * 1e-9
            if scope == UNSCOPED:
                unscoped[f"{name} [{opcode or '?'}]"] += (e - s) * 1e-9
        merged = trace_reduce._union(leaf)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            pieces = list(_named_idle((a, b), spans))
            for seconds, span in pieces:
                idle[span] += seconds
            gaps.append(max(pieces))
    gaps.sort(key=lambda g: -g[0])
    fed = collections.defaultdict(lambda: [0, 0.0])
    for s, e, name in spans:
        if name.startswith(SCOPE_PREFIX):
            fed[name][0] += 1
            fed[name][1] += (min(e, w1) - max(s, w0)) * 1e-9
    host_bound = sum(v for k, v in idle.items() if k.startswith(SCOPE_PREFIX))
    return {
        "window_s": (w1 - w0) * 1e-9,
        "devices": len(devices),
        "scope_s": dict(scope_s),
        "unscoped_ops": [[n, s] for n, s in unscoped.most_common(top)],
        "idle_by_span": dict(idle),
        "host_bound_idle_s": host_bound / len(devices),
        "idle_gaps": [[name, s] for s, name in gaps[:top]],
        "spans": {k: {"count": c, "seconds": s} for k, (c, s) in fed.items()},
        "blocks": sum(1 for *_, n in spans if n == "bench.run_block"),
    }


def reduce_bytes(data: bytes, top: int = 10) -> dict:
    from jax.profiler import ProfileData

    return reduce(ProfileData.from_serialized_xspace(data),
                  program_scopes(data), top)


def read(path: str) -> bytes:
    """The serialized XSpace at ``path`` (gzipped if it ends in .gz)."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        return f.read()


if __name__ == "__main__":
    print(json.dumps(reduce_bytes(read(sys.argv[1])), indent=1))
