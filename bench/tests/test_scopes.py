"""The split of a trace by the program's scopes and spans (``scopes.py``):
the wire reader and the attribution of every op of a made-up program,
the scope and idle attribution on a made-up trace, and the whole
reduction on a trace recorded on a TPU v5e with the scopes in place: one
8-round block of the EMNIST CNN under the ``poisson_dropout`` traffic mix
(Poisson cohorts of 40 of 3,400, dropout 0.1) right after its set-up
block, recorded by ``bench/split.py --record-trace``."""
import os
import types

import pytest

import scopes
import trace_reduce

TRACE = os.path.join(os.path.dirname(__file__), "data",
                     "poisson_scopes.xplane.pb.gz")


def _varint(v: int) -> bytes:
    out = bytearray()
    while True:
        b, v = v & 0x7F, v >> 7
        out.append(b | (0x80 if v else 0))
        if not v:
            return bytes(out)


def _field(num: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _msg(*fields) -> bytes:
    return b"".join(_field(n, v) for n, v in fields)


def _instr(iid, name, opcode, op_name=None, operands=(), called=()):
    fields = [(1, name), (2, opcode), (35, iid)]
    if op_name is not None:
        fields.append((7, _msg((2, op_name))))
    fields += [(36, o) for o in operands] + [(38, c) for c in called]
    return _msg(*fields)


P = "jit(block)/while/body/closed_call/"
# a loop body (computation 1) and a fusion (computation 2) called from the
# entry (computation 0): what the compiler makes of a round
MODULE = _msg(*[(3, comp) for comp in (
    _msg((1, "main"), (5, 0),
         (2, _instr(1, "grads_while", "while", P + "fed.grads/while",
                    called=[1])),
         (2, _instr(2, "relayout_loop", "while", operands=[1], called=[3])),
         (2, _instr(3, "pad.1", "pad", P + "fed.encode_sum/pad",
                    operands=[2])),
         (2, _instr(4, "rqm_encode.1", "custom-call",
                    P + "fed.encode_sum/rqm_encode/pallas_call",
                    operands=[3])),
         (2, _instr(5, "fusion.9", "fusion", operands=[4], called=[2])),
         (2, _instr(6, "copy.11", "copy", "data")),
         (2, _instr(7, "split.1", "fusion", P + "random_split")),
         (2, _instr(8, "reduce-window.7", "reduce-window", called=[4])),
         (2, _instr(9, "fusion.12", "fusion", P + "fed.grads/while/body/"
                    "fed.client_grad/cumsum", operands=[8]))),
    _msg((1, "body"), (5, 1),
         (2, _instr(10, "dot.1", "dot", P + "fed.grads/while/body/"
                    "fed.client_grad/dot_general")),
         (2, _instr(11, "transpose.1", "transpose", P + "fed.client_grad/"
                    "transpose")),
         (2, _instr(12, "dynamic-update-slice.3", "dynamic-update-slice",
                    operands=[10])),
         (2, _instr(13, "dynamic_update_slice.2", "dynamic-update-slice",
                    P + "fed.grads/while/body/dynamic_update_slice",
                    operands=[11]))),
    _msg((1, "fused"), (5, 2),
         (2, _instr(20, "constant.1", "constant", P[:-1])),
         (2, _instr(21, "reduce.1", "reduce", P + "fed.secure_sum/reduce_sum",
                    operands=[20]))),
    _msg((1, "sunk"), (5, 3),
         (2, _instr(30, "dynamic-slice.4", "dynamic-slice")),
         (2, _instr(31, "dynamic-update-slice.5", "dynamic-update-slice",
                    operands=[30]))),
    _msg((1, "region"), (5, 4),
         (2, _instr(40, "add.9", "add", P + "cumsum")),
         (2, _instr(41, "add.10", "add", "reduce_window_sum"))),
)])


def _xspace():
    """A serialized XSpace: the program on the ``/host:metadata`` plane,
    as the profiler keeps it, beside a device plane."""
    hlo = _msg((1, MODULE))
    meta = _msg(
        (1, 1), (2, "/host:metadata"),
        (4, _msg((1, 8), (2, _msg((1, 8), (2, "jit_block(42)"),
                                  (5, _msg((1, 3), (6, hlo))))))),
        (5, _msg((1, 3), (2, _msg((1, 3), (2, "Hlo Proto"))))),
    )
    device = _msg((1, 2), (2, "/device:TPU:0"))
    return _msg((1, device), (1, meta), (4, "a-host"))


def test_program_scopes_reads_the_trace_and_attributes_every_op():
    got = scopes.program_scopes(_xspace())
    assert list(got) == ["jit_block(42)"]
    assert got["jit_block(42)"] == {
        # the code's own ops: the innermost scope of their op_name
        "grads_while": "fed.grads", "pad.1": "fed.encode_sum",
        "rqm_encode.1": "fed.encode_sum", "dot.1": "fed.client_grad",
        "transpose.1": "fed.client_grad",
        "dynamic_update_slice.2": "fed.grads",
        "copy.11": scopes.UNSCOPED, "split.1": scopes.UNSCOPED,
        # the compiler's: a fusion by its fused ops (constants say
        # nothing), a copy by the ops that read it, a sunk loop's ops by
        # the loop, the loop by the op that reads it
        "fusion.9": "fed.secure_sum",
        "dynamic-update-slice.3": "fed.grads",
        "relayout_loop": "fed.encode_sum",
        "dynamic-slice.4": "fed.encode_sum",
        "dynamic-update-slice.5": "fed.encode_sum",
        "constant.1": "fed.secure_sum", "reduce.1": "fed.secure_sum",
        # a reduction's reducer is not its work (nor an op_name that is no
        # path): it goes by its reader
        "reduce-window.7": "fed.client_grad", "fusion.12": "fed.client_grad",
        "add.9": scopes.UNSCOPED, "add.10": "fed.client_grad",
    }


@pytest.mark.parametrize("path, want", [
    ("jit(block)/while/body/closed_call/fed.grads/while/body/closed_call/"
     "fed.client_grad/jvp(conv)/conv_general_dilated:", "fed.client_grad"),
    ("jit(block)/while/body/closed_call/fed.grads/while/body/"
     "dynamic_update_slice:", "fed.grads"),
    ("jit(block)/while/body/closed_call/fed.encode_sum/rqm_encode/"
     "pallas_call:", "fed.encode_sum"),
    ("jit(block)/while/body/closed_call/random_split:", scopes.UNSCOPED),
    ("", scopes.UNSCOPED),
])
def test_innermost_scope(path, want):
    assert scopes.innermost_scope(path) == want


def _profile(ops, spans, program="jit_block(1)"):
    ev = lambda s, e, name: types.SimpleNamespace(
        start_ns=s, duration_ns=e - s, name=name)
    line = lambda name, evs: types.SimpleNamespace(name=name, events=evs)
    plane = lambda name, lines: types.SimpleNamespace(name=name, lines=lines)
    return types.SimpleNamespace(planes=[
        plane("/host:CPU", [line("main", [ev(*sp) for sp in spans])]),
        plane("/device:TPU:0", [
            line("XLA Modules", [ev(0, 100, program)]),
            line("XLA Ops", [ev(*op) for op in ops])]),
    ])


def test_reduce_attributes_scopes_self_time_and_idle():
    a = "%a.1 = f32[2] fusion()"
    b = "%b.2 = f32[4,2] dynamic-update-slice()"
    c = "%c.3 = f32[2] copy()"
    w = "%while.4 = (f32[2]) while()"
    by_program = {"jit_block(1)": {"a.1": "fed.client_grad",
                                   "b.2": "fed.grads", "c.3": scopes.UNSCOPED,
                                   "while.4": scopes.UNSCOPED}}
    ops = [(0, 100, w), (0, 30, a), (30, 40, b), (70, 80, c)]
    spans = [(0, 100, "bench.window"), (0, 40, "bench.run_block"),
             (40, 100, "fed.finish_block"), (45, 65, "fed.account"),
             (50, 60, "fed.eps_eval"), (90, 95, "fed.account")]
    r = scopes.reduce(_profile(ops, spans), by_program)
    ns = 1e-9
    # control ops (the while) enclose others and count for nothing
    assert r["scope_s"] == pytest.approx({
        "fed.client_grad": 30 * ns, "fed.grads": 10 * ns,
        scopes.UNSCOPED: 10 * ns})
    assert r["unscoped_ops"] == [["c.3 [copy]", pytest.approx(10 * ns)]]
    # idle 40-70 and 80-100, each cut at the host span edges
    assert r["idle_by_span"] == pytest.approx({
        "fed.finish_block": (5 + 5 + 10 + 5) * ns,
        "fed.account": (5 + 5 + 5) * ns, "fed.eps_eval": 10 * ns})
    assert r["host_bound_idle_s"] == pytest.approx(50 * ns)
    assert r["idle_gaps"][0] == ["fed.eps_eval", pytest.approx(10 * ns)]
    assert r["spans"]["fed.account"] == {
        "count": 2, "seconds": pytest.approx(25 * ns)}
    assert r["blocks"] == 1
    # an op of a program the trace holds no HloProto for has no scope
    other = scopes.reduce(_profile(ops, spans, "jit_other(2)"), by_program)
    assert other["scope_s"] == pytest.approx({scopes.UNSCOPED: 50 * ns})


@pytest.fixture(scope="module")
def recorded():
    return scopes.reduce_bytes(scopes.read(TRACE))


def test_recorded_block_is_split_by_scope(recorded):
    """All five scopes, pinned; the few unscoped ops are the population's
    bfloat16 copy, the gather of the cohort's data and the slate's sort."""
    r = recorded
    assert r["devices"] == 1 and r["blocks"] == 1
    assert r["window_s"] == pytest.approx(0.224536944, rel=1e-9)
    assert r["scope_s"] == pytest.approx({
        "fed.client_grad": 0.060869514, "fed.grads": 0.01642036,
        "fed.encode_sum": 0.023138058, "fed.secure_sum": 0.006641393,
        "fed.decode_apply": 1.576e-05, scopes.UNSCOPED: 0.001394931},
        rel=1e-6)
    assert [n for n, _ in r["unscoped_ops"][:3]] == [
        "copy.11 [copy]", "fusion.140 [fusion]", "sort.16 [sort]"]


def test_recorded_block_matches_the_class_split(recorded):
    """The scopes partition the same device time ``trace_reduce`` classes
    as Pallas kernels and XLA's ops."""
    whole = trace_reduce.reduce_bytes(scopes.read(TRACE))
    assert sum(recorded["scope_s"].values()) == pytest.approx(
        sum(whole["class_s"].values()), rel=1e-9)


def test_recorded_block_names_its_idle_time(recorded):
    """The block's realized cohort sizes not seen before cost three exact
    epsilon evaluations; the device waits through them, so the longest
    gap is named by the innermost program span, ``fed.eps_eval``."""
    r = recorded
    assert r["spans"]["fed.eps_eval"]["count"] == 3
    assert r["spans"]["fed.account"]["count"] == 1
    assert r["idle_gaps"][0][0] == "fed.eps_eval"
    assert r["idle_by_span"]["fed.eps_eval"] == pytest.approx(0.112989597,
                                                              rel=1e-6)
    assert r["host_bound_idle_s"] == pytest.approx(0.116021498, rel=1e-6)
    assert not any(name.startswith("bench.") for name, _ in r["idle_gaps"])


def test_reduce_without_window_is_an_error():
    with pytest.raises(ValueError, match="bench.window"):
        scopes.reduce(_profile([], []), {})
