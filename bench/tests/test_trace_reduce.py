"""The reduction from a profiler trace to per-layer numbers, checked on a
small trace recorded on a TPU v5e: one scan block (8 rounds) of the
``emnist_cnn.paper40`` cell, recorded by ``bench/control.py
--record-trace`` inside the harness's ``bench.window`` span."""
import gzip
import os

import pytest

import trace_reduce

TRACE = os.path.join(os.path.dirname(__file__), "data",
                     "emnist_trace.xplane.pb.gz")


@pytest.fixture(scope="module")
def reduced():
    with gzip.open(TRACE, "rb") as f:
        return trace_reduce.reduce_bytes(f.read())


@pytest.mark.parametrize("text, want", [
    ('%_flat.7 = s32[69632,128]{1,0:T(8,128)S(1)} custom-call(u32[1,1]{1,0} '
     '%a, f32[69632,128]{1,0} %b), custom_call_target="tpu_custom_call"',
     ("_flat.7", "custom-call", "pallas")),
    ('%custom-call.15 = f32[40,222030]{1,0:T(8,128)S(1)} custom-call(), '
     'custom_call_target="AllocateBuffer"',
     ("custom-call.15", "custom-call", "other")),
    ('%while.25 = (s32[]{:T(128)}, f32[222030]{0:T(1024)S(1)}) while((s32[], '
     'f32[222030]) %tuple.1), condition=%cond, body=%body',
     ("while.25", "while", "control")),
    ('%all-reduce.3 = s32[222030]{0} all-reduce(s32[222030]{0} %x), '
     'replica_groups={{0,1,2,3}}, to_apply=%add',
     ("all-reduce.3", "all-reduce", "collective")),
    ('%fusion.208 = f32[20,128]{1,0:T(8,128)S(1)} fusion(bf16[20,1568]{1,0} '
     '%r), kind=kOutput, calls=%fc', ("fusion.208", "fusion", "other")),
    ("0", ("0", "", "other")),
])
def test_parse_op(text, want):
    assert trace_reduce.parse_op(text) == want


def test_union_merges_overlaps():
    assert trace_reduce._union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [
        [0, 3], [5, 9]]


def test_window_and_busy(reduced):
    assert reduced["devices"] == 1
    assert 0 < reduced["busy_s"] <= reduced["window_s"]
    # pinned: the same trace must always reduce to the same seconds
    assert reduced["window_s"] == pytest.approx(0.048738249, rel=1e-9)
    assert reduced["busy_s"] == pytest.approx(0.046972281, rel=1e-9)
    assert reduced["class_s"]["pallas"] == pytest.approx(0.005554824,
                                                         rel=1e-9)
    # the union of op intervals never exceeds the sum of their durations
    assert sum(reduced["class_s"].values()) >= reduced["busy_s"] * (1 - 1e-9)


def test_classes(reduced):
    # the unfused round's RQM encode is one Pallas kernel a round
    assert reduced["class_s"]["pallas"] > 0
    assert "collective" not in reduced["class_s"]
    kernels = [n for n, _ in reduced["top_ops"] if "[custom-call]" in n]
    assert kernels


def test_breakdown_shape(reduced):
    assert 0 < len(reduced["top_ops"]) <= 10
    assert 0 < len(reduced["idle_gaps"]) <= 10
    times = [s for _, s in reduced["top_ops"]]
    assert times == sorted(times, reverse=True)
    for name, seconds in reduced["idle_gaps"]:
        assert name.startswith("bench.") and seconds > 0


def test_no_window_is_an_error():
    class Empty:
        planes = []

    with pytest.raises(ValueError, match="bench.window"):
        trace_reduce.reduce(Empty())
