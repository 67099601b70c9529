"""The reduction from a profiler trace to numbers, on a small trace
recorded on a TPU v5e by ``benchmark/tools/record_trace.py``: three runs
of a jitted conv + tanh (about 0.21 ms each) under ``train_dispatch``,
each followed by a fenced 20 ms sleep under ``bench_fence``."""

import os

import pytest

from benchmark import trace_reduce

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "small_trace.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce_trace(
        TRACE, ("train_dispatch", "bench_fence"))


def test_busy_is_the_union_of_op_intervals(reduced):
    # the three module runs last 207771 + 207488 + 207588 ns; the ops
    # inside them cover all of that but the launch gaps
    assert reduced["n_chips"] == 1 and reduced["n_op_events"] == 42
    assert reduced["busy_s"] == pytest.approx(0.000622785, rel=1e-6)
    assert reduced["busy_s"] <= 3 * 0.000208
    assert reduced["window_s"] == pytest.approx(0.04405157, rel=1e-6)
    assert reduced["idle_share"] == pytest.approx(
        1 - reduced["busy_s"] / reduced["window_s"])


def test_groups_by_kind_of_op_and_costliest_ops(reduced):
    assert reduced["group_s"]["fusion:kOutput"] == pytest.approx(
        0.000189375, rel=1e-6)            # the convolution, three runs
    assert set(reduced["group_s"]) >= {"copy", "fusion:kLoop", "pad"}
    assert sum(reduced["group_s"].values()) == pytest.approx(
        reduced["busy_s"], rel=0.02)      # ops do not overlap on one core
    name, seconds = reduced["device_ops"][0]
    assert name == "fusion bf16[128,32,17,64]" and seconds > 1.8e-4
    assert len(reduced["device_ops"]) <= 10
    assert reduced["n_kernel_events"] == 0 and reduced["kernel_s"] == 0.0


def test_gaps_are_named_by_what_the_host_did(reduced):
    gaps = dict(reduced["idle_gaps"])
    # the device idles while the host sleeps inside the fence
    assert gaps["bench_fence"] == pytest.approx(0.0434, rel=0.01)
    assert reduced["longest_gaps"][0][0] == "bench_fence"
    assert reduced["longest_gaps"][0][1] == pytest.approx(0.0218, rel=0.01)
    assert sum(gaps.values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], rel=1e-6)


def test_a_window_bounds_the_reduction(reduced):
    full = trace_reduce.reduce_trace(TRACE, ())
    assert dict(full["idle_gaps"]).keys() == {"host_other"}
    first = trace_reduce.reduce_trace(
        TRACE, (), window=(46_000_000, 47_000_000))
    assert first["n_op_events"] == 14
    assert first["window_s"] == pytest.approx(0.001)


def test_a_window_named_by_host_annotations_counts_the_edges(reduced):
    """From the first ``train_dispatch`` to the end of the last
    ``bench_fence``: the 21.6 ms the host slept after the last device op
    are inside the window. The host's and the device's clocks lie ~1.2 ms
    apart in a trace (here the first run's ops END before the host's clock
    says they were dispatched, and fall outside): the edges are good to
    that, which a window of seconds does not feel and this one of
    milliseconds does."""
    edge = trace_reduce.reduce_trace(
        TRACE, ("train_dispatch", "bench_fence"),
        window_from=("train_dispatch", "bench_fence"))
    assert edge["window_s"] == pytest.approx(0.065889162, rel=1e-6)
    assert edge["n_op_events"] == 28
    assert edge["idle_share"] > reduced["idle_share"]
    assert sum(dict(edge["idle_gaps"]).values()) == pytest.approx(
        edge["window_s"] - edge["busy_s"], rel=1e-6)
    with pytest.raises(ValueError):
        trace_reduce.reduce_trace(TRACE, (), window_from=("no_such", "x"))


def test_parse_op():
    text = ("%fusion.7 = (bf16[8,64]{1,0:T(8,128)}, f32[8]{0}) fusion("
            "bf16[8,64]{1,0} %p), kind=kInput, calls=%fused.1")
    assert trace_reduce.parse_op(text) == (
        "fusion.7", "(bf16[8,64]{1,0:T(8,128)}, f32[8]{0})", "fusion")
    assert trace_reduce.op_group(text) == "fusion:kInput"
    assert trace_reduce.op_label(text) == "fusion.7 (bf16[8,64], f32[8])"
    call = ('%custom-call.3 = bf16[2,8]{1,0} custom-call(bf16[2,8]{1,0} %x),'
            ' custom_call_target="tpu_custom_call"')
    assert trace_reduce.is_kernel(call) and not trace_reduce.is_kernel(text)
    assert trace_reduce.op_group(call) == "custom-call:tpu_custom_call"


def test_a_trace_without_device_ops_is_an_error(tmp_path):
    with pytest.raises(FileNotFoundError):
        trace_reduce.find_xplane(str(tmp_path))
