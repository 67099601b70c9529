"""The yardstick's arithmetic: interval unions, custom-call bytes, the
code comparison, the worst-leaf gap."""

import numpy as np
import pytest

from benchmark import check, datagen, hlo_bytes, trace_reduce


def test_union_and_gaps():
    total, merged = trace_reduce.union_ns([(0, 10), (5, 20), (30, 40)])
    assert total == 30 and merged == [(0, 20), (30, 40)]
    assert trace_reduce.gaps_ns(merged, (0, 50)) == [(20, 30), (40, 50)]
    ann = {"a": [(18, 31)], "b": [(0, 100)]}
    assert trace_reduce.name_gap((20, 30), ann, ("a", "b")) == "a"
    assert trace_reduce.name_gap((40, 50), ann, ("a", "b")) == "b"
    assert trace_reduce.name_gap((40, 50), {}, ("a",)) == "host_other"


MLIR = '''
module @jit_step {
  func.func public @main(%arg0: tensor<2x8x8x4xbf16>) -> tensor<2x8x8x4xbf16> {
    %0 = call @norm(%arg0) : (tensor<2x8x8x4xbf16>) -> tensor<2x8x8x4xbf16>
    %1 = call @norm(%0) : (tensor<2x8x8x4xbf16>) -> tensor<2x8x8x4xbf16>
    return %1 : tensor<2x8x8x4xbf16>
  }
  func.func private @norm(%arg0: tensor<2x8x8x4xbf16>) -> tensor<2x8x8x4xbf16> {
    %0:2 = stablehlo.custom_call @tpu_custom_call(%arg0) {backend_config = "tensor<9x9xf32>", kernel_name = "_stats"} : (tensor<2x8x8x4xbf16>) -> (tensor<2x1x1x4xf32>, tensor<2x1x1x4xf32>)
    %1 = stablehlo.custom_call @tpu_custom_call(%arg0, %0#0, %0#1) {backend_config = "x"} : (tensor<2x8x8x4xbf16>, tensor<2x1x1x4xf32>, tensor<2x1x1x4xf32>) -> tensor<2x8x8x4xbf16>
    return %1 : tensor<2x8x8x4xbf16>
  }
}
'''


def test_custom_call_bytes_weighs_sites_by_calls():
    sites, nbytes = hlo_bytes.custom_call_bytes(MLIR)
    x, stat = 2 * 8 * 8 * 4 * 2, 2 * 4 * 4
    assert sites == 4
    assert nbytes == 2 * ((x + 2 * stat) + (x + 2 * stat + x))


def test_code_agreement_counts_levels():
    pre = np.array([0.07, 0.50, 0.93, 0.30])      # x7: 0.49 3.5 6.51 2.1
    code = np.array([1, 3, 7, 4]) / 7.0           # ref code: 0 4 7 2
    got = check.code_agreement(code, pre, 3)
    assert got["code_differs_share"] == pytest.approx(0.75)
    assert got["code_off_by_more_than_one_share"] == pytest.approx(0.25)


def test_verdict_needs_every_limited_number():
    lines = []
    say = lambda **kw: lines.append(kw)
    assert check.verdict({"a": 1.0, "b": 9.0}, {"a": 2.0}, say)
    assert not check.verdict({"a": 3.0}, {"a": 2.0}, say)
    assert not check.verdict({}, {"a": 2.0}, say)       # never produced
    assert not check.verdict({"a": float("nan")}, {"a": 2.0}, say)
    assert lines[0]["rows"][0] == {"number": "a", "value": 1.0,
                                   "limit": 2.0, "holds": True}


def test_same_seed_same_inputs():
    a = datagen.images(2 ** 31 + 7, 2, (16, 24))
    b = datagen.images(2 ** 31 + 7, 2, (16, 24))
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert a[0].shape == (16, 24, 3)


def test_worst_leaf_gap_is_of_norms_against_leaf_or_median():
    want = {"params_g/a": np.full(4, 1.0), "params_g/b": np.full(4, 2.0),
            "params_g/c": np.full(4, 1e-9), "params_d/a": np.full(9, 3.0)}
    got = {"params_g/a": np.full(4, 1.1), "params_g/b": np.full(4, -2.0),
           "params_g/c": np.full(4, 0.1), "params_d/a": np.zeros(9)}
    gap = check.worst_leaf_gap(got, want)
    # b: the norms agree though the leaves do not (the gap of the norms);
    # c: all but zero in the reference, held against the median leaf (a)
    assert gap["g"] == (pytest.approx(0.1), "params_g/a")
    assert gap["d"] == (pytest.approx(1.0), "params_d/a") and "c" not in gap
