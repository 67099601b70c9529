"""The four-chip cell ``pix2pixhd_2048x1024.train_spatial4`` is driven by
data like the others: its files are found by name and name each other, its
reference is plain, and the three readers of the ``parallel`` layer read
what ``trace_reduce`` and the program leave them, or nothing."""

import json
import math
import os
import re
import time

import pytest

from benchmark import harness

CELL = "pix2pixhd_2048x1024.train_spatial4"
CONFIG = "pix2pixhd_2048x1024"
BENCH = json.load(open(os.path.join(harness.ROOT, "BENCHMARK.json")))
COMM = ("comm.collective_ms_per_step", "comm.halo_ms_per_step",
        "comm.largest_all_gather_elems")


def test_cell_configuration_and_reference_name_each_other():
    cell = harness.load_cell(CELL, 2 ** 31 + 5, 10.0, False,
                             time.perf_counter())
    assert cell.entry["chips"] == 4 and cell.entry["config"] == CONFIG
    assert cell.workload["name"] == CELL and cell.workload["driver"] == "train"
    cfg = cell.config
    assert cfg["name"] == CONFIG and cfg["reference"] == CONFIG
    assert (cfg["preset"], cfg["image_height"], cfg["image_width"],
            cfg["batch_size"], cfg["dataset_pairs"]) == (
        "pix2pixhd", 1024, 2048, 2, 64)
    assert cfg["flags"] == {"mesh": "data=2,spatial=2", "image_size": 1024,
                            "image_width": 2048, "spike_zscore": 1e9}
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == cfg["reduced"] == ["dataset_pairs"]
    assert "1711.11585" in entry["source"] and "pix2pixhd" in entry["source"]
    # the epoch holds the steps the reference follows
    steps = cfg["dataset_pairs"] // cfg["batch_size"]
    assert 1 <= cfg["train_reference"]["steps"] <= steps == 32


def test_reference_is_plain_and_complete():
    ref = harness.load_by_path("reference", CONFIG)
    assert ref.ROW_BLOCK == 1 and ref.BATCH_KEY == "input"
    for name in ("param_shapes", "g_forward", "generator_path"):
        assert callable(getattr(ref, name))
    shapes = ref.param_shapes()
    # G1's 1024-channel trunk and the enhancer's k7 head, at the paper's
    # widths: 182M parameters
    assert shapes["params_g/global/ResnetBlock_8/ConvLayer_1/Conv_0/kernel"] \
        == (3, 3, 1024, 1024)
    assert shapes["params_g/ConvLayer_2/Conv_0/kernel"] == (7, 7, 32, 3)
    assert 180e6 < sum(math.prod(s) for s in shapes.values()) < 185e6
    with open(ref.__file__) as f:
        source = f.read()
    assert not re.search(r"^\s*(from|import)\s+p2p_tpu", source, re.M)
    # every number the step comparison judges has its limit here
    assert {"generator_mean_abs_levels", "step1_loss_d_rel_gap",
            "step1_loss_g_rel_gap", "later_loss_d_rel_gap",
            "later_loss_g_rel_gap", "first_grad_g_worst_leaf_gap",
            "first_grad_d_worst_leaf_gap", "params_change_g_worst_leaf_gap",
            "params_change_d_worst_leaf_gap"} <= set(ref.LIMITS)


@pytest.mark.parametrize("shape", [(1, 16, 32, 6), (2, 15, 9, 3),
                                   (1, 7, 8, 2), (1, 64, 128, 6)])
def test_the_pool_with_its_own_backward_is_the_plain_pool(shape):
    """The configuration's reference gives ``nn.avg_pool_3s2`` a backward
    of its own (the chip miscomputes the one jax derives at a 2048x1024
    row) and stands it in ``nn``'s place, where ``train_step.py`` finds
    it: the same values, the same cotangent, even and odd extents."""
    import jax
    import numpy as np

    from benchmark.reference import nn, train_step

    ref = harness.load_by_path("reference", CONFIG)
    plain = nn._plain_avg_pool_3s2
    assert nn.avg_pool_3s2 is ref.avg_pool_3s2 is not plain
    assert train_step.nn.avg_pool_3s2 is ref.avg_pool_3s2
    rng = np.random.default_rng(shape[1])
    x = rng.normal(size=shape).astype(np.float32)
    want, pull = jax.vjp(plain, x)
    got, pull_own = jax.vjp(ref.avg_pool_3s2, x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    ct = rng.normal(size=want.shape).astype(np.float32)
    np.testing.assert_allclose(np.asarray(pull_own(ct)[0]),
                               np.asarray(pull(ct)[0]), atol=1e-6)
    # no base-dilated reduce-window in the backward, the op at fault
    text = jax.jit(lambda v, c: jax.vjp(ref.avg_pool_3s2, v)[1](c)[0]).lower(
        x, ct).as_text()
    assert "base_dilations" not in text.replace(
        "base_dilations = array<i64: 1, 1, 1, 1>", "")


def test_metrics_that_list_the_cell():
    rate = next(m for m in BENCH["end_to_end"]
                if m["name"] == "train_img_per_s")
    assert CELL in rate["workloads"]
    for name in COMM:
        (m,) = [m for m in BENCH["per_layer"] if m["name"] == name]
        assert m["workloads"] == [CELL] and m["layer"] == "parallel"
        assert m["moves"] == "train_img_per_s" and m["better"] == "lower"
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert [w["name"] for w in four] == [CELL]


TRACE = {"group_s": {"fusion:kOutput": 3.0, "all-reduce-start": 0.010,
                     "all-reduce-done": 0.230, "collective-permute": 0.100,
                     "collective-permute-start": 0.020,
                     "collective-permute-done": 0.040, "all-to-all": 0.5,
                     "copy": 1.0}}


@pytest.mark.parametrize("run", [
    {}, {"steps": 20}, {"steps": 20, "trace": None},
    {"steps": 20, "trace": {"group_s": {"fusion:kLoop": 1.0, "copy": 2.0}}},
    {"trace": TRACE},
], ids=["empty", "no_trace", "trace_none", "one_chip_ops", "no_steps"])
def test_device_readers_find_nothing_to_read(run):
    for name in COMM[:2]:
        assert harness.load_by_path("layer_metrics", name).read(run) is None


def test_device_readers_sum_the_collective_groups():
    run = {"steps": 20, "trace": TRACE}
    read = lambda name: harness.load_by_path(  # noqa: E731
        "layer_metrics", name).read(run)
    # both halves of an asynchronous form and the synchronous form; an
    # all-to-all (a shard undone and redone) is paid for like the rest
    assert read("comm.collective_ms_per_step") == pytest.approx(
        1000.0 * 0.900 / 20)
    assert read("comm.halo_ms_per_step") == pytest.approx(
        1000.0 * 0.160 / 20)


def test_gauge_reader_without_a_program_that_sets_it(monkeypatch):
    from benchmark import epoch_records

    reader = harness.load_by_path("layer_metrics", COMM[2])
    assert reader.read({}) is None
    monkeypatch.setattr(epoch_records, "live_trainer", lambda: None)
    assert reader.read({"steps": 20}) is None

    class Registry:
        def __init__(self, snap):
            self.snap = snap

        def snapshot(self):
            return self.snap

    class Trainer:
        def __init__(self, snap):
            self.obs = Registry(snap)

    # a program from before the gauge: its registry holds other metrics
    monkeypatch.setattr(epoch_records, "live_trainer",
                        lambda: Trainer({"dispatch_secs": {"count": 3}}))
    assert reader.read({"steps": 20}) is None
    monkeypatch.setattr(epoch_records, "live_trainer", lambda: Trainer(
        {reader.GAUGE: {"value": 4096.0}}))
    assert reader.read({"steps": 20}) == 4096.0
