"""The phase metrics read the program's epoch records: from ``run`` where
a driver hands them over, else from the Trainer alive in the process; on
a program that keeps no such records each reader finds nothing."""

import json
import os
import time

import pytest

from benchmark import epoch_records, harness

PHASE_METRICS = ("loop.epoch_start_ms", "data.feed_wait_ms",
                 "loop.bookkeeping_ms")
REHEARSAL = os.path.join(harness.BENCH_DIR, "tests", "cells",
                         "REHEARSAL.json")


def record(steps, start, feed, first, book):
    return {"name": "train_epoch", "steps": steps, "epoch_start_s": start,
            "feed_next_s": feed, "first_feed_next_s": first,
            "step_bookkeeping_s": book}


def read(name, run):
    return harness.load_by_path("layer_metrics", name).read(run)


def test_readers_take_the_windows_epochs_only():
    warm_up = record(12, 9.0, 9.0, 9.0, 9.0)
    run = {"steps": 24, "epoch_records": [
        warm_up, record(12, 0.10, 0.13, 0.08, 4.8),
        record(12, 0.12, 0.15, 0.10, 4.2)]}
    assert read("loop.epoch_start_ms", run) == pytest.approx(110.0)
    assert read("data.feed_wait_ms", run) == pytest.approx(
        1000 * (0.05 + 0.05) / 22)
    assert read("loop.bookkeeping_ms", run) == pytest.approx(375.0)


@pytest.mark.parametrize("name", PHASE_METRICS)
@pytest.mark.parametrize("run", [
    {}, {"steps": 12}, {"steps": 12, "epoch_records": []},
    # the window's steps and the records' do not add up
    {"steps": 12, "epoch_records": [record(8, 0.1, 0.1, 0.1, 0.1)]},
    # records of another program: the spans are there, the fields are not
    {"steps": 12, "epoch_records": [{"name": "train_epoch", "steps": 12}]},
], ids=["empty", "no_trainer", "no_records", "odd_steps", "no_fields"])
def test_reader_finds_nothing_to_read(name, run):
    assert read(name, run) is None


def test_traced_rehearsal_prints_the_phase_metrics():
    """The driver as it stands hands no records over: the readers find
    the Trainer the driver built."""
    bench = json.load(open(os.path.join(harness.ROOT, "BENCHMARK.json")))
    cell = harness.load_cell("tiny_reference.train", 2 ** 31 + 23, 1.5, True,
                             time.perf_counter(), bench_file=REHEARSAL,
                             require_tpu=False)
    cell.per_layer = [dict(m, workloads=[cell.name])
                      for m in bench["per_layer"]
                      if m["name"] in PHASE_METRICS]
    assert len(cell.per_layer) == 3
    driver = harness.load_by_path("drivers", cell.workload["driver"])
    line = json.loads(driver.run(cell))
    assert line["correct"] is True
    assert set(line["metrics"]) == set(PHASE_METRICS)
    trainer = epoch_records.live_trainer()
    window = epoch_records.window_epochs({"steps": line["attempted"]})
    assert window and window[0] is not trainer.spans.spans[0]  # warm-up
    for e in window:
        children = sum(e[f"{p}_s"] for p in (
            "epoch_setup", "feed_next", "train_dispatch",
            "step_bookkeeping", "epoch_drain"))
        assert 0.95 * e["dur_s"] <= children <= e["dur_s"]
