"""The four readers of the Trainer's step clock take the epoch records'
fields through ``epoch_records.window_epochs`` and find nothing where a
program keeps no such fields (the parent's records, a health queue that
is off) or the window's steps do not add up."""

import pytest

from benchmark import harness

READERS = ("loop.host_ms_per_step", "loop.step_interval_ms",
           "device.starved_share.train", "loop.first_step_late_ms")


def record(steps, seconds, host, median, starved, late, key="dur_s"):
    return {"name": "train_epoch", "steps": steps, key: seconds,
            "host_s": host, "step_interval_median_s": median,
            "device_starved_s": starved, "first_step_late_s": late}


WINDOW = {"steps": 28, "epoch_records": [
    record(12, 99.0, 9.0, 9.0, 9.0, 9.0),            # the warm-up epoch
    record(12, 3.0, 0.24, 0.250, 0.06, 0.030),
    # a JSONL line holds the epoch's seconds as "sec"
    record(16, 5.0, 0.32, 0.300, 0.10, 0.010, key="sec")]}
WANT = {
    "loop.host_ms_per_step": 1000 * (0.24 + 0.32) / 28,
    "loop.step_interval_ms": 1000 * (12 * 0.250 + 16 * 0.300) / 28,
    "device.starved_share.train": 100 * (0.06 + 0.10) / 8.0,
    "loop.first_step_late_ms": 1000 * (0.030 + 0.010) / 2,
}
BARE = {"name": "train_epoch", "steps": 12, "dur_s": 3.0,
        "step_bookkeeping_s": 2.8}


@pytest.mark.parametrize("name", READERS)
@pytest.mark.parametrize("run, want", [
    (WINDOW, WANT),
    # the parent's records, or a Trainer with the health queue off
    ({"steps": 12, "epoch_records": [BARE]}, None),
    # one epoch of the window with the fields, one without: what is there
    ({"steps": 24, "epoch_records": [BARE, WINDOW["epoch_records"][1]]},
     {"loop.host_ms_per_step": 20.0, "loop.step_interval_ms": 250.0,
      "device.starved_share.train": 2.0, "loop.first_step_late_ms": 30.0}),
    # the window's steps and the records' do not add up
    ({"steps": 20, "epoch_records": WINDOW["epoch_records"]}, None),
    ({"steps": 12, "epoch_records": []}, None),
    ({}, None),
], ids=["present", "absent", "partly", "odd_steps", "no_records", "empty"])
def test_step_clock_reader(name, run, want):
    module = harness.load_by_path("layer_metrics", name)
    assert module.META["name"] == name
    got = module.read(run)
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want[name])
