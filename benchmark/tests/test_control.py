"""The control of ``correct``, kept as a test: at the PUBLISHED WIDTHS and
a smaller extent (what a CPU test run can hold), the sound generator path
stays inside the limits of ``benchmark/reference/<config>.py`` and the
program's own int8 generator path, put in its place, does not. The
readings the limits were set from come from the chip at the cells' own
sizes (``benchmark/tools/control.py``, PERF.md section 2)."""

import os
import time

import pytest

from benchmark import harness
from benchmark.tools import control

REHEARSAL = os.path.join(harness.BENCH_DIR, "tests", "cells",
                         "REHEARSAL.json")


def _rows(cell_name, kind, seed):
    cell = harness.load_cell(cell_name, seed, 0.0, False, time.perf_counter(),
                             bench_file=REHEARSAL, require_tpu=False)
    reference = harness.load_by_path("reference", cell.config["reference"])
    assert kind == "train"
    row = control.train_row(cell, reference)
    limits = {k: v for k, v in reference.LIMITS.items()
              if k.startswith(("generator_", "prequant_", "code_"))}
    return row, limits


@pytest.mark.parametrize("cell_name, kind", [
    ("widths_reference.train", "train"),
    ("widths_pix2pixhd.train", "train"),
])
def test_lower_precision_is_not_correct(cell_name, kind):
    row, limits = _rows(cell_name, kind, seed=2 ** 31 + 77)
    sound = {k: row[f"sound.{k}"] for k in limits}
    ctrl = {k: row[f"control.{k}"] for k in limits}
    assert all(sound[k] <= limits[k] for k in limits), (sound, limits)
    # the lower precision has to fail ONE of the cell's numbers, not each
    assert any(ctrl[k] > limits[k] for k in limits), (ctrl, limits)
