"""The harness is driven by data: everything a cell, a configuration or a
per-layer metric needs is found by the NAME in the benchmark file."""

import json
import os
import re
import subprocess
import sys
import time

import pytest

from benchmark import harness

ROOT = harness.ROOT
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
REHEARSAL = os.path.join(harness.BENCH_DIR, "tests", "cells",
                         "REHEARSAL.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_files_found_by_name(cell):
    c = harness.load_cell(cell, 1, 1.0, False, time.perf_counter())
    assert c.workload["name"] == cell
    assert c.config["name"] == c.config_name
    harness.load_by_path("drivers", c.workload["driver"]).run
    ref = harness.load_by_path("reference", c.config["reference"])
    assert ref.LIMITS and callable(ref.generator_path)
    # a limit may only be overridden by the CPU rehearsal's tiny configs
    assert "limits" not in c.config
    assert c.cache_dir.startswith(os.path.join(harness.BENCH_DIR, ".work"))


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_layer_metric_has_its_own_reader(metric):
    bench = BENCH
    mod = harness.load_by_path("layer_metrics", metric["name"])
    assert mod.META == {k: metric[k]
                        for k in ("name", "unit", "layer", "moves")}
    assert mod.read({}) is None          # nothing to read -> left out
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    moved = e2e[metric["moves"]]      # moves ONE end-to-end metric
    for cell in metric.get("workloads", []):
        assert "workloads" not in moved or cell in moved["workloads"]


def test_names_units_and_cells():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in BENCH[group]:
            assert NAME.match(entry["name"]), entry["name"]
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert set(m.get("workloads", [])) <= cells
        assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"])
    for w in BENCH["workloads"]:
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)


def test_unknown_names_are_errors():
    with pytest.raises(harness.CellError):
        harness.load_cell("no_such.cell", 1, 1.0, False, 0.0)
    with pytest.raises(harness.CellError):
        harness.load_by_path("layer_metrics", "no.such_metric")


def test_peaks_table_and_unknown_device():
    peaks = harness.load_peaks()
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
    assert peaks["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
    with pytest.raises(harness.CellError):
        harness.device_info(1, require_tpu=True)     # the CPU is refused


def test_run_refuses_off_tpu():
    """A BENCHMARK.json cell never times the CPU: exit code != 0 and no
    result line."""
    out = subprocess.run(
        [sys.executable, os.path.join(harness.BENCH_DIR, "run.py"),
         "--workload", "reference_256.train", "--seed", "3",
         "--seconds", "1", "--trace", "0"],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True,
        text=True, timeout=300, cwd=ROOT)
    assert out.returncode != 0
    assert "correct" not in out.stdout
