"""Every driver end to end on the CPU, on tiny rehearsal cells (NOT in
BENCHMARK.json): the whole run but the harness's look for a chip —
inputs and weights from the seed, the system through its own entry point,
warm-up, window, the output check, the result line. Then the same with
the timed path broken underneath: ``correct`` must come out false."""

import json
import os
import time

import pytest

from benchmark import harness

REHEARSAL = os.path.join(harness.BENCH_DIR, "tests", "cells",
                         "REHEARSAL.json")
SEED = 2 ** 31 + 11        # more than 32 signed bits hold


def drive(cell_name, trace=False, seed=SEED, seconds=1.5):
    cell = harness.load_cell(cell_name, seed, seconds, trace,
                             time.perf_counter(), bench_file=REHEARSAL,
                             require_tpu=False)
    driver = harness.load_by_path("drivers", cell.workload["driver"])
    return cell, json.loads(driver.run(cell))


@pytest.mark.parametrize("cell_name, rate", [
    ("tiny_reference.train", "train_img_per_s"),
    ("tiny_pix2pixhd.train", "train_img_per_s"),
])
def test_driver_end_to_end(cell_name, rate, capsys):
    cell, line = drive(cell_name)
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True, capsys.readouterr().out[-3000:]
    assert line["failed"] == 0 and line["attempted"] > 0
    names = {m["name"] for m in cell.metrics_for(cell.end_to_end)}
    assert set(line["metrics"]) == names and rate in names
    assert line["metrics"][rate]["value"] > 0
    assert line["metrics"]["setup_s"]["value"] > 0
    assert line["device"]["platform"] == "cpu"      # named for what it is
    out = capsys.readouterr().out
    assert '"check": "correct"' in out and '"limit"' in out


def test_traced_run_reports_layer_metrics(cell_name="tiny_pix2pixhd.train"):
    cell, line = drive(cell_name, trace=True)
    assert line["correct"] is True
    wanted = {m["name"] for m in cell.metrics_for(cell.per_layer)}
    assert set(line["metrics"]) <= wanted
    # what needs no device trace is there even on the CPU
    assert "entry.compile_s" in line["metrics"]
    assert {"loop.dispatch_ms", "data.loader_img_per_s"} <= set(
        line["metrics"])


def test_broken_generator_is_not_correct(monkeypatch, capsys):
    """The trainer's generator path with one answer altered where it is
    produced (the image scaled by 0.98)."""
    driver = harness.load_by_path("drivers", "train")
    sound = driver.program_generator_path

    def broken(cfg, dtype, int8=False):
        fn = sound(cfg, dtype, int8)

        def path(state, batch):
            pred, raw, code = fn(state, batch)
            return pred * 0.9, raw, code

        return path

    monkeypatch.setattr(driver, "program_generator_path", broken)
    _, line = drive("tiny_reference.train", seed=SEED + 1)
    assert line["correct"] is False
    assert '"generator_mean_abs_levels"' in capsys.readouterr().out


def test_skipped_steps_are_not_correct(monkeypatch):
    """A step that returns its state unchanged (and yesterday's losses):
    the step counter does not advance, so the images counted are not
    steps x batch."""
    from p2p_tpu.train.loop import Trainer

    real_epoch = Trainer.train_epoch
    calls = []

    def lazy_epoch(self, *a, **kw):
        calls.append(1)
        if len(calls) < 3:
            return real_epoch(self, *a, **kw)
        step, seen = self.train_step, {}

        def lazy_step(state, batch):
            if not seen:
                state, seen["metrics"] = step(state, batch)
            return state, seen["metrics"]

        self.train_step = lazy_step
        try:
            return real_epoch(self, *a, **kw)
        finally:
            self.train_step = step

    monkeypatch.setattr(Trainer, "train_epoch", lazy_epoch)
    _, line = drive("tiny_reference.train", seed=SEED + 2)
    assert line["correct"] is False


def _break_step(monkeypatch, wrap):
    """Put ``wrap(step)`` under the Trainer in place of its compiled step:
    the timed path itself is broken, from the first step on."""
    from p2p_tpu.train.loop import Trainer

    build = Trainer._build_step_fns

    def build_broken(self):
        build(self)
        self.train_step = wrap(self.train_step)

    monkeypatch.setattr(Trainer, "_build_step_fns", build_broken)


def _state_unchanged(step):
    import jax
    import jax.numpy as jnp

    def lazy(state, batch):
        kept = jax.tree_util.tree_map(jnp.copy, state)
        new, metrics = step(state, batch)
        return kept.replace(step=new.step), metrics

    return lazy


def _half_the_batch(step):
    import jax.numpy as jnp

    def half(state, batch):
        n = next(iter(batch.values())).shape[0] // 2
        return step(state, {k: jnp.concatenate([v[:n], v[:n]])
                            for k, v in batch.items()})

    return half


@pytest.mark.parametrize("fault, caught_by", [
    (_state_unchanged, "params_change_g_worst_leaf_gap"),
    (_half_the_batch, "first_grad_d_worst_leaf_gap"),
])
def test_broken_train_step_is_not_correct(monkeypatch, capsys, fault,
                                          caught_by):
    """The Trainer's own step broken underneath (it returns its state
    unchanged; it trains on half the batch): every step is counted, every
    loss is finite, and the first steps no longer follow the reference."""
    _break_step(monkeypatch, fault)
    _, line = drive("tiny_reference.train", seed=SEED + 4)
    assert line["correct"] is False
    rows = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
            if ln.startswith('{"check"')][-1]["rows"]
    held = {r["number"]: r.get("holds") for r in rows}
    assert held[caught_by] is False, rows
    assert held["steps_not_counted"] and held["window_xla_compiles"]
