"""Driver ``train_labels`` end to end on the CPU, on the rehearsal twin of
``spade_cityscapes_512x256.train`` (``cells/REHEARSAL_LABELS.json``:
preset spade_cityscapes at nf 8, 3 classes + edge, 32x64, batch 2; NOT in
BENCHMARK.json): the seeded label/photo dataset, the Trainer through its
own entry point with its label loader, warm-up, window, the generator
check, the first steps against the configuration's own step reference,
the result line. Then with the timed path broken underneath: ``correct``
must come out false."""

import json
import os
import time

import pytest

from benchmark import harness

REHEARSAL = os.path.join(harness.BENCH_DIR, "tests", "cells",
                         "REHEARSAL_LABELS.json")
CELL = "tiny_spade.train"
SEED = 2 ** 31 + 11        # more than 32 signed bits hold


def _state_unchanged(step):
    import jax
    import jax.numpy as jnp

    def lazy(state, batch):
        kept = jax.tree_util.tree_map(jnp.copy, state)
        new, metrics = step(state, batch)
        return kept.replace(step=new.step), metrics

    return lazy


def _vectors_not_threaded(step):
    """G's spectral vectors left as they were seeded."""
    import jax
    import jax.numpy as jnp

    def stale(state, batch):
        kept = jax.tree_util.tree_map(jnp.copy, state.spectral_g)
        new, metrics = step(state, batch)
        return new.replace(spectral_g=kept), metrics

    return stale


@pytest.mark.parametrize("trace, fault, caught_by", [
    (False, None, None),
    (True, None, None),
    (False, _state_unchanged, "params_change_g_worst_leaf_gap"),
    (False, _vectors_not_threaded, "spectral_g_u_widest_gap"),
], ids=["untraced", "traced", "state_unchanged", "vectors_not_threaded"])
def test_label_driver_end_to_end(monkeypatch, capsys, trace, fault,
                                 caught_by):
    if fault is not None:
        from p2p_tpu.train.loop import Trainer

        build = Trainer._build_step_fns

        def build_broken(self):
            build(self)
            self.train_step = fault(self.train_step)

        monkeypatch.setattr(Trainer, "_build_step_fns", build_broken)
    cell = harness.load_cell(CELL, SEED, 1.5, trace, time.perf_counter(),
                             bench_file=REHEARSAL, require_tpu=False)
    driver = harness.load_by_path("drivers", cell.workload["driver"])
    assert driver.__name__.endswith("train_labels")
    line = json.loads(driver.run(cell))
    out = capsys.readouterr().out
    rows = [json.loads(ln) for ln in out.splitlines()
            if ln.startswith('{"check"')][-1]["rows"]
    held = {r["number"]: r.get("holds") for r in rows}
    assert held["steps_not_counted"] and held["window_xla_compiles"]
    if fault is not None:
        assert line["correct"] is False and held[caught_by] is False, rows
        return
    assert line["correct"] is True, out[-3000:]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    judged = {r["number"] for r in rows if r["limit"] is not None}
    assert {"generator_mean_abs_levels", "step1_loss_d_rel_gap",
            "first_grad_g_worst_leaf_gap", "params_change_d_worst_leaf_gap",
            "spectral_g_u_widest_gap"} <= judged
    if not trace:
        assert set(line["metrics"]) == {"train_img_per_s", "setup_s"}
        assert line["metrics"]["train_img_per_s"]["value"] > 0
        return
    # what needs no device trace is there even on the CPU; the readers of
    # the scope join find nothing to read without a device plane
    wanted = {m["name"] for m in cell.metrics_for(cell.per_layer)}
    assert set(line["metrics"]) <= wanted
    assert {"entry.compile_s", "loop.dispatch_ms",
            "data.loader_img_per_s"} <= set(line["metrics"])
    assert "model.spade_share" not in line["metrics"]


def test_spade_readers_on_a_scope_join():
    """The two readers on what ``scope_time.by_scope`` hands them (the
    scope's own ops and both kinds of fusion that hold its work under
    another name), and on a run that lacks it (a program without the
    scope): nothing, no raise."""
    ms = harness.load_by_path("layer_metrics", "model.spade_ms_per_step")
    share = harness.load_by_path("layer_metrics", "model.spade_share")
    run = {"steps": 10, "trace": {"busy_s": 2.0},
           "spade_scope": {"executions": 10,
                           "scope_s": {"spade": 1.2, "spade_fused_passes": 0.1,
                                       "spade_fused_in_conv": 0.2,
                                       "unscoped": 0.4}}}
    assert ms.read(run) == pytest.approx(150.0)
    assert share.read(run) == pytest.approx(75.0)
    for lacking in ({}, {"steps": 10, "trace": {"busy_s": 2.0}},
                    dict(run, spade_scope={"executions": 10,
                                           "scope_s": {"unscoped": 1.9}})):
        assert ms.read(lacking) is None and share.read(lacking) is None


_FUSED_TEXT = """HloModule jit_step, is_scheduled=true

%fused_passes (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %m = f32[8]{0} multiply(%p0, %p0), metadata={op_name="jit(step)/jvp(G)/up_3/norm_0/spade/modulate/mul"}
  ROOT %a = f32[8]{0} add(%m, %p0), metadata={op_name="jit(step)/opt_g/add"}
}

%nested (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  ROOT %r = f32[8]{0} negate(%p0), metadata={op_name="jit(step)/transpose(jvp(G))/up_3/norm_1/spade/modulate/neg"}
}

%fused_conv (p0: f32[8], p1: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %p1 = f32[8]{0} parameter(1)
  %n = f32[8]{0} fusion(%p0), kind=kLoop, calls=%nested
  ROOT %c = f32[8]{0} convolution(%n, %p1), metadata={op_name="jit(step)/transpose(jvp(G))/up_3/conv_0/conv_general_dilated"}
}

%fused_own_conv (p0: f32[8], p1: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %p1 = f32[8]{0} parameter(1)
  %c = f32[8]{0} convolution(%p0, %p1), metadata={op_name="jit(step)/jvp(G)/up_3/norm_0/spade/gamma_beta/gamma/conv_general_dilated"}
  ROOT %p = f32[8]{0} pad(%c), metadata={op_name="jit(step)/jvp(G)/up_3/pad"}
}

%fused_plain (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  ROOT %t = f32[8]{0} tanh(%p0), metadata={op_name="jit(step)/jvp(G)/tanh"}
}

ENTRY %main (x: f32[8], w: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %w = f32[8]{0} parameter(1)
  %fusion.1 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_passes, metadata={op_name="jit(step)/opt_g/add"}
  %fusion.2 = f32[8]{0} fusion(%fusion.1, %w), kind=kOutput, calls=%fused_conv, metadata={op_name="jit(step)/transpose(jvp(G))/up_3/conv_0/conv_general_dilated"}
  %fusion.3 = f32[8]{0} fusion(%fusion.2, %w), kind=kOutput, calls=%fused_own_conv, metadata={op_name="jit(step)/jvp(G)/up_3/pad"}
  %fusion.4 = f32[8]{0} fusion(%fusion.3), kind=kLoop, calls=%fused_plain
  %fusion.5 = f32[8]{0} fusion(%fusion.4), kind=kLoop, calls=%nested
  ROOT %fusion.6 = f32[8]{0} fusion(%fusion.5), kind=kLoop, calls=%nested, metadata={op_name="jit(step)/jvp(G)/up_3/norm_1/spade/modulate/neg"}
}
"""


def test_fusions_that_hold_the_scope_under_another_name_are_tagged():
    """``fused_scope.tagged``: a fusion of passes, a fusion around a
    convolution outside the scope (through a nested fusion), a fusion
    whose convolution is the scope's, one with no name at all; a fusion
    without the scope and one named under it stay as they are, and the
    text stays one ``by_scope`` reads."""
    from benchmark import fused_scope, scope_time

    tags = fused_scope.tags("spade")
    assert tags == ("spade", "spade_fused_passes", "spade_fused_in_conv")
    out = fused_scope.tagged(_FUSED_TEXT, "spade")
    assert scope_time.module_name(out) == "jit_step"
    before = scope_time.instruction_scopes(_FUSED_TEXT, tags)
    after = scope_time.instruction_scopes(out, tags)
    assert [before[f"fusion.{i}"] for i in range(1, 7)] == [
        None, None, None, None, None, "spade"]
    assert [after[f"fusion.{i}"] for i in range(1, 7)] == [
        "spade_fused_passes", "spade_fused_in_conv", "spade", None,
        "spade_fused_passes", "spade"]
    changed = [i for i, (a, b) in enumerate(zip(
        _FUSED_TEXT.split("\n"), out.split("\n"))) if a != b]
    # the four of the entry and the fusion nested in ``fused_conv``
    assert len(changed) == 5 and len(out.split("\n")) == len(
        _FUSED_TEXT.split("\n"))
    assert fused_scope.tagged(_FUSED_TEXT, "no_such_scope") == _FUSED_TEXT


def test_control_of_the_steps_comes_out_as_not_correct(capsys):
    """``tools/control_labels.py --kind steps`` at the toy size: the sound
    program passes ``check.verdict`` under the rehearsal's limits, the
    step that saw half of its batch does not, by a first gradient."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "control_labels", os.path.join(harness.BENCH_DIR, "tools",
                                       "control_labels.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    rc = tool.main(["--workload", CELL, "--bench_file", REHEARSAL,
                    "--allow_cpu", "--kind", "steps", "--seeds", "1",
                    "--first_seed", str(SEED)])
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    row = [ln for ln in lines if "seed" in ln][-1]
    assert row["sound.correct"] is True and row["control.correct"] is False
    assert rc == 0 and lines[-1]["sound_correct_and_controls_refused"]
    assert row["control.first_grad_d_worst_leaf_gap"] > 3 * row[
        "sound.first_grad_d_worst_leaf_gap"]


def test_driver_sets_the_cache_before_jax_and_refuses_an_unknown_preset():
    """In a process of its own, as ``run.py`` starts the driver: jax must
    not be imported before ``prepare_jax_env`` has named the cell's compile
    cache (jax reads the variable once, at import: the cell's first chip
    runs compiled cold every time, 0 bytes cached), and a program without
    the preset is refused at once, by a ``CellError``."""
    import subprocess
    import sys

    code = f"""
import sys, time
from benchmark import harness
cell = harness.load_cell({CELL!r}, 1, 1.0, False, time.perf_counter(),
                         bench_file={REHEARSAL!r}, require_tpu=False)
cell.config["preset"] = "no_such_preset"
driver = harness.load_by_path("drivers", cell.workload["driver"])
assert "jax" not in sys.modules
try:
    driver.run(cell)
except harness.CellError as e:
    assert "no_such_preset" in str(e)
else:
    raise SystemExit("the unknown preset was not refused")
import jax
assert jax.config.jax_compilation_cache_dir == cell.cache_dir
"""
    done = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, cwd=os.path.dirname(harness.BENCH_DIR),
                          env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert done.returncode == 0, done.stderr[-2000:]
