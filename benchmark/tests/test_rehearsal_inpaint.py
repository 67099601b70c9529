"""Driver ``train_inpaint`` end to end on the CPU, on the rehearsal twin
of ``big_lama_places256.train`` (``cells/REHEARSAL_INPAINT.json``: preset
big_lama at ngf 8, two blocks, D 8 features, 64x64, batch 4; NOT in
BENCHMARK.json): the seeded images, the Trainer through its own entry
point with its loader drawing the masks, warm-up, window, the generator
check, the first steps against the configuration's own step reference,
the run's clock, the result line. Then with the timed path broken
underneath: ``correct`` must come out false, by the number named. And
the seven readers of the scope joins, the control tool, the refusal of a
program without the preset, the configuration's file against the preset.
"""

import inspect
import json
import os
import time

import pytest

from benchmark import harness

REHEARSAL = os.path.join(harness.BENCH_DIR, "tests", "cells",
                         "REHEARSAL_INPAINT.json")
CELL = "tiny_lama.train"
SEED = 2 ** 31 + 11        # more than 32 signed bits hold
READERS = ("model.ffc_spectral_ms_per_step", "model.ffc_spectral_share",
           "model.ffc_fft_ms_per_step", "model.ffc_local_ms_per_step",
           "loss.r1_ms_per_step", "loss.hrf_ms_per_step",
           "model.ffc_fft_hbm_share")


def _state_unchanged(step):
    import jax
    import jax.numpy as jnp

    def lazy(state, batch):
        kept = jax.tree_util.tree_map(jnp.copy, state)
        new, metrics = step(state, batch)
        return kept.replace(step=new.step), metrics

    return lazy


def _statistics_not_threaded(step):
    """The running statistics of G and of D left as they were made."""
    import jax
    import jax.numpy as jnp

    def stale(state, batch):
        kept = jax.tree_util.tree_map(
            jnp.copy, (state.batch_stats_g, state.batch_stats_d))
        new, metrics = step(state, batch)
        return new.replace(batch_stats_g=kept[0],
                           batch_stats_d=kept[1]), metrics

    return stale


def _half_a_batch(step):
    """A step that sees the first half of its batch twice."""
    import jax.numpy as jnp

    def halved(state, batch):
        half = next(iter(batch.values())).shape[0] // 2
        return step(state, {k: jnp.concatenate([v[:half]] * 2)
                            for k, v in batch.items()})

    return halved


def _penalty_left_out(monkeypatch):
    from p2p_tpu.train import step

    sound = step.masked_r1_d_losses
    monkeypatch.setattr(
        step, "masked_r1_d_losses",
        lambda *args: sound(*args[:-1], 0.0))


def _mask_ignored_in_the_l1(monkeypatch):
    """The generator's loss built as for an input without a mask: the L1
    over every pixel, logged under the plain name."""
    from p2p_tpu.train import step

    sound = step.input_mask_channel

    def blind_in_the_g_loss(model):
        caller = inspect.stack()[1].function
        return None if caller == "make_g_loss_fn" else sound(model)

    monkeypatch.setattr(step, "input_mask_channel", blind_in_the_g_loss)


def _bf16_fft_operands(monkeypatch):
    """The generator check's REFERENCE with both transforms of every
    Fourier unit reading operands rounded to bfloat16: the sound program
    reads against it what a program with that fault reads against the
    sound reference (``tools/control_inpaint.py --kind train`` plants it
    the same way)."""
    import jax.numpy as jnp

    reference = harness.load_by_path("reference", "big_lama_places256")
    sound = reference.generator_path
    monkeypatch.setattr(
        reference, "generator_path",
        lambda params, wire, train: sound(
            params, wire, train,
            fft=reference.rounded_transforms(jnp.bfloat16)))


@pytest.mark.parametrize("trace, fault, caught_by", [
    (False, None, ()),
    (True, None, ()),
    (False, _state_unchanged, ("params_change_g_worst_leaf_gap",
                               "params_change_d_worst_leaf_gap")),
    (False, _statistics_not_threaded, ("batch_stats_g_widest_gap",
                                       "batch_stats_d_widest_gap")),
    (False, _half_a_batch, ("step1_g_l1_known_rel_gap",
                            "first_grad_fu_kernel_diff_over_norm")),
    (False, _penalty_left_out, ("step1_loss_d_rel_gap",)),
    (False, _mask_ignored_in_the_l1, ("step1_g_l1_known_rel_gap",)),
    (False, _bf16_fft_operands, ("generator_f32_mean_abs_levels",)),
], ids=["untraced", "traced", "state_unchanged", "statistics_not_threaded",
        "half_a_batch", "penalty_left_out", "mask_ignored_in_the_l1",
        "bf16_fft_operands"])
def test_inpaint_driver_end_to_end(monkeypatch, capsys, trace, fault,
                                   caught_by):
    if fault in (_penalty_left_out, _mask_ignored_in_the_l1,
                 _bf16_fft_operands):
        fault(monkeypatch)
    elif fault is not None:
        from p2p_tpu.train.loop import Trainer

        build = Trainer._build_step_fns

        def build_broken(self):
            build(self)
            self.train_step = fault(self.train_step)

        monkeypatch.setattr(Trainer, "_build_step_fns", build_broken)
    cell = harness.load_cell(CELL, SEED, 1.5, trace, time.perf_counter(),
                             bench_file=REHEARSAL, require_tpu=False)
    driver = harness.load_by_path("drivers", cell.workload["driver"])
    assert driver.__name__.endswith("train_inpaint")
    line = json.loads(driver.run(cell))
    out = capsys.readouterr().out
    said = lambda key: [json.loads(ln) for ln in out.splitlines()  # noqa
                        if ln.startswith('{"%s"' % key)][-1]
    rows = said("check")["rows"]
    held = {r["number"]: r.get("holds") for r in rows}
    assert held["steps_not_counted"] and held["window_xla_compiles"]
    # the loader kept its contract whatever the step did
    assert held["input_not_target_under_mask_share"]
    assert held["mask_not_binary_share"]
    if fault is not None:
        assert line["correct"] is False, rows
        assert all(held[name] is False for name in caught_by), rows
        return
    assert line["correct"] is True, out[-3000:]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    judged = {r["number"] for r in rows if r["limit"] is not None}
    assert {"generator_mean_abs_levels", "generator_p99_abs_levels",
            "generator_f32_mean_abs_levels", "step1_loss_d_rel_gap",
            "step1_loss_d_r1_rel_gap", "step1_g_gan_rel_gap",
            "step1_g_feat_rel_gap", "step1_g_hrf_rel_gap",
            "step1_g_l1_known_rel_gap", "first_grad_d_worst_leaf_gap",
            "first_grad_d_last_kernel_diff_over_norm",
            "first_grad_fu_kernel_diff_over_norm",
            "params_change_g_worst_leaf_gap",
            "params_change_d_worst_leaf_gap", "batch_stats_g_widest_gap",
            "batch_stats_d_widest_gap"} <= judged
    assert {"generator_spread_levels", "masked_share_mean",
            "first_grad_g_worst_leaf_gap", "later_loss_g_rel_gap"} <= {
        r["number"] for r in rows if r["limit"] is None}
    # the run's clock: every phase and their sum, in the last lines
    clock = said("run_clock")["run_clock"]
    assert {"run_wall_s", "setup_s", "generator_check_s", "warmup_s",
            "window_s", "trace_written_s", "trace_reduction_s",
            "followed_steps_s"} <= set(clock)
    parts = sum(clock[k] for k in ("setup_s", "generator_check_s",
                                   "window_s", "trace_written_s",
                                   "trace_reduction_s", "followed_steps_s"))
    assert 0.9 * parts < clock["run_wall_s"] < parts + 10.0
    # two steps were followed, from the loader's own batches
    steps = said("train_steps")["train_steps"]
    assert len(steps["program"]) == len(steps["reference"]) == 2
    assert {"ffc_layers", "ffc_fft_calls_per_step"} <= set(
        said("window")["gauges"])
    if not trace:
        assert set(line["metrics"]) == {"train_img_per_s", "setup_s"}
        assert line["metrics"]["train_img_per_s"]["value"] > 0
        return
    # what needs no device trace is there even on the CPU; the readers of
    # the scope joins find nothing to read without a device plane
    wanted = {m["name"] for m in cell.metrics_for(cell.per_layer)}
    assert set(READERS) <= wanted and set(line["metrics"]) <= wanted
    assert {"entry.compile_s", "loop.dispatch_ms",
            "data.loader_img_per_s"} <= set(line["metrics"])
    assert not set(READERS) & set(line["metrics"])


def test_inpaint_readers_on_a_scope_join():
    """The seven readers on what ``scope_time.by_scope`` hands them, and
    on a run that lacks it (a program without the scopes, as the parent of
    the PR that brought them): nothing, no raise."""
    read = {name: harness.load_by_path("layer_metrics", name).read
            for name in READERS}
    run = {"steps": 10, "trace": {"busy_s": 2.0},
           "device_kind": "TPU v5 lite",
           "ffc_shapes": {"units": 36, "n": 16, "h": 32, "w": 32, "c": 192},
           "inpaint_scopes": {"executions": 10, "scope_s": {
               "ffc_local": 0.6, "ffc_spectral": 0.4, "d_r1": 0.3,
               "loss_hrf": 0.35, "unscoped": 0.35}},
           "inpaint_fft": {"executions": 10, "scope_s": {
               "ffc_fft": 0.1, "unscoped": 1.9}}}
    assert read["model.ffc_spectral_ms_per_step"](run) == pytest.approx(40.0)
    assert read["model.ffc_spectral_share"](run) == pytest.approx(20.0)
    assert read["model.ffc_fft_ms_per_step"](run) == pytest.approx(10.0)
    assert read["model.ffc_local_ms_per_step"](run) == pytest.approx(60.0)
    assert read["loss.r1_ms_per_step"](run) == pytest.approx(30.0)
    assert read["loss.hrf_ms_per_step"](run) == pytest.approx(35.0)
    # 144 transforms of 12.6 MB in, 13.4 MB out: 3.74 GB a step at 819
    # GB/s = 4.57 ms of the 10 traced
    nbytes = harness.load_by_path(
        "layer_metrics", "model.ffc_fft_hbm_share").fft_bytes_per_step(
            36, 16, 32, 32, 192)
    assert nbytes == 36 * 4 * (4 * 16 * 32 * 32 * 192
                               + 8 * 16 * 32 * 17 * 192)
    assert read["model.ffc_fft_hbm_share"](run) == pytest.approx(
        100.0 * nbytes / 819e9 / 0.010)
    assert read["model.ffc_fft_hbm_share"](run) < 100.0
    for lacking in ({}, {"steps": 10, "trace": {"busy_s": 2.0}},
                    dict(run, inpaint_scopes={
                        "executions": 10, "scope_s": {"unscoped": 1.9}},
                        inpaint_fft={"executions": 10,
                                     "scope_s": {"unscoped": 1.9}})):
        assert all(r(lacking) is None for r in read.values())


def test_one_pass_joins_are_by_scope_s():
    """The driver's ``joined`` (one pass over the trace for all its scope
    lists) against ``scope_time.by_scope`` called once a list, on the
    recorded trace of ``benchmark/tests/data``: the same module, the same
    executions, the same seconds scope by scope."""
    from benchmark import scope_time

    data = os.path.join(harness.BENCH_DIR, "tests", "data")
    trace = os.path.join(data, "small_trace.xplane.pb")
    with open(os.path.join(data, "small_trace.hlo.txt")) as f:
        text = f.read()
    joins = {"both": ("net_a", "net_b"), "one": ("net_b",), "none": ()}
    driver = harness.load_by_path("drivers", "train_inpaint")
    got = driver.joined(trace, text, joins)
    assert set(got) == set(joins)
    for name, scopes in joins.items():
        want = scope_time.by_scope(trace, text, scopes)
        assert got[name]["module"] == want["module"]
        assert got[name]["executions"] == want["executions"] > 0
        assert got[name]["scope_s"] == pytest.approx(want["scope_s"])
        assert got[name]["op_s"] == pytest.approx(want["op_s"])
    assert set(got["both"]["scope_s"]) >= {"net_a", "net_b"}
    assert set(got["none"]["scope_s"]) == {scope_time.UNSCOPED}
    with pytest.raises(ValueError, match="HloModule"):
        driver.joined(trace, "not a module", joins)


def test_the_cell_is_in_the_benchmark_as_the_issue_names_it():
    """BENCHMARK.json: the configuration, the one-chip cell, the seven
    readers on this cell alone, the cell on the lists of what it reports;
    the configuration file's widths are the preset's and the modules'."""
    from p2p_tpu.core.config import get_preset
    from p2p_tpu.data import masks
    from p2p_tpu.models import ffc

    bench = harness._read_json(os.path.join(
        os.path.dirname(harness.BENCH_DIR), "BENCHMARK.json"))
    name = "big_lama_places256.train"
    (cell,) = [w for w in bench["workloads"] if w["name"] == name]
    assert cell["chips"] == 1 and cell["traffic"] == "train"
    assert bench["workloads"][-1] is cell        # appended, not inserted
    assert len(cell["why"]) <= 200
    (config,) = [c for c in bench["configs"] if c["name"] == cell["config"]]
    assert config["reduced"] == ["dataset_pairs"]
    assert len(config["why"]) <= 200 and len(config["source"]) <= 200
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for reader in READERS:
        assert by_name[reader]["workloads"] == [name]
        assert by_name[reader]["moves"] == "train_img_per_s"
        assert by_name[reader]["layer"] == "models"
    assert [m["name"] for m in bench["per_layer"][-7:]] == list(READERS)
    for listed in ("data.loader_img_per_s", "loop.dispatch_ms",
                   "step.device_ms", "device.idle_share.train",
                   "device.peak_hbm_gib", "loop.epoch_start_ms",
                   "data.feed_wait_ms", "loop.bookkeeping_ms",
                   "loop.host_ms_per_step", "loop.step_interval_ms",
                   "device.starved_share.train", "loop.first_step_late_ms"):
        assert by_name[listed]["workloads"][-1] == name
    (rate,) = [m for m in bench["end_to_end"]
               if m["name"] == "train_img_per_s"]
    assert rate["workloads"][-1] == name
    stated = harness._read_json(os.path.join(
        os.path.dirname(harness.BENCH_DIR), config["file"]))
    model, preset = stated["model"], get_preset("big_lama")
    assert (model["ngf"], model["n_blocks"], model["ratio_gin"],
            model["input_nc"], model["ndf"], model["n_layers_D"]) == (
        preset.model.ngf, preset.model.n_blocks, preset.model.ffc_ratio,
        preset.model.input_nc, preset.model.ndf, preset.model.n_layers_D)
    assert model["n_downsampling"] == ffc.N_DOWN
    assert (model["local_channels"], model["global_channels"]) == \
        ffc.split_channels(model["bottleneck_channels"], model["ratio_gin"])
    assert model["fourier_unit_channels"] == model["global_channels"] // 2
    assert model["ffc_layers"] == 1 + ffc.N_DOWN + 2 * model["n_blocks"]
    gen = model["mask_generator"]
    assert (gen["irregular_kwargs"]["max_len"],
            gen["irregular_kwargs"]["max_width"],
            gen["irregular_kwargs"]["max_times"]) == (
        masks.POLYLINES["max_len"], masks.POLYLINES["max_width"],
        masks.POLYLINES["max_times"])
    assert (gen["box_kwargs"]["bbox_max_size"], gen["box_kwargs"]["margin"],
            gen["box_kwargs"]["max_times"]) == (
        masks.BOXES["max_size"], masks.BOXES["margin"],
        masks.BOXES["max_times"])
    assert (stated["image_height"], stated["image_width"]) == preset.image_hw
    assert stated["batch_size"] == preset.data.batch_size == 16
    assert stated["dataset_pairs"] // stated["batch_size"] == 16
    hyper = stated["train_reference"]
    assert hyper["steps"] == 2
    assert (hyper["lr_g"], hyper["lr_d"], hyper["beta1"], hyper["beta2"],
            hyper["gan_weight"], hyper["fm_weight"], hyper["hrf_weight"],
            hyper["gp_coef"]) == (
        preset.optim.lr, preset.optim.lr_d, preset.optim.beta1,
        preset.optim.beta2, preset.loss.gan_weight, preset.loss.lambda_feat,
        preset.loss.lambda_hrf, preset.loss.gp_coef)
    # the L1 weight on [0, 1] images is twice the preset's on [-1, 1]
    assert hyper["l1_weight"] == 2 * preset.loss.lambda_l1
    losses = model["losses"]
    assert (losses["adversarial"]["weight"], losses["adversarial"]["gp_coef"],
            losses["l1"]["weight_known"], losses["feature_matching"]["weight"],
            losses["resnet_pl"]["weight"]) == (
        hyper["gan_weight"], hyper["gp_coef"], hyper["l1_weight"],
        hyper["fm_weight"], hyper["hrf_weight"])
    assert stated["run_budget"]["limit_s"] == 360


def _control_tool():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "control_inpaint", os.path.join(harness.BENCH_DIR, "tools",
                                        "control_inpaint.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    return tool


@pytest.mark.parametrize("kind, controls", [("train", 2), ("steps", 3)])
def test_controls_come_out_as_not_correct(capsys, kind, controls):
    """``tools/control_inpaint.py`` at the toy size: the sound program
    passes ``check.verdict`` under the rehearsal's limits; kernels rounded
    to three mantissa bits and the transforms' operands in bfloat16, and a
    step without the penalty, on half of every batch or with the mask
    ignored in the L1, do not."""
    rc = _control_tool().main(
        ["--workload", CELL, "--bench_file", REHEARSAL, "--allow_cpu",
         "--kind", kind, "--seeds", "1", "--first_seed", str(SEED)])
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    row = [ln for ln in lines if "seed" in ln][-1]
    assert row["sound.correct"] is True, row
    refused = [k for k in row if k.startswith("control")
               and k.endswith(".correct")]
    assert len(refused) == controls, row
    assert not any(row[k] for k in refused), row
    assert rc == 0 and lines[-1]["sound_correct_and_controls_refused"]
    if kind == "steps":
        # the same step built at float32 is followed too, comes out
        # correct, and holds G's first gradient far closer than bf16 does
        assert row["sound_float32_program.correct"] is True, row
        for number in ("first_grad_first_fu_kernel_diff_over_norm",
                       "first_grad_fu_kernel_diff_over_norm",
                       "first_grad_g_worst_leaf_gap"):
            assert (row[f"sound_float32_program.{number}"]
                    < 0.2 * row[f"sound.{number}"]), (number, row)


def test_chip_reference_kind_holds_the_two_sides_of_one_program(capsys):
    """``--kind chip_reference`` on the CPU, where both sides are the host:
    every gap reads zero and the tool says so."""
    rc = _control_tool().main(
        ["--workload", CELL, "--bench_file", REHEARSAL, "--allow_cpu",
         "--kind", "chip_reference", "--seeds", "1", "--first_seed",
         str(SEED)])
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    row = [ln for ln in lines if "seed" in ln][-1]
    gaps = {k: v for k, v in row.items()
            if k.startswith("sound.chip_reference_")}
    assert len(gaps) >= 10 and max(gaps.values()) < 1e-6
    assert rc == 0


def test_driver_sets_the_cache_before_jax_and_refuses_an_unknown_preset():
    """In a process of its own, as ``run.py`` starts the driver: jax is not
    imported before ``prepare_jax_env`` has named the cell's compile cache,
    and a program without the preset (the parent of the PR that brought
    it) is refused at once, by a ``CellError``."""
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
