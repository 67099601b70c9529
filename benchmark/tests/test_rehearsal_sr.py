"""Driver ``train_sr`` end to end on the CPU, on the rehearsal twin of
``swinir_m_realsr_x4_gan.train`` (``cells/REHEARSAL_SR.json``: preset
swinir_realsr_x4 at embed 60 = 2 heads of 30, one group of 6 layers, D 8
features, LQ 16x16 -> HQ 64x64, batch 2; NOT in BENCHMARK.json): the
seeded LQ / HQ dataset, the Trainer through its own entry point, warm-up,
window, the generator check, the first steps against the configuration's
own step reference with the masks the program drew, the result line. Then
with the timed path broken underneath: ``correct`` must come out false.
And the five readers of the scope joins, the control tool, the refusal of
a program without the preset."""

import json
import os
import time

import pytest

from benchmark import harness

REHEARSAL = os.path.join(harness.BENCH_DIR, "tests", "cells",
                         "REHEARSAL_SR.json")
CELL = "tiny_swinir.train"
SEED = 2 ** 31 + 11        # more than 32 signed bits hold
READERS = ("model.swin_attn_ms_per_step", "model.swin_attn_share",
           "model.swin_window_ms_per_step", "model.swin_mlp_ms_per_step",
           "model.d_unet_ms_per_step")


def _state_unchanged(step):
    import jax
    import jax.numpy as jnp

    def lazy(state, batch):
        kept = jax.tree_util.tree_map(jnp.copy, state)
        new, metrics = step(state, batch)
        return kept.replace(step=new.step), metrics

    return lazy


def _spectral_and_ema_not_threaded(step):
    """D's spectral vectors and G's EMA left as they were made."""
    import jax
    import jax.numpy as jnp

    def stale(state, batch):
        kept = jax.tree_util.tree_map(jnp.copy,
                                      (state.spectral_d, state.ema_g))
        new, metrics = step(state, batch)
        return new.replace(spectral_d=kept[0], ema_g=kept[1]), metrics

    return stale


@pytest.mark.parametrize("trace, fault, caught_by", [
    (False, None, ()),
    (True, None, ()),
    (False, _state_unchanged, ("params_change_g_worst_leaf_gap",
                               "params_change_d_worst_leaf_gap")),
    (False, _spectral_and_ema_not_threaded, ("spectral_d_widest_gap",
                                             "ema_g_change_gap")),
], ids=["untraced", "traced", "state_unchanged",
        "spectral_and_ema_not_threaded"])
def test_sr_driver_end_to_end(monkeypatch, capsys, trace, fault, caught_by):
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
    assert driver.__name__.endswith("train_sr")
    line = json.loads(driver.run(cell))
    out = capsys.readouterr().out
    rows = [json.loads(ln) for ln in out.splitlines()
            if ln.startswith('{"check"')][-1]["rows"]
    held = {r["number"]: r.get("holds") for r in rows}
    assert held["steps_not_counted"] and held["window_xla_compiles"]
    if fault is not None:
        assert line["correct"] is False, rows
        assert all(held[name] is False for name in caught_by), rows
        return
    assert line["correct"] is True, out[-3000:]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    judged = {r["number"] for r in rows if r["limit"] is not None}
    assert {"generator_mean_abs_levels", "generator_p99_abs_levels",
            "generator_max_abs_levels", "generator_f32_mean_abs_levels",
            "generator_f32_p99_abs_levels", "generator_f32_max_abs_levels",
            "followed_steps_xla_compiles", "step1_loss_d_rel_gap",
            "step1_loss_g_rel_gap", "step1_g_l1_rel_gap",
            "step1_g_vgg_rel_gap", "step1_g_gan_rel_gap",
            "later_g_vgg_rel_gap", "first_grad_g_worst_leaf_gap",
            "first_grad_d_worst_leaf_gap", "params_change_g_worst_leaf_gap",
            "params_change_d_worst_leaf_gap",
            "first_grad_bias_table_diff_over_norm",
            "first_grad_qkv_kernel_diff_over_norm",
            "spectral_d_widest_gap", "ema_g_change_gap"} <= judged
    assert {"generator_spread_levels",
            "first_grad_d_x3_kernel_diff_over_norm"} <= {
        r["number"] for r in rows if r["limit"] is None}
    # the masks the reference followed were the program's: some branch of
    # some image was dropped in the three steps of this seed
    steps = [json.loads(ln) for ln in out.splitlines()
             if ln.startswith('{"sr_steps"')][-1]["sr_steps"]
    assert len(steps["keep_dropped"]) == 3
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


def test_sr_readers_on_a_scope_join():
    """The five readers on what ``scope_time.by_scope`` hands them, and on
    a run that lacks it (a program without the scopes, as the parent of
    the PR that brought them): nothing, no raise."""
    read = {name: harness.load_by_path("layer_metrics", name).read
            for name in READERS}
    run = {"steps": 10, "trace": {"busy_s": 2.0},
           "sr_scopes": {"executions": 10,
                         "scope_s": {"swin_attn": 0.5, "swin_window": 0.1,
                                     "swin_mlp": 0.2, "swin_ln": 0.15,
                                     "unscoped": 1.0}},
           "sr_nets": {"executions": 10,
                       "scope_s": {"G": 1.0, "D_fake": 0.25, "D_real": 0.15,
                                   "loss_vgg": 0.3}}}
    assert read["model.swin_attn_ms_per_step"](run) == pytest.approx(50.0)
    assert read["model.swin_attn_share"](run) == pytest.approx(25.0)
    assert read["model.swin_window_ms_per_step"](run) == pytest.approx(10.0)
    assert read["model.swin_mlp_ms_per_step"](run) == pytest.approx(20.0)
    assert read["model.d_unet_ms_per_step"](run) == pytest.approx(40.0)
    for lacking in ({}, {"steps": 10, "trace": {"busy_s": 2.0}},
                    dict(run, sr_scopes={"executions": 10,
                                         "scope_s": {"unscoped": 1.9}},
                         sr_nets={"executions": 10,
                                  "scope_s": {"unscoped": 1.9}})):
        assert all(r(lacking) is None for r in read.values())


def test_the_cell_is_in_the_benchmark_as_the_issue_names_it():
    """BENCHMARK.json: the configuration, the one-chip cell, the five
    readers on this cell alone, the cell on the lists of what it reports;
    the configuration file's widths are the preset's and the module's."""
    from p2p_tpu.core.config import get_preset
    from p2p_tpu.models import swinir

    bench = harness._read_json(os.path.join(
        os.path.dirname(harness.BENCH_DIR), "BENCHMARK.json"))
    name = "swinir_m_realsr_x4_gan.train"
    (cell,) = [w for w in bench["workloads"] if w["name"] == name]
    assert cell["chips"] == 1 and cell["traffic"] == "train"
    assert bench["workloads"][-1] is cell        # appended, not inserted
    (config,) = [c for c in bench["configs"] if c["name"] == cell["config"]]
    assert config["reduced"] == ["dataset_pairs"]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for reader in READERS:
        assert by_name[reader]["workloads"] == [name]
        assert by_name[reader]["moves"] == "train_img_per_s"
        assert by_name[reader]["layer"] == "models"
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
    model, preset = stated["model"], get_preset("swinir_realsr_x4")
    assert (model["embed_dim"], len(model["depths"]), model["upscale"],
            model["ndf"]) == (preset.model.ngf, preset.model.n_blocks,
                              preset.model.scale, preset.model.ndf)
    assert (set(model["depths"]), model["window_size"], model["mlp_ratio"],
            model["head_dim"], model["drop_path_rate"],
            model["upsampler_features"], tuple(model["rgb_mean"])) == (
        {swinir.LAYERS_PER_GROUP}, swinir.WINDOW, swinir.MLP_RATIO,
        swinir.HEAD_DIM, swinir.DROP_PATH, swinir.UP_FEATURES, swinir.MEAN)
    assert stated["scale"] == preset.model.scale
    assert (stated["image_height"], stated["image_width"]) == preset.image_hw
    hyper = stated["train_reference"]
    assert (hyper["lr_g"], hyper["beta1"], hyper["beta2"],
            hyper["ema_decay"], hyper["gan_weight"]) == (
        preset.optim.lr, preset.optim.beta1, preset.optim.beta2,
        preset.health.ema_decay, preset.loss.gan_weight)
    # the L1 weight on [0, 1] images is twice the preset's on [-1, 1]
    assert hyper["l1_weight"] == 2 * preset.loss.lambda_l1
    assert hyper["perceptual_weight"] == preset.loss.lambda_vgg


def _control_tool():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "control_sr", os.path.join(harness.BENCH_DIR, "tools",
                                   "control_sr.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    return tool


@pytest.mark.parametrize("kind", ["train", "steps"])
def test_controls_come_out_as_not_correct(capsys, kind):
    """``tools/control_sr.py`` at the toy size: the sound program passes
    ``check.verdict`` under the rehearsal's limits; kernels rounded to
    8-bit integers, the softmax and LayerNorm's moments in bfloat16 and the
    step that saw half of every batch do not."""
    rc = _control_tool().main(
        ["--workload", CELL, "--bench_file", REHEARSAL, "--allow_cpu",
         "--kind", kind, "--seeds", "1", "--first_seed", str(SEED)])
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    row = [ln for ln in lines if "seed" in ln][-1]
    assert row["sound.correct"] is True, row
    refused = [k for k in row if k.startswith("control")
               and k.endswith(".correct")]
    assert len(refused) == (3 if kind == "train" else 1), row
    assert not any(row[k] for k in refused), row
    assert rc == 0 and lines[-1]["sound_correct_and_controls_refused"]


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
