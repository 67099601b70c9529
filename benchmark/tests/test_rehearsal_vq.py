"""Driver ``train_vq`` end to end on the CPU, on the rehearsal twin of
``vqgan_imagenet_f16_16384.train`` (``cells/REHEARSAL_VQ.json``: preset
vqgan_imagenet_f16 at ch 64, ch_mult (1, 2), 1 block a level, 64 codes of
width 32, 32x32, batch 2; NOT in BENCHMARK.json): the seeded dataset whose
two sides are the same image, the Trainer through its own entry point,
warm-up, window, the teacher-forced generator check, the first steps
against the configuration's own step reference, the result line. Then
with the timed path broken underneath: ``correct`` must come out false.
And the five readers of the scope join, the control tool, the refusal of
a program without the preset."""

import json
import os
import time

import pytest

from benchmark import harness

REHEARSAL = os.path.join(harness.BENCH_DIR, "tests", "cells",
                         "REHEARSAL_VQ.json")
CELL = "tiny_vqgan.train"
SEED = 2 ** 31 + 11        # more than 32 signed bits hold
READERS = ("model.gn_swish_ms_per_step", "model.gn_swish_share",
           "model.vq_ms_per_step", "model.attn_ms_per_step",
           "loss.adaptive_weight_ms_per_step")


def _state_unchanged(step):
    import jax
    import jax.numpy as jnp

    def lazy(state, batch):
        kept = jax.tree_util.tree_map(jnp.copy, state)
        new, metrics = step(state, batch)
        return kept.replace(step=new.step), metrics

    return lazy


def _statistics_not_threaded(step):
    """D's running statistics left as they were made."""
    import jax
    import jax.numpy as jnp

    def stale(state, batch):
        kept = jax.tree_util.tree_map(jnp.copy, state.batch_stats_d)
        new, metrics = step(state, batch)
        return new.replace(batch_stats_d=kept), metrics

    return stale


def _encoder_backward_flipped(step):
    """The encoder's share of G's gradient with the wrong sign, as the
    optimizer holds it after the step (Adam's first moments): its norms
    are the sound ones, its direction is not."""
    import jax

    def flipped(state, batch):
        new, metrics = step(state, batch)

        def flip(path, leaf):
            names = [getattr(k, "name", getattr(k, "key", None))
                     for k in path]
            return -leaf if "mu" in names and "encoder" in names else leaf

        return new.replace(opt_g=jax.tree_util.tree_map_with_path(
            flip, new.opt_g)), metrics

    return flipped


@pytest.mark.parametrize("trace, fault, caught_by", [
    (False, None, None),
    (True, None, None),
    (False, _state_unchanged, "params_change_g_worst_leaf_gap"),
    (False, _statistics_not_threaded, "batch_stats_d_widest_gap"),
    (False, _encoder_backward_flipped, "first_grad_g_encoder_cosine_gap"),
], ids=["untraced", "traced", "state_unchanged", "statistics_not_threaded",
        "encoder_backward_flipped"])
def test_vq_driver_end_to_end(monkeypatch, capsys, trace, fault, caught_by):
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
    assert driver.__name__.endswith("train_vq")
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
    assert {"generator_mean_abs_levels", "generator_p99_abs_levels",
            "distance_rel_gap", "index_disagrees_beyond_margin_share",
            "step1_d_weight_rel_gap", "first_grad_d_worst_leaf_gap",
            "first_grad_d_diff_over_norm", "params_change_g_worst_leaf_gap",
            "params_change_d_worst_leaf_gap", "codebook_first_grad_gap",
            "batch_stats_d_widest_gap", "step1_loss_d_rel_gap",
            "step1_g_lpips_rel_gap", "step1_g_codebook_rel_gap",
            "first_grad_g_encoder_cosine_gap",
            "first_grad_g_decoder_cosine_gap"} <= judged
    printed = {r["number"] for r in rows if r["limit"] is None}
    assert {"latent_rel_gap", "step1_loss_g_rel_gap",
            "first_grad_g_worst_leaf_gap", "later_d_weight_rel_gap",
            "codebook_params_change_gap"} <= printed
    if not trace:
        assert set(line["metrics"]) == {"train_img_per_s", "setup_s"}
        assert line["metrics"]["train_img_per_s"]["value"] > 0
        return
    # what needs no device trace is there even on the CPU; the readers of
    # the scope join find nothing to read without a device plane
    wanted = {m["name"] for m in cell.metrics_for(cell.per_layer)}
    assert set(READERS) <= wanted and set(line["metrics"]) <= wanted
    assert {"entry.compile_s", "loop.dispatch_ms",
            "data.loader_img_per_s"} <= set(line["metrics"])
    assert not set(READERS) & set(line["metrics"])


def test_vq_readers_on_a_scope_join():
    """The five readers on what ``scope_time.by_scope`` hands them, and on
    a run that lacks it (a program without the scopes, as the parent of
    the PR that brought them): nothing, no raise. The GN + swish reader
    leaves out the convolutions that carry the scope's passes."""
    read = {name: harness.load_by_path("layer_metrics", name).read
            for name in READERS}
    run = {"steps": 10, "trace": {"busy_s": 2.0},
           "vq_scopes": {"executions": 10,
                         "scope_s": {"gn_swish": 0.3,
                                     "gn_swish_fused_passes": 0.1,
                                     "gn_swish_fused_in_conv": 0.9,
                                     "attn": 0.05, "vq": 0.02,
                                     "loss_adaptive": 0.01,
                                     "unscoped": 0.4}}}
    assert read["model.gn_swish_ms_per_step"](run) == pytest.approx(40.0)
    assert read["model.gn_swish_share"](run) == pytest.approx(20.0)
    assert read["model.attn_ms_per_step"](run) == pytest.approx(5.0)
    assert read["model.vq_ms_per_step"](run) == pytest.approx(2.0)
    assert read["loss.adaptive_weight_ms_per_step"](run) == pytest.approx(
        1.0)
    for lacking in ({}, {"steps": 10, "trace": {"busy_s": 2.0}},
                    dict(run, vq_scopes={"executions": 10,
                                         "scope_s": {"unscoped": 1.9}})):
        assert all(r(lacking) is None for r in read.values())


def test_the_cell_is_in_the_benchmark_as_the_issue_names_it():
    """BENCHMARK.json: the configuration, the one-chip cell, the five
    readers on this cell alone, the cell on the lists of what it
    reports; the configuration file's widths are the preset's."""
    from p2p_tpu.core.config import get_preset

    bench = harness._read_json(os.path.join(
        os.path.dirname(harness.BENCH_DIR), "BENCHMARK.json"))
    name = "vqgan_imagenet_f16_16384.train"
    (cell,) = [w for w in bench["workloads"] if w["name"] == name]
    assert cell["chips"] == 1 and cell["traffic"] == "train"
    (config,) = [c for c in bench["configs"] if c["name"] == cell["config"]]
    assert config["reduced"] == ["dataset_pairs"]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for reader in READERS:
        assert by_name[reader]["workloads"] == [name]
        assert by_name[reader]["moves"] == "train_img_per_s"
    for listed in ("data.loader_img_per_s", "loop.dispatch_ms",
                   "step.device_ms", "device.idle_share.train",
                   "device.peak_hbm_gib", "loop.epoch_start_ms",
                   "data.feed_wait_ms", "loop.bookkeeping_ms"):
        assert name in by_name[listed]["workloads"]
    (rate,) = [m for m in bench["end_to_end"]
               if m["name"] == "train_img_per_s"]
    assert name in rate["workloads"]
    model = harness._read_json(os.path.join(
        os.path.dirname(harness.BENCH_DIR), config["file"]))["model"]
    preset = get_preset("vqgan_imagenet_f16").model
    assert (model["ch"], tuple(model["ch_mult"]), model["num_res_blocks"],
            model["n_embed"], model["embed_dim"], model["z_channels"]) == (
        preset.ngf, preset.vq_ch_mult, preset.vq_res_blocks,
        preset.vq_codes, preset.vq_embed_dim, preset.vq_embed_dim)
    from p2p_tpu.models import vqgan

    assert (model["attn_resolutions"], model["beta"]) == (
        [vqgan.ATTN_EXTENT], vqgan.BETA)


def _control_tool():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "control_vq", os.path.join(harness.BENCH_DIR, "tools",
                                   "control_vq.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    return tool


@pytest.mark.parametrize("kind", ["train", "steps"])
def test_controls_come_out_as_not_correct(capsys, kind):
    """``tools/control_vq.py`` at the toy size: the sound program passes
    ``check.verdict`` under the rehearsal's limits; the autoencoder with
    int8 kernels, the nearest-code search in bfloat16 and the step that
    saw half of its batch do not."""
    rc = _control_tool().main(
        ["--workload", CELL, "--bench_file", REHEARSAL, "--allow_cpu",
         "--kind", kind, "--seeds", "1", "--first_seed", str(SEED)])
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    row = [ln for ln in lines if "seed" in ln][-1]
    assert row["sound.correct"] is True, row
    refused = [k for k in row if k.startswith("control")
               and k.endswith(".correct")]
    assert len(refused) == (2 if kind == "train" else 1)
    assert not any(row[k] for k in refused), row
    assert rc == 0 and lines[-1]["sound_correct_and_controls_refused"]
    if kind == "train":
        assert row["control_bf16_distances.distance_rel_gap"] > 30 * max(
            row["sound.distance_rel_gap"], 1e-7)


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
