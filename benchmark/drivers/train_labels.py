"""Driver ``train_labels``: driver ``train``'s run for a configuration
whose conditioning input is a LABEL MAP (class ids + an instance-edge
bit) and whose whole-step reference is its own.

The window is ``drivers/train.py``'s, letter for letter: one warm-up
epoch through ``Trainer.train_epoch()``, then whole epochs between two
fences on the state for ``--seconds``; ``train_img_per_s`` = steps
completed x batch over the time between the fences; ``setup_s`` net of
the check; the same three window conditions (no compile, no uncounted
step, no skipped epoch). What differs:

- the seeded dataset is ``benchmark/datagen_labels.py``'s (photo + label
  map), and the Trainer reads it through its label loader;
- the generator check before warm-up runs the generator from the label
  map, with its spectral vectors, against the configuration's reference
  on the host CPU; its CONTROL (``program_generator_path(control=True)``,
  read by ``benchmark/tools/control_labels.py``) is the same program with
  every generator kernel rounded to int8, the nearest precision below
  the bf16 the configuration computes in;
- after the window the tapped first steps are followed by the
  configuration's own ``StepReference`` (hinge, two learning rates, the
  power iterations of G and D), and the spectral vectors after the last
  step are compared too;
- a traced run joins the trace with the compiled step's text by the
  scope ``spade`` (``benchmark/scope_time.by_scope``) before the trace is
  removed, fusions that do the scope's work under another instruction's
  name included (``benchmark/fused_scope.py``), and keeps the result in
  ``run["spade_scope"]`` for the readers ``model.spade_ms_per_step`` and
  ``model.spade_share``.
"""

from __future__ import annotations

import math
import os
import shutil
import time
from typing import Any, Dict

import numpy as np

from benchmark import (check, datagen_labels, fused_scope, harness, scope_time,
                       trace_reduce)
from benchmark.drivers import train as base
from benchmark.harness import Cell, say

#: host annotations a gap of the device is named by, innermost first
GAP_PRIORITY = base.GAP_PRIORITY
#: the power-iteration vectors compared after the last tapped step
VECTORS = ("spectral_g", "spectral_d")
#: the mechanism's scope with its two tags for work fused under another
#: name; the scopes inside one SPADE site; the generator's ResBlks
SPADE_JOIN = fused_scope.tags("spade")
SPADE_PARTS = ("shared_conv", "gamma_beta", "modulate")
BLOCKS = ("head_0", "G_middle_0", "G_middle_1", "up_0", "up_1", "up_2",
          "up_3")


def make_trainer(cell: Cell, marks: Dict[str, float], extra_argv=()):
    """``drivers/train.make_trainer`` with the label-map dataset."""
    from p2p_tpu.cli import train as cli_train
    from p2p_tpu.core.cache import enable_compilation_cache
    from p2p_tpu.train.loop import Trainer

    cfgf = cell.config
    data_root = os.path.join(cell.work, "data")
    datagen_labels.write_label_dataset(
        data_root, cell.seed, cfgf["dataset_pairs"], 1,
        (cfgf["image_height"], cfgf["image_width"]), cfgf["label_classes"])
    marks["dataset_written"] = time.perf_counter() - cell.t_start
    workdir = os.path.join(cell.work, "train")
    shutil.rmtree(workdir, ignore_errors=True)   # a checkpoint = a resume
    os.makedirs(workdir)
    args = cli_train.build_parser().parse_args(
        base.train_argv(cell, data_root, workdir) + list(extra_argv))
    cfg = cli_train.config_from_flags(args)
    enable_compilation_cache(args.compilation_cache)
    trainer = Trainer(cfg, data_root=data_root, workdir=workdir)
    marks["trainer_built"] = time.perf_counter() - cell.t_start
    return trainer, cfg


def first_batch(cell: Cell, batch_size: int) -> Dict[str, np.ndarray]:
    cfgf = cell.config
    made = datagen_labels.pairs(
        cell.seed, batch_size, (cfgf["image_height"], cfgf["image_width"]),
        cfgf["label_classes"])
    return {"input": np.stack([m for m, _ in made]),
            "target": np.stack([p for _, p in made])}


def int8_kernels(params):
    """Every ``kernel`` leaf rounded to 8 bits (symmetric, one scale a
    tensor): the control's weights."""
    import jax
    import jax.numpy as jnp

    def q(path, leaf):
        if getattr(path[-1], "key", None) != "kernel":
            return leaf
        scale = jnp.max(jnp.abs(leaf)) / 127.0
        return jnp.round(leaf / scale) * scale

    return jax.tree_util.tree_map_with_path(q, params)


def program_generator_path(cfg, dtype, control: bool = False):
    """The system's generator on one batch of label maps, from the module
    the train step builds, in train mode (the batch's own moments, one
    power iteration of its spectral norms). ``control``: its kernels
    rounded to int8 — what ``correct`` must refuse."""
    import jax

    from p2p_tpu.train.state import build_models
    from p2p_tpu.utils.images import ingest_input

    g, _, _ = build_models(cfg, dtype)

    def path(state, batch):
        params = int8_kernels(state.params_g) if control else state.params_g
        pred, _ = g.apply(
            {"params": params, "batch_stats": state.batch_stats_g,
             "spectral": state.spectral_g},
            ingest_input(batch["input"], cfg.model, dtype), True,
            mutable=["batch_stats", "spectral"])
        return pred

    return jax.jit(path)


def generator_numbers(reference, params: Dict[str, np.ndarray],
                      batch: Dict[str, np.ndarray], pred) -> Dict[str, float]:
    from benchmark.reference import nn

    want = nn.on_cpu(lambda p, x: reference.generator_path(p, x, True)[0])(
        params, batch[reference.BATCH_KEY])
    got = np.asarray(pred, np.float32)
    numbers = {f"generator_{k}": v
               for k, v in check.image_errors(got, want).items()}
    # a seeded SPADE generator (xavier gain 0.02) paints a faint image, a
    # few levels around grey: printed beside the errors, not judged
    numbers["generator_spread_levels"] = check.LEVEL * float(
        np.mean(np.abs(want - want.mean(axis=(0, 1, 2)))))
    return numbers


class LabelTap(check.StepTap):
    """``check.StepTap`` that starts from G's spectral vectors too and
    keeps both nets' vectors after the last tapped step."""

    def __init__(self, step, state, steps: int):
        super().__init__(step, state, steps)
        t0 = self._clock()
        self.state0.update(check.flatten_state(state, ("spectral_g",)))
        self.vectors: Dict[str, np.ndarray] = {}
        self.seconds += self._clock() - t0

    def __call__(self, state, batch):
        last = len(self.losses) == self.steps - 1
        state, metrics = super().__call__(state, batch)
        if last:
            t0 = self._clock()
            self.vectors = check.flatten_state(state, VECTORS)
            self.seconds += self._clock() - t0
        return state, metrics


def compiled_step_text(trainer) -> str:
    """The text of the executable the Trainer's step runs: lowered from a
    DEVICE batch under the Trainer's batch sharding, as the loop feeds it
    (from a host array the text numbers its functions another way: another
    cache key, a cold compile), and "compiled" by a load from the cache
    the run's own compile filled."""
    from p2p_tpu.data.pipeline import device_prefetch

    (batch,) = device_prefetch([trainer._host_batch_sample()],
                               trainer.batch_sharding)
    return trainer.train_step.lower(trainer.state, batch).compile().as_text()


def run(cell: Cell) -> str:
    # first of all: importing the program's configuration imports jax,
    # which reads the compile cache's directory from the environment once
    harness.prepare_jax_env(cell)
    import jax

    from p2p_tpu.core.config import list_presets

    cfgf = cell.config
    if cfgf["preset"] not in list_presets():
        raise harness.CellError(
            f"the program has no preset {cfgf['preset']!r}: it cannot run "
            f"the configuration {cell.config_name!r}")

    device = harness.device_info(cell.entry["chips"], cell.require_tpu)
    reference = harness.load_by_path("reference", cfgf["reference"])
    meter = harness.CompileMeter()
    cache_before = harness.dir_bytes(cell.cache_dir)

    marks = {"imports_device": time.perf_counter() - cell.t_start}
    trainer, cfg = make_trainer(cell, marks)
    batch_size = cfg.data.batch_size
    steps_per_epoch = trainer.steps_per_epoch
    run_obs: Dict[str, Any] = {"batch": batch_size,
                               "device_kind": device["kind"]}

    # ---- the output check, before the window; not counted as set-up ----
    t_check = time.perf_counter()
    batch = first_batch(cell, batch_size)
    params = check.flatten_state(
        trainer.state, ("params_g", "batch_stats_g", "spectral_g"))
    pred = jax.device_get(program_generator_path(
        cfg, base.train_dtype(cfg))(trainer.state, batch))
    limits = dict(reference.LIMITS)
    numbers = generator_numbers(reference, params, batch, pred)
    del params, pred
    check_s = time.perf_counter() - t_check

    # ---- warm-up: one epoch compiles the step and fills the memo -------
    if cell.trace and cell.workload.get("dump_lowered_step"):
        ir_dir = os.path.join(cell.work, "ir")
        shutil.rmtree(ir_dir, ignore_errors=True)
        os.makedirs(ir_dir)
        jax.config.update("jax_dump_ir_to", ir_dir)
        run_obs["ir_dir"] = ir_dir
    marks["check_done"] = time.perf_counter() - cell.t_start
    # the first steps of the very step, state and feed the window times,
    # kept for the comparison with the plain reference after the window
    hyper = cfgf["train_reference"]
    tap = LabelTap(trainer.train_step, trainer.state, hyper["steps"])
    trainer.train_step = tap
    warm = trainer.train_epoch(seed=trainer.epoch)
    trainer.train_step = tap.inner
    check_s += tap.seconds
    marks["warm_epoch_done"] = time.perf_counter() - cell.t_start
    jax.config.update("jax_dump_ir_to", None)
    if cell.trace:
        run_obs["loader_img_per_s"] = base.loader_rate(trainer, 2.0)
    setup_counts = meter.counts()
    cache_written = harness.dir_bytes(cell.cache_dir) - cache_before
    say(setup=setup_counts, cache_bytes_written=cache_written,
        cache_bytes_total=harness.dir_bytes(cell.cache_dir),
        machine_cache_cap=harness.MACHINE_CACHE_CAP, check_seconds=check_s,
        seconds_since_start=marks,
        memory_stats=jax.local_devices()[0].memory_stats(),
        warm_epoch={k: float(v) for k, v in warm.items()})
    run_obs["setup"] = setup_counts

    # ---- the window ----------------------------------------------------
    disp = trainer.obs.histogram("dispatch_secs")
    disp_before = (disp.sum, disp.count)
    seconds = cell.seconds
    trace_dir = os.path.join(cell.work, "trace")
    if cell.trace:
        seconds = min(seconds, float(cell.workload.get("trace_seconds", 8)))
        shutil.rmtree(trace_dir, ignore_errors=True)
    jax.block_until_ready(trainer.state)
    step_before = int(trainer.state.step)
    setup_s = time.perf_counter() - cell.t_start - check_s
    if cell.trace:
        jax.profiler.start_trace(trace_dir)
    epochs, means = 0, []
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        trainer.epoch += 1
        with jax.profiler.TraceAnnotation("bench_epoch"):
            means.append(trainer.train_epoch(seed=trainer.epoch))
        epochs += 1
    with jax.profiler.TraceAnnotation("bench_fence"):
        jax.block_until_ready(trainer.state)
    elapsed = time.perf_counter() - t0
    if cell.trace:
        jax.profiler.stop_trace()
    window_counts = harness.delta(meter.counts(), setup_counts)
    steps = int(trainer.state.step) - step_before
    img_per_s = steps * batch_size / elapsed

    # ---- what the window itself must show ------------------------------
    finite = all(math.isfinite(float(v)) for m in means for v in m.values())
    healthy = all(float(m.get("health_ok", 1.0)) == 1.0 for m in means)
    numbers.update({
        "window_xla_compiles": float(window_counts["n_compiles"]),
        "steps_not_counted": float(abs(steps - epochs * steps_per_epoch)),
        "nonfinite_or_skipped_epochs": float(not (finite and healthy)),
    })
    limits.update({"window_xla_compiles": 0.0, "steps_not_counted": 0.0,
                   "nonfinite_or_skipped_epochs": 0.0})

    run_obs.update(
        steps=steps, images=steps * batch_size, elapsed=elapsed,
        dispatch_s=disp.sum - disp_before[0],
        dispatches=disp.count - disp_before[1],
        peak_bytes=harness.peak_memory_bytes())
    say(window={"epochs": epochs, "steps": steps, "elapsed_s": elapsed,
                "img_per_s": img_per_s, "setup_s": setup_s,
                "losses": {k: float(v) for k, v in means[-1].items()}},
        window_counts=window_counts,
        gauges={k: v["value"] for k, v in trainer.obs.snapshot().items()
                if k.startswith(("spade_", "generator_gflop"))})
    if cell.trace:
        try:
            xplane = trace_reduce.find_xplane(trace_dir)
            run_obs["trace"] = trace_reduce.reduce_trace(
                xplane, GAP_PRIORITY,
                window_from=("bench_epoch", "bench_fence"))
            say(trace=run_obs["trace"])
            # the step's device time by scope, before the trace goes: by
            # the mechanism (the readers' join: the scope's own ops and
            # the fusions that hold its work under another name), then by
            # its parts, by ResBlk and by net, for whoever reads the lines
            text = compiled_step_text(trainer)
            run_obs["spade_scope"] = scope_time.by_scope(
                xplane, fused_scope.tagged(text, "spade"), scopes=SPADE_JOIN)
            say(by_scope={
                "spade": run_obs["spade_scope"],
                "spade_parts": scope_time.by_scope(xplane, text,
                                                   SPADE_PARTS)["scope_s"],
                "blocks": scope_time.by_scope(xplane, text,
                                              BLOCKS)["scope_s"],
                "nets": scope_time.by_scope(xplane, text)["scope_s"]})
        except ValueError:
            # the CPU rehearsal has no device plane; on the chip a trace in
            # which no device op ran is a failed run
            if cell.require_tpu:
                raise
        shutil.rmtree(trace_dir, ignore_errors=True)
    device["memory_peak_bytes"] = run_obs["peak_bytes"]
    trainer.close()
    meter.close()

    # ---- the whole step against the plain reference, the chip freed ----
    t_ref = time.perf_counter()
    numbers.update(followed_steps(reference, hyper, tap,
                                  reference_start(tap, trainer)))
    say(reference_seconds=time.perf_counter() - t_ref)
    if not cell.require_tpu:
        # a rehearsal at toy sizes states its own limits
        limits.update({k: v for k, v in cfgf.get("limits", {}).items()
                       if k in limits})
    correct = check.verdict(numbers, limits, say)
    measured = {"train_img_per_s": img_per_s, "setup_s": setup_s}
    return harness.result_line(cell, correct, steps, 0, measured, run_obs,
                               device)


def reference_start(tap: LabelTap, trainer) -> Dict[str, np.ndarray]:
    """The flat state the step reference starts from: what the tap kept
    of the state before its first step, and VGG19's weights. Frees the
    program's state: the reference needs the chip."""
    import jax

    start = dict(tap.state0)
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            trainer.vgg_params or {})[0]:
        start[check.leaf_key("vgg", path)] = np.asarray(jax.device_get(leaf))
    for leaf in jax.tree_util.tree_leaves(trainer.state):
        leaf.delete()
    return start


def followed_steps(reference, hyper: dict, tap: LabelTap,
                   start: Dict[str, np.ndarray]) -> Dict[str, float]:
    """Follow the tapped batches with the configuration's float32 step
    reference from the same start (``reference_start``), and compare:
    ``check.train_step_numbers`` and, of each net's spectral vectors after
    the last step, the widest distance between the program's unit vector
    and the reference's (a vector the step does not thread stays the
    seeded one: ~1.4). Leaves the reference names as having no gradient
    at all (``zero_gradient_leaves``) are left out."""
    losses, grads, params, vectors = reference.StepReference(hyper).follow(
        start, tap.batches)
    dead = reference.zero_gradient_leaves(start)
    grads = {k: v for k, v in grads.items() if k not in dead}
    params = {k: v for k, v in params.items() if k not in dead}
    numbers = check.train_step_numbers(tap, losses, grads, params,
                                       hyper["beta1"], say)
    widest = {}
    for net in VECTORS:
        gap, leaf = max((float(np.linalg.norm(tap.vectors[k] - u)), k)
                        for k, u in vectors.items() if k.startswith(net))
        numbers[f"{net}_u_widest_gap"] = gap
        widest[net] = {"widest_gap": gap, "leaf": leaf}
    say(spectral_vectors=widest)
    return numbers
