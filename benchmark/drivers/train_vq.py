"""Driver ``train_vq``: driver ``train``'s run for an autoencoder with a
learned quantizer (input = target), whose whole-step reference is its own.

The window is ``drivers/train.py``'s, letter for letter: one warm-up
epoch through ``Trainer.train_epoch()``, then whole epochs between two
fences on the state for ``--seconds``; ``train_img_per_s`` = steps
completed x batch over the time between the fences; ``setup_s`` net of
the check; the same three window conditions (no compile, no uncounted
step, no skipped epoch). What differs:

- the seeded dataset is ``benchmark/datagen.py``'s images with BOTH sides
  of a pair the image itself (``bits=8``: the banded copy is the image);
- the generator check before warm-up runs the autoencoder in train mode
  with its ``vq`` collection open and holds it against the
  configuration's reference on the host CPU, teacher-forced: the
  reference decodes the PROGRAM's indices (the reconstruction in 8-bit
  levels), its encoder's latent is held against the program's, and its
  nearest-code arithmetic runs on the program's OWN latent (the distance
  matrix's relative gap; the share of positions whose best and
  second-best code lie further apart than a stated margin and that carry
  another index). Two CONTROLS (``program_generator_path(control=...)``,
  read by ``benchmark/tools/control_vq.py``): ``int8`` rounds every
  kernel of the autoencoder to 8 bits, ``bf16_distances`` runs the
  nearest-code search in bfloat16;
- after the window the tapped first steps are followed by the
  configuration's own ``StepReference`` (LPIPS, the adaptive weight by
  two separate gradients, the codebook loss, D's BatchNorm statistics),
  and lambda, the codebook's own leaf and D's running statistics are
  compared beside what ``check.train_step_numbers`` compares, and D's
  first gradient is also held as a vector (the norm of its difference
  from the reference's), which a gradient of the right norm and the
  wrong direction does not pass;
- a traced run joins the trace with the compiled step's text by the
  scopes ``gn_swish`` (with ``benchmark/fused_scope.py``'s two tags),
  ``attn``, ``vq`` and ``loss_adaptive`` before the trace is removed and
  keeps the result in ``run["vq_scopes"]`` for the five readers
  ``model.gn_swish_*``, ``model.vq_ms_per_step``,
  ``model.attn_ms_per_step``, ``loss.adaptive_weight_ms_per_step``.
"""

from __future__ import annotations

import math
import os
import shutil
import time
from typing import Any, Dict

import numpy as np

from benchmark import (check, datagen, fused_scope, harness, scope_time,
                       trace_reduce)
from benchmark.drivers import train as base
from benchmark.drivers.train_labels import compiled_step_text, int8_kernels
from benchmark.harness import Cell, say

GAP_PRIORITY = base.GAP_PRIORITY
#: the state the step reference starts from, beside ``check.TRAIN_FIELDS``
STATS = "batch_stats_d"
#: the join the readers read: the GN + swish sites (their own ops, passes
#: fused under another name, convolutions outside the scope that carry
#: its passes), then the other mechanisms
JOIN = fused_scope.tags("gn_swish") + ("attn", "vq", "loss_adaptive")


def make_trainer(cell: Cell, marks: Dict[str, float], extra_argv=()):
    """``drivers/train.make_trainer`` on pairs whose two sides are the
    same image."""
    from p2p_tpu.cli import train as cli_train
    from p2p_tpu.core.cache import enable_compilation_cache
    from p2p_tpu.train.loop import Trainer

    cfgf = cell.config
    data_root = os.path.join(cell.work, "data")
    datagen.write_paired_dataset(
        data_root, cell.seed, cfgf["dataset_pairs"], 1,
        (cfgf["image_height"], cfgf["image_width"]), bits=8)
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
    images = np.stack(datagen.images(
        cell.seed, batch_size, (cfgf["image_height"], cfgf["image_width"])))
    return {"input": images, "target": images}


def program_generator_path(cfg, dtype, control: str = ""):
    """The system's autoencoder on one batch, from the module the train
    step builds, in train mode with its ``vq`` collection open: the
    reconstruction, the indices, the distance matrix and the latent it
    was taken on. ``control``: ``"int8"`` rounds the autoencoder's
    kernels to 8 bits, ``"bf16_distances"`` runs the nearest-code search
    in bfloat16 — what ``correct`` must refuse."""
    import jax
    import jax.numpy as jnp

    from p2p_tpu.models.vqgan import side_outputs
    from p2p_tpu.train.state import build_models
    from p2p_tpu.utils.images import ingest_input

    g, _, _ = build_models(cfg, dtype)
    if control == "bf16_distances":
        g = g.clone(distance_dtype=jnp.bfloat16)

    def path(state, batch):
        params = (int8_kernels(state.params_g) if control == "int8"
                  else state.params_g)
        pred, mut = g.apply({"params": params},
                            ingest_input(batch["input"], cfg.model, dtype),
                            True, mutable=["vq"])
        side = side_outputs(mut["vq"])
        return pred, side["indices"], side["distances"], side["latent"]

    return jax.jit(path)


def generator_numbers(reference, params: Dict[str, np.ndarray],
                      batch: Dict[str, np.ndarray], pred, indices, distances,
                      latent) -> Dict[str, float]:
    """The program's autoencoder against the reference on the same
    parameters, the decoder on the program's codes and the nearest-code
    arithmetic on the program's latent."""
    from benchmark.reference import nn

    indices, latent = np.asarray(indices), np.asarray(latent, np.float32)

    def forced(p, x, code, z):
        want, _, moments = reference.generator_path(p, x, True, code=code,
                                                    latent=z)
        return want, moments["latent"], moments["distances_on_latent"]

    want, ref_latent, ref_dist = nn.on_cpu(forced)(
        params, batch[reference.BATCH_KEY], indices, latent)
    numbers = {f"generator_{k}": v for k, v in check.image_errors(
        np.asarray(pred, np.float32), want).items()}
    numbers["generator_spread_levels"] = check.LEVEL * float(
        np.mean(np.abs(want - want.mean(axis=(0, 1, 2)))))
    rel = lambda got, ref: float(  # noqa: E731
        np.linalg.norm((got - ref).astype(np.float64))
        / max(np.linalg.norm(ref.astype(np.float64)), 1e-30))
    numbers["latent_rel_gap"] = rel(latent, ref_latent)
    numbers["distance_rel_gap"] = rel(np.asarray(distances, np.float32),
                                      ref_dist)
    # where the reference's own arithmetic on the same latent separates
    # the best code from the second best by more than the margin, the
    # program's index must be the best
    two = np.partition(ref_dist, 1, axis=1)[:, :2]
    spread = ref_dist.max(axis=1) - two[:, 0]
    clear = (two[:, 1] - two[:, 0]) > reference.INDEX_MARGIN * spread
    differs = ref_dist.argmin(axis=1) != indices.reshape(-1)
    numbers["index_disagrees_beyond_margin_share"] = float(
        np.mean(clear & differs))
    numbers["index_clear_share"] = float(np.mean(clear))
    numbers["index_differs_share"] = float(np.mean(differs))
    numbers["codes_used_in_batch"] = float(len(np.unique(indices)))
    return numbers


class VqTap(check.StepTap):
    """``check.StepTap`` that starts from D's running statistics too and
    keeps them after the last tapped step."""

    def __init__(self, step, state, steps: int, generator_path=None):
        super().__init__(step, state, steps)
        t0 = self._clock()
        self.state0.update(check.flatten_state(state, (STATS,)))
        self.stats: Dict[str, np.ndarray] = {}
        # ``program_generator_path``'s function: the codes the program
        # picks in its first step, for the reference to decode
        self._path, self.first_indices = generator_path, None
        self.seconds += self._clock() - t0

    def __call__(self, state, batch):
        if self._path is not None and not self.losses:
            t0 = self._clock()
            self.first_indices = np.asarray(self._path(state, batch)[1])
            self.seconds += self._clock() - t0
        last = len(self.losses) == self.steps - 1
        state, metrics = super().__call__(state, batch)
        if last:
            t0 = self._clock()
            self.stats = check.flatten_state(state, (STATS,))
            self.seconds += self._clock() - t0
        return state, metrics


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
    params = check.flatten_state(trainer.state, ("params_g",))
    path = program_generator_path(cfg, base.train_dtype(cfg))
    out = jax.device_get(path(trainer.state, batch))
    limits = dict(reference.LIMITS)
    numbers = generator_numbers(reference, params, batch, *out)
    del params, out
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
    tap = VqTap(trainer.train_step, trainer.state, hyper["steps"], path)
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
                if k.startswith(("vqgan_", "generator_gflop"))})
    if cell.trace:
        try:
            xplane = trace_reduce.find_xplane(trace_dir)
            run_obs["trace"] = trace_reduce.reduce_trace(
                xplane, GAP_PRIORITY,
                window_from=("bench_epoch", "bench_fence"))
            say(trace=run_obs["trace"])
            # the step's device time by mechanism, before the trace goes
            text = compiled_step_text(trainer)
            run_obs["vq_scopes"] = scope_time.by_scope(
                xplane, fused_scope.tagged(text, "gn_swish"), scopes=JOIN)
            say(by_scope={
                "mechanisms": run_obs["vq_scopes"],
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


def reference_start(tap: VqTap, trainer) -> Dict[str, np.ndarray]:
    """The flat state the step reference starts from: what the tap kept
    of the state before its first step, and the frozen LPIPS tree. Frees
    the program's state: the reference needs the chip."""
    import jax

    start = dict(tap.state0)
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            trainer.vgg_params or {})[0]:
        start[check.leaf_key("vgg", path)] = np.asarray(jax.device_get(leaf))
    for leaf in jax.tree_util.tree_leaves(trainer.state):
        leaf.delete()
    return start


def followed_steps(reference, hyper: dict, tap: VqTap,
                   start: Dict[str, np.ndarray]) -> Dict[str, float]:
    """Follow the tapped batches with the configuration's float32 step
    reference from the same start (its first step teacher-forced through
    the codes the program picked, where the tap kept them), and compare: ``check.
    train_step_numbers`` (losses, per net the worst leaf's first gradient
    and parameter change); lambda at step one and its widest gap later;
    the codebook's own leaf; D's running statistics after the last step
    (the widest distance over the reference's norm, a mean's over the
    norm of its layer's running standard deviation: statistics the step
    does not thread stay at their start). Leaves the reference names as
    having no gradient at all are left out."""
    losses, grads, params, stats = reference.StepReference(hyper).follow(
        start, tap.batches, tap.first_indices)
    dead = reference.zero_gradient_leaves(start)
    grads = {k: v for k, v in grads.items() if k not in dead}
    params = {k: v for k, v in params.items() if k not in dead}
    numbers = check.train_step_numbers(tap, losses, grads, params,
                                       hyper["beta1"], say)
    rel = lambda got, want: abs(got - want) / max(abs(want), 1e-30)  # noqa
    for name in ("d_weight", "g_codebook", "g_lpips"):
        gaps = [rel(got[name], want[name])
                for got, want in zip(tap.losses, losses)]
        numbers[f"step1_{name}_rel_gap"] = gaps[0]
        numbers[f"later_{name}_rel_gap"] = max(gaps[1:])
    book = reference.CODEBOOK
    norm = lambda a: float(np.linalg.norm(a.astype(np.float64)))  # noqa
    numbers["first_grad_d_diff_over_norm"] = first_grad_d_difference(
        tap, grads, hyper["beta1"])
    numbers.update(first_grad_g_direction(tap, grads))
    got_grad = tap.moments[book].astype(np.float32) / (1.0 - hyper["beta1"])
    # gaps of NORMS, as for every leaf: which rows the scatter-add lands
    # on turns on near-ties of the search
    numbers["codebook_first_grad_gap"] = (
        abs(norm(got_grad) - norm(grads[book]))
        / max(norm(grads[book]), 1e-30))
    moved = params[book] - tap.state0[book]
    numbers["codebook_params_change_gap"] = (
        abs(norm(tap.params[book] - tap.state0[book]) - norm(moved))
        / max(norm(moved), 1e-30))
    # a running mean is held against the running standard deviation it
    # normalises with, not against its own norm: the batch mean of a
    # convolution's output is what is left of a cancelling sum
    scale = lambda k: (np.sqrt(stats[k[:-len("mean")] + "var"])  # noqa: E731
                       if k.endswith("/mean") else stats[k])
    gap, leaf = max(
        (norm(tap.stats[k] - want) / max(norm(scale(k)), 1e-30), k)
        for k, want in stats.items())
    numbers["batch_stats_d_widest_gap"] = gap
    say(vq_steps={"batch_stats_d_widest_leaf": leaf,
                  "codes_used": [s.get("vq_codes_used") for s in tap.losses],
                  "perplexity": [s.get("vq_perplexity") for s in tap.losses]})
    return numbers


def first_grad_g_direction(tap, ref_grads) -> Dict[str, float]:
    """1 - the cosine between G's first gradient as its optimizer got it
    and the reference's, the encoder's leaves as one vector and the
    decoder's as another (a cosine needs no scale, so Adam's first moment
    stands for the gradient). A worst-leaf gap compares NORMS and a
    parameter change under Adam knows a gradient's signs only; this is
    what sees the direction of the autoencoder's backward. At the seeded
    start that direction does not survive bf16 (PERF.md section 6, PR
    34), so the limits refuse a backward that is uncorrelated with the
    truth or has its sign, not one that is a little off."""
    out = {}
    for part in ("encoder", "decoder"):
        keys = sorted(k for k in ref_grads
                      if k.startswith(f"params_g/{part}/"))
        flat = lambda tree: np.concatenate(  # noqa: E731
            [np.asarray(tree[k], np.float64).ravel() for k in keys])
        got, want = flat(tap.moments), flat(ref_grads)
        out[f"first_grad_g_{part}_cosine_gap"] = 1.0 - float(
            np.vdot(got, want) / max(
                np.linalg.norm(got) * np.linalg.norm(want), 1e-300))
    return out


def first_grad_d_difference(tap, ref_grads, beta1: float) -> float:
    """D's first gradient as its optimizer got it against the
    reference's, all leaves as one vector: the norm of the DIFFERENCE over
    the reference's norm. The worst-leaf gaps compare norms, which a
    gradient of the right size and the wrong direction passes (a step
    that saw half of its batch reads a D gradient of the sound norm); D
    is small and bf16 moves its gradient by a few percent, so the
    difference itself can be held."""
    keys = sorted(k for k in ref_grads if k.startswith("params_d/"))
    flat = lambda tree, scale: np.concatenate(  # noqa: E731
        [np.asarray(tree[k], np.float64).ravel() * scale for k in keys])
    want = flat(ref_grads, 1.0)
    got = flat(tap.moments, 1.0 / (1.0 - beta1))
    return float(np.linalg.norm(got - want)
                 / max(np.linalg.norm(want), 1e-30))
