"""Driver ``train_inpaint``: driver ``train``'s run for an inpainting
configuration (the loader MAKES the input from the target and a mask it
draws for every sample), whose whole-step reference is its own, with
every phase of the run reckoned against the run's clock.

The window is ``drivers/train.py``'s, letter for letter: one warm-up
epoch through ``Trainer.train_epoch()``, then whole epochs between two
fences on the state for ``--seconds``; ``train_img_per_s`` = steps
completed x batch over the time between the fences; ``setup_s`` net of
the check; the same three window conditions (no compile, no uncounted
step, no skipped epoch). What differs:

- the seeded dataset is ``benchmark/datagen.py``'s images with both sides
  of a pair the image itself (``bits=8``); the input side is never read:
  the program's loader blanks the target under a mask drawn from (seed,
  epoch, index) and ships it with the mask as a fourth channel;
- what the loader fed is held to that contract on the tapped batches
  (:func:`feed_numbers`: a missing pixel is blank, a known one the
  target's, the mask channel is 0 / 255, the masked share lies in the
  generator's range);
- the generator check before warm-up runs the generator in train mode on
  the loader's first items and holds its image, in 8-bit levels, against
  the configuration's reference on the host CPU, twice: as the step
  computes it (bf16) and from the same modules at float32
  (``generator_f32_*``), where what the configuration states as float32
  (the transforms' operands) shows; the three programs (the reference on
  the host, the two on the chip) run side by side, so a cold run compiles
  them at once. Two CONTROLS (read by ``benchmark/tools/
  control_inpaint.py``): ``lowp_kernels`` rounds every kernel of the
  program's generator to 3 mantissa bits (``lax.reduce_precision``);
  ``bf16_fft`` is a REFERENCE whose transforms read operands rounded to
  bfloat16 (``reference.rounded_transforms``), against which the sound
  program reads what a program with that fault would read against the
  sound reference;
- after the window the tapped first steps are followed by the
  configuration's own ``StepReference`` (FFCs, the masked non-saturating
  loss with its R1 penalty by ``jax.grad`` of ``jax.grad``, feature
  matching, the dilated ResNet50), on the chip in float32 unless the
  reference says ``HOST``; beside what ``check.train_step_numbers``
  compares, each term of G's loss and the penalty's own value at step
  one, two named leaves' first gradients as vectors (a Fourier unit's 1x1
  kernel, D's last kernel), and the running statistics of G and of D;
- a traced run joins the trace with the compiled step's text by the
  scopes ``ffc_local`` / ``ffc_spectral`` / ``d_r1`` / ``loss_hrf``
  (``run["inpaint_scopes"]``), by ``ffc_fft`` alone (it lies inside
  ``ffc_spectral``: ``run["inpaint_fft"]``) and by the step's own, before
  the trace is removed, for the readers ``model.ffc_*``,
  ``loss.r1_ms_per_step`` and ``loss.hrf_ms_per_step``;
- the last lines before the result say where the run's seconds went
  (``run_wall_s`` and its parts): a run is given 360 s, process start to
  exit.
"""

from __future__ import annotations

import math
import os
import shutil
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict

import numpy as np

from benchmark import check, harness, scope_time, trace_reduce
from benchmark.drivers import train as base
from benchmark.drivers.train_labels import compiled_step_text
# images alone, both sides of a pair the image: the autoencoder cell's
# dataset and Trainer; here the loader makes the input from the target
from benchmark.drivers.train_vq import make_trainer
from benchmark.harness import Cell, say

GAP_PRIORITY = base.GAP_PRIORITY
#: the state the step reference starts from, beside ``check.TRAIN_FIELDS``
STATS = ("batch_stats_g", "batch_stats_d")
#: the mechanisms the readers read; ``ffc_fft`` lies inside
#: ``ffc_spectral`` and is joined alone
JOIN = ("ffc_local", "ffc_spectral", "d_r1", "loss_hrf")
JOIN_FFT = ("ffc_fft",)
#: mantissa bits the ``lowp_kernels`` control keeps (bfloat16 has 7)
LOWP_MANTISSA = 3
#: each term of the step's losses compared beside loss_d and loss_g
TERMS = ("loss_d_r1", "g_gan", "g_feat", "g_hrf", "g_l1_known")


def first_batch(trainer, batch_size: int) -> Dict[str, np.ndarray]:
    """The first ``batch_size`` items of the Trainer's own dataset, in
    order: masks as the loader draws them."""
    items = [trainer.train_ds[i] for i in range(batch_size)]
    return {k: np.stack([it[k] for it in items]) for k in items[0]}


def feed_numbers(batches, mask_channel: int = 3) -> Dict[str, float]:
    """What the loader fed, held to its contract: the share of values of
    the input's image channels that are neither blank under the mask nor
    the target's outside it, the share of mask values that are neither 0
    nor 255, and the mean masked share (printed beside its range)."""
    wrong = odd = total = 0
    shares = []
    for b in batches:
        u, x = np.asarray(b["input"]), np.asarray(b["target"])
        m = u[..., mask_channel:mask_channel + 1]
        want = np.where(m > 0, 0, x)
        wrong += int(np.sum(u[..., :mask_channel] != want))
        odd += int(np.sum((m != 0) & (m != 255)))
        total += want.size
        shares += list((m > 0).mean(axis=(1, 2, 3)))
    return {"input_not_target_under_mask_share": wrong / max(total, 1),
            "mask_not_binary_share": odd / max(total, 1),
            "masked_share_mean": float(np.mean(shares)),
            "masked_share_min": float(np.min(shares)),
            "masked_share_max": float(np.max(shares))}


def lowp_kernels(params):
    """Every ``kernel`` leaf rounded to ``LOWP_MANTISSA`` mantissa bits:
    the control's weights (``lax.reduce_precision``: the compiler keeps
    it where it drops an ``astype`` pair)."""
    import jax

    def q(path, leaf):
        if getattr(path[-1], "key", None) != "kernel":
            return leaf
        return jax.lax.reduce_precision(leaf, 8, LOWP_MANTISSA)

    return jax.tree_util.tree_map_with_path(q, params)


def program_generator_path(cfg, dtype, control: str = ""):
    """The system's generator on one batch, from the module the train
    step builds, in train mode (the batch's own moments): the predicted
    image. At ``dtype`` float32 every product runs at HIGHEST precision.
    ``control`` (what ``correct`` must refuse): ``"lowp_kernels"`` rounds
    the generator's kernels."""
    import contextlib

    import jax
    import jax.numpy as jnp

    from p2p_tpu.train.state import build_models
    from p2p_tpu.utils.images import ingest_input

    g, _, _ = build_models(cfg, dtype)
    if control not in ("", "lowp_kernels"):
        raise ValueError(f"unknown control {control!r}")

    def path(state, batch):
        params = state.params_g
        if control == "lowp_kernels":
            params = lowp_kernels(params)
        with (jax.default_matmul_precision("highest")
              if dtype == jnp.float32 else contextlib.nullcontext()):
            pred, _ = g.apply(
                {"params": params, "batch_stats": state.batch_stats_g},
                ingest_input(batch["input"], cfg.model, dtype), True,
                mutable=["batch_stats"])
        return pred

    return jax.jit(path)


def reference_image(reference, state, batch, **variant) -> np.ndarray:
    """The reference's predicted image from ``state``'s generator, on the
    host CPU (part by part: the reference jits its encoder, ONE residual
    block and its decoder, so a cold run compiles one block). ``variant``:
    a control's keywords of ``reference.generator_path``."""
    import jax

    with jax.default_device(jax.devices("cpu")[0]):
        return np.asarray(reference.generator_path(
            check.flatten_state(state, ("params_g", "batch_stats_g")),
            batch[reference.BATCH_KEY], True, **variant)[0])


def program_images(cfg, dtype, state, batch, control: str = ""
                   ) -> Dict[str, np.ndarray]:
    """The program's predicted image from ``state``: as the step computes
    it (``generator``) and from the same modules at float32
    (``generator_f32``); the two side by side, each a compile of its
    own."""
    import jax
    import jax.numpy as jnp

    def image(dt):
        return np.asarray(jax.device_get(program_generator_path(
            cfg, dt, control)(state, batch)), np.float32)

    with ThreadPoolExecutor(2) as pool:
        return dict(zip(("generator", "generator_f32"),
                        pool.map(image, (dtype, jnp.float32))))


def generator_numbers(want: np.ndarray, images: Dict[str, np.ndarray]
                      ) -> Dict[str, float]:
    """The program's ``images`` (:func:`program_images`) against the
    reference's (``want``, :func:`reference_image`), in 8-bit levels. And
    how far the reference's own output spreads (a constant image would
    pass any comparison)."""
    numbers = {f"{prefix}_{k}": v for prefix, pred in images.items()
               for k, v in check.image_errors(pred, want).items()}
    numbers["generator_spread_levels"] = check.LEVEL * float(
        np.mean(np.abs(want - want.mean(axis=(0, 1, 2)))))
    return numbers


class InpaintTap(check.StepTap):
    """``check.StepTap`` that starts from the running statistics of G and
    D too and keeps them after the last tapped step."""

    def __init__(self, step, state, steps: int):
        super().__init__(step, state, steps)
        t0 = self._clock()
        self.state0.update(check.flatten_state(state, STATS))
        self.stats: Dict[str, np.ndarray] = {}
        self.seconds += self._clock() - t0

    def __call__(self, state, batch):
        last = len(self.losses) == self.steps - 1
        state, metrics = super().__call__(state, batch)
        if last:
            t0 = self._clock()
            self.stats = check.flatten_state(state, STATS)
            self.seconds += self._clock() - t0
        return state, metrics


def joined(xplane_path: str, hlo_text: str, joins: Dict[str, tuple]
           ) -> Dict[str, dict]:
    """``scope_time.by_scope`` for several scope lists in ONE pass over the
    trace (a traced window of this cell holds over a million op events,
    and a pass over them costs tens of seconds of the run's 360): for each
    name of ``joins`` what ``by_scope(xplane_path, hlo_text, scopes)``
    returns of ``module``, ``executions``, ``scope_s`` and ``op_s``, from
    the same instruction -> first-scope maps, the same module runs and
    the same ops (container ops left out, seconds averaged over chips)."""
    import bisect

    from jax.profiler import ProfileData

    module = scope_time.module_name(hlo_text)
    owners = {name: scope_time.instruction_scopes(hlo_text, scopes)
              for name, scopes in joins.items()}
    sums: Dict[str, Dict[str, float]] = {name: {} for name in joins}
    executions = n_chips = 0
    for plane in ProfileData.from_file(xplane_path).planes:
        if not trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        runs, ops = [], []
        for line in plane.lines:
            if line.name == scope_time.MODULE_LINE:
                runs += [(int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
                         for ev in line.events
                         if ev.name.split("(", 1)[0] == module]
            elif line.name == trace_reduce.OP_LINE:
                ops += list(line.events)
        if not ops:
            continue
        n_chips += 1
        runs.sort()
        executions += len(runs)
        starts = [s for s, _ in runs]
        for ev in ops:
            i = bisect.bisect_right(starts, int(ev.start_ns)) - 1
            if i < 0 or ev.start_ns >= runs[i][1]:
                continue
            name, _, opcode = trace_reduce.parse_op(ev.name)
            if opcode in trace_reduce.CONTAINER_OPCODES:
                continue
            seconds = ev.duration_ns / 1e9
            for join, owner in owners.items():
                scope = owner.get(name) or scope_time.UNSCOPED
                sums[join][scope] = sums[join].get(scope, 0.0) + seconds
    if not n_chips:
        raise ValueError(f"{xplane_path}: no device op in the trace")
    out = {}
    for join, scope_s in sums.items():
        scope_s = {k: v / n_chips for k, v in scope_s.items()}
        out[join] = {"module": module, "executions": executions // n_chips,
                     "scope_s": scope_s, "op_s": sum(scope_s.values())}
    return out


def ffc_shapes(cfg, batch_size: int) -> Dict[str, int]:
    """The Fourier units' operand, from the configuration: how many
    units, and the ``[n, h, w, c]`` each transforms."""
    from p2p_tpu.models.ffc import EXTENT_MULTIPLE, N_DOWN, split_channels

    h, w = cfg.image_hw
    _, c_g = split_channels(cfg.model.ngf * 2 ** N_DOWN, cfg.model.ffc_ratio)
    return {"units": 2 * cfg.model.n_blocks, "n": batch_size,
            "h": h // EXTENT_MULTIPLE, "w": w // EXTENT_MULTIPLE,
            "c": c_g // 2}


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
    dtype = base.train_dtype(cfg)
    run_obs: Dict[str, Any] = {"batch": batch_size,
                               "device_kind": device["kind"],
                               "ffc_shapes": ffc_shapes(cfg, batch_size)}

    # ---- the output check, before the window; not counted as set-up ----
    t_check = time.perf_counter()
    batch = first_batch(trainer, batch_size)
    limits = dict(reference.LIMITS)
    with ThreadPoolExecutor(1) as host:
        want = host.submit(reference_image, reference, trainer.state, batch)
        images = program_images(cfg, dtype, trainer.state, batch)
        numbers = generator_numbers(want.result(), images)
    generator_check_s = check_s = time.perf_counter() - t_check

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
    tap = InpaintTap(trainer.train_step, trainer.state, hyper["steps"])
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
    t_stop = time.perf_counter()
    if cell.trace:
        jax.profiler.stop_trace()   # writes the trace: seconds of its own
    trace_written_s = time.perf_counter() - t_stop
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
                if k.startswith(("ffc_", "lama_", "generator_gflop"))})
    t_trace = time.perf_counter()
    if cell.trace:
        try:
            xplane = trace_reduce.find_xplane(trace_dir)
            run_obs["trace"] = trace_reduce.reduce_trace(
                xplane, GAP_PRIORITY,
                window_from=("bench_epoch", "bench_fence"))
            say(trace=run_obs["trace"])
            # the step's device time by mechanism, before the trace goes
            text = compiled_step_text(trainer)
            by = joined(xplane, text, {
                "mechanisms": JOIN, "fft": JOIN_FFT,
                "nets": scope_time.program_scopes()})
            run_obs["inpaint_scopes"] = by["mechanisms"]
            run_obs["inpaint_fft"] = by["fft"]
            say(by_scope={"mechanisms": by["mechanisms"],
                          "fft": by["fft"]["scope_s"],
                          "nets": by["nets"]["scope_s"]})
        except ValueError:
            # the CPU rehearsal has no device plane; on the chip a trace in
            # which no device op ran is a failed run
            if cell.require_tpu:
                raise
        shutil.rmtree(trace_dir, ignore_errors=True)
    trace_reduction_s = time.perf_counter() - t_trace
    device["memory_peak_bytes"] = run_obs["peak_bytes"]
    trainer.close()
    meter.close()

    # ---- the whole step against the plain reference, the chip freed ----
    t_ref = time.perf_counter()
    numbers.update(feed_numbers(tap.batches))
    limits.update({"input_not_target_under_mask_share": 0.0,
                   "mask_not_binary_share": 0.0})
    numbers.update(followed_steps(reference, hyper, tap,
                                  reference_start(tap, trainer)))
    followed_steps_s = time.perf_counter() - t_ref
    say(reference_seconds=followed_steps_s, reference_on_host=reference.HOST)
    if not cell.require_tpu:
        # a rehearsal at toy sizes states its own limits
        limits.update({k: v for k, v in cfgf.get("limits", {}).items()
                       if k in limits})
    correct = check.verdict(numbers, limits, say)
    measured = {"train_img_per_s": img_per_s, "setup_s": setup_s}
    # where the run's seconds went: a run is given 360 s, start to exit
    say(run_clock={
        "run_wall_s": time.perf_counter() - cell.t_start,
        "setup_s": setup_s, "generator_check_s": generator_check_s,
        "warmup_s": marks["warm_epoch_done"] - marks["check_done"],
        "window_s": elapsed, "trace_written_s": trace_written_s,
        "trace_reduction_s": trace_reduction_s,
        "followed_steps_s": followed_steps_s,
        "limit_s": cfgf.get("run_budget", {}).get("limit_s")})
    return harness.result_line(cell, correct, steps, 0, measured, run_obs,
                               device)


def reference_start(tap: InpaintTap, trainer) -> Dict[str, np.ndarray]:
    """The flat state the step reference starts from: what the tap kept
    of the state before its first step, and the frozen perceptual tree.
    Frees the program's state: the reference needs the chip."""
    import jax

    start = dict(tap.state0)
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            trainer.vgg_params or {})[0]:
        start[check.leaf_key("vgg", path)] = np.asarray(jax.device_get(leaf))
    for leaf in jax.tree_util.tree_leaves(trainer.state):
        leaf.delete()
    return start


def stats_gap(got: Dict[str, np.ndarray], want: Dict[str, np.ndarray],
              field: str):
    """The widest distance between the program's running statistics of
    ``field`` after the followed steps and the reference's, over the
    reference's norm; a running mean is held against the running standard
    deviation it normalises with (the batch mean of a convolution's
    output is what is left of a cancelling sum). Statistics the step does
    not thread stay at their start and read ~1. ``(gap, leaf)``."""
    norm = lambda a: float(np.linalg.norm(a.astype(np.float64)))  # noqa
    scale = lambda k: (np.sqrt(want[k[:-len("mean")] + "var"])  # noqa: E731
                       if k.endswith("/mean") else want[k])
    return max((norm(got[k] - v) / max(norm(scale(k)), 1e-30), k)
               for k, v in want.items() if k.startswith(field + "/"))


def followed_steps(reference, hyper: dict, tap: InpaintTap,
                   start: Dict[str, np.ndarray],
                   followed=None) -> Dict[str, float]:
    """Follow the tapped batches with the configuration's float32 step
    reference from the same start and compare: ``check.
    train_step_numbers`` (both losses, per net the worst leaf's first
    gradient and parameter change); each term of the losses at step one
    and its widest gap later (the penalty's own value among them: D's
    loss alone hardly sees ``gp_coef`` x it); the named leaves' first
    gradients as vectors (the norm of the difference over the
    reference's norm); the running statistics of G and of D after the
    last step. ``followed``: what the reference's ``follow`` already
    gave (a control holds another program, or another feed, against
    it)."""
    losses, grads, params, stats = (
        followed or reference.StepReference(hyper).follow(
            start, tap.batches))
    numbers = check.train_step_numbers(tap, losses, grads, params,
                                       hyper["beta1"], say)
    rel = lambda got, want: abs(got - want) / max(abs(want), 1e-30)  # noqa
    for name in TERMS:
        # a term the program does not log reads nan: its limit fails
        gaps = [rel(got.get(name, float("nan")), want[name])
                for got, want in zip(tap.losses, losses)]
        numbers[f"step1_{name}_rel_gap"] = gaps[0]
        numbers[f"later_{name}_rel_gap"] = max(gaps[1:])
    norm = lambda a: float(np.linalg.norm(a.astype(np.float64)))  # noqa
    for name, leaf in reference.named_leaves(start).items():
        got = tap.moments[leaf].astype(np.float32) / (1.0 - hyper["beta1"])
        numbers[f"first_grad_{name}_diff_over_norm"] = (
            norm(got - grads[leaf]) / max(norm(grads[leaf]), 1e-30))
    worst = {}
    for field in STATS:
        gap, leaf = stats_gap(tap.stats, stats, field)
        numbers[f"{field}_widest_gap"] = gap
        worst[field] = leaf
    say(inpaint_steps={"stats_widest_leaves": worst})
    return numbers
