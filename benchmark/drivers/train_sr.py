"""Driver ``train_sr``: driver ``train``'s run for a super-resolution
configuration (an input of the target's extent over ``scale``), whose
whole-step reference is its own.

The window is ``drivers/train.py``'s, letter for letter: one warm-up
epoch through ``Trainer.train_epoch()``, then whole epochs between two
fences on the state for ``--seconds``; ``train_img_per_s`` = steps
completed x batch (HQ images trained) over the time between the fences;
``setup_s`` net of the check; the same three window conditions (no
compile, no uncounted step, no skipped epoch). What differs:

- the seeded dataset is ``benchmark/datagen_sr.py``'s: ``a/`` the HQ
  images, ``b/`` their area-downsampled LQ copies;
- both comparisons start from the CHECK's state, the program's seeded
  start OFF its init (:func:`widened`: every qkv kernel, every bias table
  and the last convolution's kernel of G and of its EMA multiplied by the
  stated factors of ``WIDEN``, so that attention logits spread over units
  and the x4 image over ~20 levels; at the init itself the logits lie
  within a tenth of zero, the image spreads one level and nothing of the
  attention shows). The Trainer's own state, the one the window times, is
  the published init and is never touched;
- the generator check before warm-up runs the generator from that state
  with stochastic depth off and holds its x4 output, in 8-bit levels,
  against the configuration's reference on the host CPU. Three CONTROLS
  (``program_generator_path(control=...)``, read by
  ``benchmark/tools/control_sr.py``), each in the nearest precision below
  the one the configuration states: ``int8`` rounds every kernel of the
  generator to 8-bit integers, one scale a tensor (below its bf16
  compute); ``bf16_softmax`` keeps every intermediate of the softmax in
  bfloat16 and ``bf16_norm`` those of LayerNorm (below their float32). The
  image is compared twice, as the step computes it and from the same
  modules at float32 (``generator_f32_*``): in a program whose products
  read bf16 operands a bf16 softmax is lost in that rounding (1.00x the
  sound error, a bf16 LayerNorm 1.06x), in the float32 one it is a
  thousand times float32's own;
- a tap on the warm-up epoch keeps the first batches as the loader fed
  them (``FeedTap``); after the window the Trainer's own compiled step
  takes those batches from the check's state (``followed_tap``: no
  compile, the call is the window's), and the configuration's own
  ``StepReference`` follows with the stochastic-depth masks the program
  drew (``keep_masks``: the same key, the same ``jax.random.uniform``
  call, through the module's own method); beside what
  ``check.train_step_numbers`` compares, each term of G's loss, three
  named leaves' first gradients as vectors (the bias table, a qkv kernel,
  D's ``x3`` kernel), D's spectral vectors and the EMA of G after the last
  step;
- a traced run joins the trace with the compiled step's text by the
  scopes ``swin_attn``, ``swin_window``, ``swin_mlp``, ``swin_ln`` and by
  the step's own (``D_fake`` / ``D_real``) before the trace is removed and
  keeps both in ``run["sr_scopes"]`` / ``run["sr_nets"]`` for the five
  readers ``model.swin_*`` and ``model.d_unet_ms_per_step``; it also counts
  the compiled step's instructions under ``swin_window`` by opcode.
"""

from __future__ import annotations

import math
import os
import re
import shutil
import time
from typing import Any, Dict, List

import numpy as np

from benchmark import check, datagen_sr, harness, scope_time, trace_reduce
from benchmark.drivers import train as base
from benchmark.drivers.train_labels import compiled_step_text, int8_kernels
from benchmark.harness import Cell, say

GAP_PRIORITY = base.GAP_PRIORITY
#: the state the step reference starts from, beside ``check.TRAIN_FIELDS``
EXTRA_FIELDS = ("ema_g",)
#: the mechanisms the readers read
JOIN = ("swin_attn", "swin_window", "swin_mlp", "swin_ln")
#: ``program_generator_path`` variants that ``correct`` must refuse
CONTROLS = ("int8", "bf16_softmax", "bf16_norm")
#: the check's state: leaves of G (and of its EMA) whose path ends so, times
#: the factor. qkv: q and k of std 0.27 x 6, logits of std ~2.6; the bias
#: table: std 0.02 x 50; the last convolution: an image of ~20 levels
WIDEN = (("attn/qkv/kernel", 6.0),
         ("attn/relative_position_bias_table", 50.0),
         ("conv_last/Conv_0/kernel", 20.0))


def make_trainer(cell: Cell, marks: Dict[str, float], extra_argv=()):
    """``drivers/train.make_trainer`` on LQ / HQ pairs."""
    from p2p_tpu.cli import train as cli_train
    from p2p_tpu.core.cache import enable_compilation_cache
    from p2p_tpu.train.loop import Trainer

    cfgf = cell.config
    data_root = os.path.join(cell.work, "data")
    datagen_sr.write_sr_dataset(
        data_root, cell.seed, cfgf["dataset_pairs"], 1,
        (cfgf["image_height"], cfgf["image_width"]), cfgf["scale"])
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
    lqs, hqs = datagen_sr.pairs(
        cell.seed, batch_size, (cfgf["image_height"], cfgf["image_width"]),
        cfgf["scale"])
    return {"input": np.stack(lqs), "target": np.stack(hqs)}


def widened(state):
    """A host copy of ``state`` with the leaves ``WIDEN`` names drawn
    wider: still the seeded draw, times a stated factor."""
    import jax

    def wider(path, leaf):
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        for suffix, factor in WIDEN:
            if name.endswith(suffix):
                return leaf * np.float32(factor)
        return leaf

    host = jax.device_get(state)
    return host.replace(**{
        field: jax.tree_util.tree_map_with_path(wider, getattr(host, field))
        for field in ("params_g", "ema_g")})


def program_generator_path(cfg, dtype, control: str = ""):
    """The system's generator on one batch, from the module the train step
    builds, with stochastic depth off: the x4 image. At ``dtype`` float32
    every product runs at HIGHEST precision (this chip's default rounds a
    float32 product's operands to bfloat16). ``control`` (what ``correct``
    must refuse): ``"int8"`` rounds the generator's kernels to 8-bit
    integers, ``"bf16_softmax"`` keeps the softmax's intermediates in
    bfloat16, ``"bf16_norm"`` LayerNorm's."""
    import contextlib

    import jax
    import jax.numpy as jnp

    from p2p_tpu.train.state import build_models
    from p2p_tpu.utils.images import ingest_input

    g, _, _ = build_models(cfg, dtype)
    if control == "bf16_softmax":
        g = g.clone(softmax_dtype=jnp.bfloat16)
    elif control == "bf16_norm":
        g = g.clone(norm_dtype=jnp.bfloat16)
    elif control not in ("", "int8"):
        raise ValueError(f"unknown control {control!r}")

    def path(state, batch):
        params = state.params_g
        if control == "int8":
            params = int8_kernels(params)
        with (jax.default_matmul_precision("highest")
              if dtype == jnp.float32 else contextlib.nullcontext()):
            return g.apply({"params": params},
                           ingest_input(batch["input"], cfg.model, dtype),
                           False)

    return jax.jit(path)


def reference_image(reference, state, batch) -> np.ndarray:
    """The reference's x4 image from ``state``'s generator, on the host."""
    from benchmark.reference import nn

    return nn.on_cpu(lambda p, x: reference.generator_path(p, x, False)[0])(
        check.flatten_state(state, ("params_g",)), batch[reference.BATCH_KEY])


def generator_numbers(want: np.ndarray, cfg, dtype, state, batch,
                      control: str = "") -> Dict[str, float]:
    """The program's x4 output from ``state`` against the reference's
    (``want``, :func:`reference_image`), in 8-bit levels: as the step
    computes it
    (``generator_*``: its bf16 rounding through 36 layers, which a bf16
    softmax does not exceed), and from the same modules at float32
    (``generator_f32_*``: what the configuration states as float32, the
    softmax, the logits and LayerNorm's moments, and the arithmetic itself,
    held to float32's own error). And how far the reference's own output
    spreads (a constant image would pass any comparison)."""
    import jax
    import jax.numpy as jnp

    numbers = {}
    for prefix, dt in (("generator", dtype), ("generator_f32", jnp.float32)):
        pred = jax.device_get(program_generator_path(cfg, dt, control)(
            state, batch))
        numbers.update({f"{prefix}_{k}": v for k, v in check.image_errors(
            np.asarray(pred, np.float32), want).items()})
    numbers["generator_spread_levels"] = check.LEVEL * float(
        np.mean(np.abs(want - want.mean(axis=(0, 1, 2)))))
    return numbers


def keep_masks(cfg, dtype, noise_seed: int, step: int,
               batch_size: int) -> np.ndarray:
    """The stochastic-depth masks the program's train step number ``step``
    (0-based, the state's counter before it) draws: the step's own rng
    (``train/step.py``: the key of the seed the state carries,
    ``TrainState.noise_seed``, folded with the step) handed to the
    generator module's own ``keep_masks``."""
    import jax
    import jax.numpy as jnp

    from p2p_tpu.train.state import build_models

    g, _, _ = build_models(cfg, dtype)
    rng = jax.random.fold_in(
        jax.random.key(jnp.asarray(noise_seed, jnp.uint32)), step)
    return np.asarray(g.apply({}, batch_size, method="keep_masks",
                              rngs={"dropout": rng}))


class FeedTap:
    """Sits on the Trainer's step through the warm-up epoch and keeps the
    first ``steps`` batches as they were fed; ``seconds`` is the host time
    the copies took (not set-up's)."""

    def __init__(self, step, steps: int):
        self.inner, self.steps = step, steps
        self.batches: List[Dict[str, np.ndarray]] = []
        self.seconds = 0.0

    def __call__(self, state, batch):
        if len(self.batches) < self.steps:
            import jax

            t0 = time.perf_counter()
            self.batches.append({k: np.asarray(v) for k, v in
                                 jax.device_get(batch).items()})
            self.seconds += time.perf_counter() - t0
        return self.inner(state, batch)


class SrTap(check.StepTap):
    """``check.StepTap`` that starts from G's EMA too and keeps it and D's
    spectral vectors after the last tapped step."""

    def __init__(self, step, state, steps: int):
        super().__init__(step, state, steps)
        t0 = self._clock()
        self.state0.update(check.flatten_state(state, EXTRA_FIELDS))
        self.first_step = int(state.step)
        self.noise_seed = int(state.noise_seed)
        self.after: Dict[str, np.ndarray] = {}
        self.seconds += self._clock() - t0

    def __call__(self, state, batch):
        last = len(self.losses) == self.steps - 1
        state, metrics = super().__call__(state, batch)
        if last:
            t0 = self._clock()
            self.after = check.flatten_state(
                state, ("spectral_d",) + EXTRA_FIELDS)
            self.seconds += self._clock() - t0
        return state, metrics


def followed_tap(trainer, start_host, shardings,
                 batches: List[Dict[str, np.ndarray]]) -> SrTap:
    """The Trainer's own compiled step (``trainer.train_step``, whatever
    wraps it) through ``batches`` from ``start_host``, placed as the loop
    places its state and its feed, under a tap."""
    import jax

    state = jax.device_put(start_host, shardings)
    tap = SrTap(trainer.train_step, state, len(batches))
    for fed in batches:
        state, _ = tap(state, {k: jax.device_put(v, trainer.batch_sharding)
                               for k, v in fed.items()})
    return tap


def window_ops(hlo_text: str) -> Dict[str, int]:
    """The compiled step's instructions whose first scope among ``JOIN``
    is ``swin_window`` (roll, partition, reverse: layout only), by opcode,
    fused computations' bodies left out: what the window split costs in
    ops of its own."""
    counts: Dict[str, int] = {}
    opcode = re.compile(r"=\s*[^=]*?\s([a-z][\w\-]*)\(")
    in_entry = False
    for line in hlo_text.splitlines():
        if line.startswith("ENTRY"):
            in_entry = True
            continue
        if in_entry and line.startswith("}"):
            break
        if not in_entry:
            continue
        op = scope_time._OP_NAME.search(line)
        if not op or scope_time.first_scope(op.group(1), JOIN) != JOIN[1]:
            continue
        m = opcode.search(line)
        if m:
            counts[m.group(1)] = counts.get(m.group(1), 0) + 1
    return counts


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
                               "device_kind": device["kind"]}

    # ---- the output check, before the window; not counted as set-up ----
    t_check = time.perf_counter()
    batch = first_batch(cell, batch_size)
    shardings = jax.tree_util.tree_map(lambda x: x.sharding, trainer.state)
    start_host = widened(trainer.state)
    limits = dict(reference.LIMITS)
    numbers = generator_numbers(
        reference_image(reference, start_host, batch), cfg, dtype,
        start_host, batch)
    check_s = time.perf_counter() - t_check

    # ---- warm-up: one epoch compiles the step and fills the memo -------
    if cell.trace and cell.workload.get("dump_lowered_step"):
        ir_dir = os.path.join(cell.work, "ir")
        shutil.rmtree(ir_dir, ignore_errors=True)
        os.makedirs(ir_dir)
        jax.config.update("jax_dump_ir_to", ir_dir)
        run_obs["ir_dir"] = ir_dir
    marks["check_done"] = time.perf_counter() - cell.t_start
    # the first batches of the very feed the window times, kept for the
    # comparison with the plain reference after the window
    hyper = cfgf["train_reference"]
    feed = FeedTap(trainer.train_step, hyper["steps"])
    trainer.train_step = feed
    warm = trainer.train_epoch(seed=trainer.epoch)
    trainer.train_step = feed.inner
    check_s += feed.seconds
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
                if k.startswith(("swinir_", "generator_gflop"))})
    if cell.trace:
        try:
            xplane = trace_reduce.find_xplane(trace_dir)
            run_obs["trace"] = trace_reduce.reduce_trace(
                xplane, GAP_PRIORITY,
                window_from=("bench_epoch", "bench_fence"))
            say(trace=run_obs["trace"])
            # the step's device time by mechanism, before the trace goes
            text = compiled_step_text(trainer)
            run_obs["sr_scopes"] = scope_time.by_scope(xplane, text,
                                                       scopes=JOIN)
            run_obs["sr_nets"] = scope_time.by_scope(xplane, text)
            say(by_scope={"mechanisms": run_obs["sr_scopes"],
                          "nets": run_obs["sr_nets"]["scope_s"]},
                swin_window_ops=window_ops(text))
        except ValueError:
            # the CPU rehearsal has no device plane; on the chip a trace in
            # which no device op ran is a failed run
            if cell.require_tpu:
                raise
        shutil.rmtree(trace_dir, ignore_errors=True)
    device["memory_peak_bytes"] = run_obs["peak_bytes"]
    # the very step the window timed, from the check's state
    before = meter.counts()
    tap = followed_tap(trainer, start_host, shardings, feed.batches)
    numbers["followed_steps_xla_compiles"] = float(
        harness.delta(meter.counts(), before)["n_compiles"])
    limits["followed_steps_xla_compiles"] = 0.0
    keeps = [keep_masks(cfg, dtype, tap.noise_seed, tap.first_step + i,
                        batch_size) for i in range(hyper["steps"])]
    trainer.close()
    meter.close()

    # ---- the whole step against the plain reference, the chip freed ----
    t_ref = time.perf_counter()
    numbers.update(followed_steps(reference, hyper, tap,
                                  reference_start(tap, trainer), keeps))
    say(reference_seconds=time.perf_counter() - t_ref)
    if not cell.require_tpu:
        # a rehearsal at toy sizes states its own limits
        limits.update({k: v for k, v in cfgf.get("limits", {}).items()
                       if k in limits})
    correct = check.verdict(numbers, limits, say)
    measured = {"train_img_per_s": img_per_s, "setup_s": setup_s}
    return harness.result_line(cell, correct, steps, 0, measured, run_obs,
                               device)


def ema_change_gap(after: Dict[str, np.ndarray], want: Dict[str, np.ndarray],
                   start: Dict[str, np.ndarray]) -> float:
    """The gap between the norm of the EMA's change over the followed steps
    as the program made it and as the reference did, all leaves as ONE
    vector, over the reference's norm. (Leaf by leaf the change of three
    steps at decay 0.999 is a few ulps of a LayerNorm scale; an EMA left
    alone reads 1, one with another decay many times that.)"""
    norm = lambda tree: math.sqrt(sum(  # noqa: E731
        float(np.sum(np.square((tree[k] - start[k]).astype(np.float64))))
        for k in want))
    return abs(norm(after) - norm(want)) / max(norm(want), 1e-30)


def reference_start(tap: SrTap, trainer) -> Dict[str, np.ndarray]:
    """The flat state the step reference starts from: what the tap kept of
    the state before its first step, and the frozen VGG19 tree. Frees the
    program's state."""
    import jax

    start = dict(tap.state0)
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            trainer.vgg_params or {})[0]:
        start[check.leaf_key("vgg", path)] = np.asarray(jax.device_get(leaf))
    for leaf in jax.tree_util.tree_leaves(trainer.state):
        leaf.delete()
    return start


def followed_steps(reference, hyper: dict, tap: SrTap,
                   start: Dict[str, np.ndarray], keeps: List[np.ndarray],
                   followed=None) -> Dict[str, float]:
    """Follow the tapped batches with the configuration's float32 step
    reference from the same start and the program's own masks, and
    compare: ``check.train_step_numbers`` (both losses, per net the worst
    leaf's first gradient and parameter change); each term of G's loss at
    step one and its widest gap later; the named leaves' first gradients
    as vectors (the norm of the difference over the reference's norm); D's
    spectral vectors after the last step (the widest distance, the vectors
    being of unit length); the EMA's change (:func:`ema_change_gap`).
    ``followed``: what the reference's ``follow`` already gave (a control
    holds another program, or another feed, against it)."""
    losses, grads, params, spectral, ema = (
        followed or reference.StepReference(hyper).follow(
            start, tap.batches, keeps))
    numbers = check.train_step_numbers(tap, losses, grads, params,
                                       hyper["beta1"], say)
    rel = lambda got, want: abs(got - want) / max(abs(want), 1e-30)  # noqa
    for name in ("g_l1", "g_vgg", "g_gan"):
        gaps = [rel(got[name], want[name])
                for got, want in zip(tap.losses, losses)]
        numbers[f"step1_{name}_rel_gap"] = gaps[0]
        numbers[f"later_{name}_rel_gap"] = max(gaps[1:])
    norm = lambda a: float(np.linalg.norm(a.astype(np.float64)))  # noqa
    for name, leaf in reference.NAMED_LEAVES.items():
        got = tap.moments[leaf].astype(np.float32) / (1.0 - hyper["beta1"])
        numbers[f"first_grad_{name}_diff_over_norm"] = (
            norm(got - grads[leaf]) / max(norm(grads[leaf]), 1e-30))
    numbers["spectral_d_widest_gap"] = max(
        norm(tap.after[k] - want) for k, want in spectral.items())
    numbers["ema_g_change_gap"] = ema_change_gap(tap.after, ema, tap.state0)
    say(sr_steps={"keep_dropped": [int(k.size - k.sum()) for k in keeps]})
    return numbers
