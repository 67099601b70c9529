"""Driver ``train``: the trainer, through its own entry point, with the
loader running.

Builds the run exactly as ``python -m p2p_tpu.cli.train`` does (its
parser, ``config_from_flags``, ``enable_compilation_cache``, ``Trainer``),
then calls ``Trainer.train_epoch()`` itself: one warm-up epoch (compiles
the step, fills the decode memo), then whole epochs until ``--seconds``
have passed, between two fences on the state. No eval and no save inside
the window. ``train_img_per_s`` = steps completed x batch over the time
between the fences.

``correct``: the warm-up epoch's first steps are tapped on the Trainer's
own compiled step (``check.StepTap``); after the window the Trainer's
state is deleted and ``benchmark/reference/train_step.py`` follows the
same batches from the same start in float32; losses, first gradients and
parameter changes are compared (``check.train_step_numbers``). Before the
warm-up the generator path is compared too, for the int8 control.
"""

from __future__ import annotations

import math
import os
import shutil
import time
from typing import Any, Dict

import numpy as np

from benchmark import check, datagen, harness
from benchmark.harness import Cell, say

#: host annotations a gap of the device is named by, innermost first
GAP_PRIORITY = ("train_dispatch", "bench_fence", "bench_epoch")


def train_argv(cell: Cell, data_root: str, workdir: str) -> list:
    """The command line of this cell: the preset, where its data and
    outputs live, the batch the configuration file sizes, the run's seed.
    Nothing else the preset does not set."""
    cfg = cell.config
    argv = ["--preset", cfg["preset"], "--data_root", data_root,
            "--workdir", workdir, "--name", cell.config_name,
            "--dataset", "seeded", "--batch_size", str(cfg["batch_size"]),
            "--seed", str(cell.seed % (2 ** 31 - 1))]
    for flag, value in cfg.get("flags", {}).items():
        argv += [f"--{flag}", str(value)]
    return argv


def make_trainer(cell: Cell, marks: Dict[str, float], extra_argv=()):
    """The run as ``python -m p2p_tpu.cli.train`` builds it: the seeded
    dataset on disk, the parser, ``config_from_flags``, the compile cache,
    ``Trainer``. Returns the Trainer and its configuration."""
    from p2p_tpu.cli import train as cli_train
    from p2p_tpu.core.cache import enable_compilation_cache
    from p2p_tpu.train.loop import Trainer

    cfgf = cell.config
    data_root = os.path.join(cell.work, "data")
    datagen.write_paired_dataset(
        data_root, cell.seed, cfgf["dataset_pairs"], 1,
        (cfgf["image_height"], cfgf["image_width"]))
    marks["dataset_written"] = time.perf_counter() - cell.t_start
    workdir = os.path.join(cell.work, "train")
    shutil.rmtree(workdir, ignore_errors=True)   # a checkpoint = a resume
    os.makedirs(workdir)
    args = cli_train.build_parser().parse_args(
        train_argv(cell, data_root, workdir) + list(extra_argv))
    cfg = cli_train.config_from_flags(args)
    enable_compilation_cache(args.compilation_cache)
    trainer = Trainer(cfg, data_root=data_root, workdir=workdir)
    marks["trainer_built"] = time.perf_counter() - cell.t_start
    return trainer, cfg


def program_generator_path(cfg, dtype, int8: bool = False):
    """The system's generator path on one batch, from the very modules the
    train step builds (``train.state.build_models``), in train mode:
    compression net -> quantizer -> generator, or the generator alone.
    ``int8`` switches the generator's convs to the program's own int8
    path — the CONTROL, which ``correct`` must refuse."""
    import dataclasses

    import jax

    from p2p_tpu.ops.quantize import quantize
    from p2p_tpu.train.state import build_models
    from p2p_tpu.utils.images import ingest

    if int8:
        cfg = cfg.replace(model=dataclasses.replace(
            cfg.model, int8=True, int8_generator=True))
    g, _, c = build_models(cfg, dtype)
    use_c = cfg.model.use_compression_net

    def path(state, batch):
        raw = code = None
        if use_c:
            raw, _ = c.apply({"params": state.params_c,
                              "batch_stats": state.batch_stats_c},
                             ingest(batch["target"], dtype), True,
                             mutable=["batch_stats"])
            g_in = code = quantize(raw, cfg.model.quant_bits)
        else:
            g_in = ingest(batch["input"], dtype)
        pred, _ = g.apply({"params": state.params_g,
                           "batch_stats": state.batch_stats_g},
                          g_in, True, mutable=["batch_stats"])
        return pred, raw, code

    return jax.jit(path)


def generator_numbers(reference, params: Dict[str, np.ndarray],
                      batch: Dict[str, np.ndarray], pred, raw, code,
                      bits: int) -> Dict[str, float]:
    """What the system's generator path produced on ``batch`` against the
    plain reference on the same parameters, teacher-forced through the
    system's own quantizer code where there is one."""
    from benchmark.reference import nn

    image = batch[reference.BATCH_KEY]
    if code is None:
        ref = nn.on_cpu(lambda p, x: reference.generator_path(p, x, True)[0])
        want = ref(params, image)
        numbers: Dict[str, float] = {}
    else:
        code32 = np.asarray(code, np.float32)

        def both(p, x, k):
            forced, pre, _ = reference.generator_path(p, x, True, code=k)
            return forced, pre

        want, pre = nn.on_cpu(both)(params, image, code32)
        numbers = check.code_agreement(code32, pre, bits)
        gap = np.abs(np.asarray(raw, np.float32) - pre)
        numbers["prequant_mean_abs"] = float(gap.mean())
        numbers["prequant_max_abs"] = float(gap.max())
    errs = check.image_errors(np.asarray(pred, np.float32), want)
    numbers.update({f"generator_{k}": v for k, v in errs.items()})
    return numbers


def run(cell: Cell) -> str:
    harness.prepare_jax_env(cell)
    import jax

    device = harness.device_info(cell.entry["chips"], cell.require_tpu)
    reference = harness.load_by_path("reference", cell.config["reference"])
    meter = harness.CompileMeter()
    cache_before = harness.dir_bytes(cell.cache_dir)

    cfgf = cell.config
    hw = (cfgf["image_height"], cfgf["image_width"])
    marks = {"imports_device": time.perf_counter() - cell.t_start}
    trainer, cfg = make_trainer(cell, marks)
    batch_size = cfg.data.batch_size
    steps_per_epoch = trainer.steps_per_epoch
    run_obs: Dict[str, Any] = {"batch": batch_size,
                               "device_kind": device["kind"]}

    # ---- the output check, before the window; not counted as set-up ----
    t_check = time.perf_counter()
    first = np.stack(datagen.images(cell.seed, batch_size, hw))
    batch = {"target": first,
             "input": np.stack([datagen.compress_uint8(i, 3) for i in first])}
    params = check.flatten_state(trainer.state)
    pred, raw, code = jax.device_get(program_generator_path(
        cfg, train_dtype(cfg))(trainer.state, batch))
    limits = dict(reference.LIMITS)
    numbers = generator_numbers(reference, params, batch, pred, raw, code,
                                cfg.model.quant_bits)
    del params, pred, raw, code
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
    tap = check.StepTap(trainer.train_step, trainer.state, hyper["steps"])
    trainer.train_step = tap
    warm = trainer.train_epoch(seed=trainer.epoch)
    trainer.train_step = tap.inner
    check_s += tap.seconds
    marks["warm_epoch_done"] = time.perf_counter() - cell.t_start
    jax.config.update("jax_dump_ir_to", None)
    if cell.trace:
        run_obs["loader_img_per_s"] = loader_rate(trainer, 2.0)
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
        window_counts=window_counts)
    if cell.trace:
        from benchmark import trace_reduce

        try:
            run_obs["trace"] = trace_reduce.reduce_trace(
                trace_reduce.find_xplane(trace_dir), GAP_PRIORITY,
                window_from=("bench_epoch", "bench_fence"))
            say(trace=run_obs["trace"])
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
    numbers.update(followed_steps(reference, hyper, tap, trainer))
    say(reference_seconds=time.perf_counter() - t_ref)
    if not cell.require_tpu:
        # a rehearsal at toy sizes states its own limits
        limits.update({k: v for k, v in cfgf.get("limits", {}).items()
                       if k in limits})
    correct = check.verdict(numbers, limits, say)
    measured = {"train_img_per_s": img_per_s, "setup_s": setup_s}
    return harness.result_line(cell, correct, steps, 0, measured, run_obs,
                               device)


def train_dtype(cfg):
    """The compute type the Trainer gives its step (its own rule)."""
    import jax.numpy as jnp

    return jnp.bfloat16 if cfg.train.mixed_precision else None


def followed_steps(reference, hyper: dict, tap, trainer) -> Dict[str, float]:
    """Free the program's state, follow the tapped batches with the plain
    float32 reference from the same start, and compare."""
    import jax

    from benchmark.reference.train_step import TrainReference

    start = dict(tap.state0)
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            trainer.vgg_params or {})[0]:
        start[check.leaf_key("vgg", path)] = np.asarray(jax.device_get(leaf))
    for leaf in jax.tree_util.tree_leaves(trainer.state):
        leaf.delete()
    losses, grads, params = TrainReference(reference, hyper).follow(
        start, tap.batches)
    return check.train_step_numbers(tap, losses, grads, params,
                                    hyper["beta1"], say)


def loader_rate(trainer, seconds: float) -> float:
    """The cell's own loader iterated alone (memo filled, nothing sent to
    the device): images per second of host input."""
    from p2p_tpu.data.pipeline import make_loader

    n, t0, epoch = 0, time.perf_counter(), 0
    while time.perf_counter() - t0 < seconds:
        loader = make_loader(trainer.train_ds, trainer.local_bs, shuffle=True,
                             seed=epoch, num_workers=0)
        for b in loader:
            n += next(iter(b.values())).shape[0]
            if time.perf_counter() - t0 >= seconds:
                break
        epoch += 1
    return n / (time.perf_counter() - t0)
