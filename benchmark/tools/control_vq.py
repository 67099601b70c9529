"""``tools/control_labels.py`` for the quantized-autoencoder cell (driver
``train_vq``), on the chip at the cell's own size: for each seed what the
SOUND program gives against the configuration's plain reference, and what
each CONTROL gives, each passed through ``check.verdict`` under the
limits of ``benchmark/reference/<config>.py``: the sound program has to
come out correct and every control not (exit 1 otherwise). The limits
are set between the readings; the benchmark's own runs never run this.

    python benchmark/tools/control_vq.py --workload vqgan_imagenet_f16_16384.train --kind train --seeds 3
    python benchmark/tools/control_vq.py --workload vqgan_imagenet_f16_16384.train --kind steps --seeds 1

``train``: the autoencoder in train mode on the first seeded batch from
the state ``create_train_state`` makes of the seed, with two controls in
the nearest precision below the one the configuration states for that
part: ``control_int8`` rounds every kernel of the autoencoder to 8 bits
(below its bf16 compute; ``generator_mean_abs_levels`` must refuse it),
``control_bf16_distances`` runs the nearest-code search in bfloat16
(below its float32; ``distance_rel_gap`` must refuse it). ``steps``: the
Trainer's own compiled step through ``train_epoch`` for its first steps
against the configuration's ``StepReference``; the control is a step
that saw only HALF of its batch (``half_batch_numbers``).
The loop over seeds, the verdicts and the summary are
``control_labels.main``'s.
"""

import importlib.util
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

CONTROLS = ("int8", "bf16_distances")


def _labels_tool():
    spec = importlib.util.spec_from_file_location(
        "control_labels", os.path.join(os.path.dirname(
            os.path.abspath(__file__)), "control_labels.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    return tool


def train_row(cell, driver, reference):
    import jax

    from p2p_tpu.cli import train as cli_train
    from p2p_tpu.train.state import create_train_state

    from benchmark import check
    from benchmark.drivers import train as base

    cfgf = cell.config
    cfg = cli_train.config_from_flags(cli_train.build_parser().parse_args(
        base.train_argv(cell, "unused", "unused")))
    bs = cfgf["batch_size"]
    dtype = base.train_dtype(cfg)
    batch = driver.first_batch(cell, bs)
    state = create_train_state(cfg, jax.random.key(cfg.train.seed), batch,
                               max(1, cfgf["dataset_pairs"] // bs), dtype)
    params = check.flatten_state(state, ("params_g",))
    out = {}
    for control in ("",) + CONTROLS:
        got = jax.device_get(driver.program_generator_path(
            cfg, dtype, control)(state, batch))
        out[f"control_{control}" if control else "sound"] = (
            driver.generator_numbers(reference, params, batch, *got))
    return out


def steps_row(cell, driver, reference, tool):
    from benchmark import harness
    from benchmark.drivers import train as base

    t0 = time.perf_counter()
    hyper = cell.config["train_reference"]
    trainer, cfg = driver.make_trainer(cell, {})
    tap = driver.VqTap(
        trainer.train_step, trainer.state, hyper["steps"],
        driver.program_generator_path(cfg, base.train_dtype(cfg)))

    def tapped(state, batch):
        if len(tap.losses) >= tap.steps:
            raise tool._FirstStepsDone
        return tap(state, batch)

    trainer.train_step = tapped
    try:
        trainer.train_epoch(seed=trainer.epoch)
    except tool._FirstStepsDone:
        pass
    trainer.close()
    t1 = time.perf_counter()
    start = driver.reference_start(tap, trainer)
    sound = driver.followed_steps(reference, hyper, tap, start)
    t2 = time.perf_counter()
    control = half_batch_numbers(driver, reference, hyper, tap, start)
    harness.say(seconds={"program": t1 - t0, "reference": t2 - t1,
                         "control": time.perf_counter() - t2})
    return {"sound": sound, "control": control}


def half_batch_numbers(driver, reference, hyper, tap, start):
    """``control_labels.half_batch_numbers`` (the program's first
    gradients against the reference's on the first tapped batch with its
    second half replaced by its first) and, beside those worst-leaf gaps
    of the NORMS, which a D gradient of the sound size and another
    direction passes, the difference of D's first gradient as a vector
    and the cosines of G's, as the driver holds them, and the step-one
    losses' gaps."""
    import numpy as np

    from benchmark import check

    first = tap.batches[0]
    half = len(next(iter(first.values()))) // 2
    halved = {k: np.concatenate([v[:half], v[:half]])
              for k, v in first.items()}
    losses, grads, _, _ = reference.StepReference(hyper).follow(
        start, [halved], None if tap.first_indices is None else
        np.concatenate([tap.first_indices[:half], tap.first_indices[:half]]))
    dead = reference.zero_gradient_leaves(start)
    got = {k: v.astype(np.float32) / (1.0 - hyper["beta1"])
           for k, v in tap.moments.items()}
    numbers = {f"first_grad_{net}_worst_leaf_gap": gap
               for net, (gap, _) in check.worst_leaf_gap(
                   got, {k: v for k, v in grads.items() if k not in dead}
               ).items()}
    numbers["first_grad_d_diff_over_norm"] = driver.first_grad_d_difference(
        tap, grads, hyper["beta1"])
    numbers.update(driver.first_grad_g_direction(tap, grads))
    for name, key in (("loss_d", "loss_d"), ("g_lpips", "g_lpips"),
                      ("g_codebook", "g_codebook")):
        numbers[f"step1_{name}_rel_gap"] = (
            abs(tap.losses[0][key] - float(losses[0][key]))
            / max(abs(float(losses[0][key])), 1e-30))
    return numbers


def main(argv=None) -> int:
    tool = _labels_tool()
    tool.train_row = train_row
    tool.steps_row = lambda *a: steps_row(*a, tool)
    return tool.main(argv)


if __name__ == "__main__":
    sys.exit(main())
