"""``tools/control.py`` for a label-map cell (driver ``train_labels``), on
the chip at the cell's own size: for each seed what the SOUND program
gives against the configuration's plain reference, and what a CONTROL
gives, each passed through ``check.verdict`` under the limits of
``benchmark/reference/<config>.py``: the sound program has to come out
correct and every control not (exit 1 otherwise). The limits are set
between the two readings; the benchmark's own runs never run this.

    python benchmark/tools/control_labels.py --workload spade_cityscapes_512x256.train --kind train --seeds 6
    python benchmark/tools/control_labels.py --workload spade_cityscapes_512x256.train --kind steps --seeds 4

``train``: the generator path in train mode on the first seeded batch
from the state ``create_train_state`` makes of the seed; the control is
the same program with every generator kernel rounded to int8
(``drivers/train_labels.int8_kernels``), the nearest precision below the
bf16 the configuration computes in. ``steps``: the Trainer's own compiled
step through ``train_epoch`` for its first steps against the
configuration's ``StepReference`` (the epoch is broken off after them);
the control is a step that saw only HALF of its batch: the program's
first gradients against the reference's on the first batch with its
second half replaced by its first (the same shapes, so the reference's
compiled programs serve).
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


class _FirstStepsDone(Exception):
    pass


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
    params = check.flatten_state(
        state, ("params_g", "batch_stats_g", "spectral_g"))
    out = {}
    for label, control in (("sound", False), ("control", True)):
        pred = jax.device_get(driver.program_generator_path(
            cfg, dtype, control)(state, batch))
        out[label] = driver.generator_numbers(reference, params, batch, pred)
    return out


def steps_row(cell, driver, reference):
    import jax

    from benchmark import harness

    t0 = time.perf_counter()
    trainer, _ = driver.make_trainer(cell, {})
    hyper = cell.config["train_reference"]
    tap = driver.LabelTap(trainer.train_step, trainer.state, hyper["steps"])

    def tapped(state, batch):
        if len(tap.losses) >= tap.steps:
            raise _FirstStepsDone
        return tap(state, batch)

    trainer.train_step = tapped
    try:
        trainer.train_epoch(seed=trainer.epoch)
    except _FirstStepsDone:
        pass
    trainer.close()
    t1 = time.perf_counter()
    start = driver.reference_start(tap, trainer)
    sound = driver.followed_steps(reference, hyper, tap, start)
    t2 = time.perf_counter()
    control = half_batch_numbers(reference, hyper, tap, start)
    harness.say(seconds={"program": t1 - t0, "reference": t2 - t1,
                         "control": time.perf_counter() - t2},
                device_peak_gb=max(
                    (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                    for d in jax.local_devices()) / 1e9)
    return {"sound": sound, "control": control}


def half_batch_numbers(reference, hyper, tap, start):
    """The program's first gradients, as each optimizer got them, against
    the reference's on the first tapped batch with its second half
    replaced by its first: what a step that dropped half of its batch
    would read."""
    import numpy as np

    from benchmark import check

    first = tap.batches[0]
    half = len(next(iter(first.values()))) // 2
    halved = {k: np.concatenate([v[:half], v[:half]]) for k, v in
              first.items()}
    _, grads, _, _ = reference.StepReference(hyper).follow(start, [halved])
    dead = reference.zero_gradient_leaves(start)
    got = {k: v.astype(np.float32) / (1.0 - hyper["beta1"])
           for k, v in tap.moments.items()}
    return {f"first_grad_{net}_worst_leaf_gap": gap
            for net, (gap, _) in check.worst_leaf_gap(
                got, {k: v for k, v in grads.items() if k not in dead}
            ).items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--kind", choices=("train", "steps"), required=True)
    ap.add_argument("--seeds", type=int, default=4)
    ap.add_argument("--first_seed", type=int, default=2147480000)
    ap.add_argument("--bench_file", default=None)
    ap.add_argument("--allow_cpu", action="store_true")
    args = ap.parse_args(argv)

    from benchmark import check, harness

    rows, as_expected = [], True
    for k in range(args.seeds):
        seed = args.first_seed + 7919 * k
        cell = harness.load_cell(args.workload, seed, 0.0, False,
                                 time.perf_counter(), args.bench_file,
                                 require_tpu=not args.allow_cpu)
        if k == 0:
            harness.prepare_jax_env(cell)
            harness.say(device=harness.device_info(1, not args.allow_cpu))
        driver = harness.load_by_path("drivers", cell.workload["driver"])
        reference = harness.load_by_path("reference",
                                         cell.config["reference"])
        groups = (train_row if args.kind == "train" else steps_row)(
            cell, driver, reference)
        # each group under the limits of the numbers it holds, as the
        # driver's own verdict holds them
        limits = dict(reference.LIMITS)
        if args.allow_cpu:
            limits.update({k: v for k, v in cell.config.get(
                "limits", {}).items() if k in limits})
        row = {"seed": seed}
        for label, numbers in groups.items():
            correct = check.verdict(
                numbers, {k: v for k, v in limits.items() if k in numbers},
                harness.say)
            row[f"{label}.correct"] = correct
            as_expected = as_expected and correct == (label == "sound")
            row.update({f"{label}.{k}": v for k, v in numbers.items()})
        rows.append(row)
        harness.say(**row)
    keys = sorted({k for r in rows for k in r if k != "seed"})
    harness.say(summary={k: {"min": min(r[k] for r in rows),
                             "max": max(r[k] for r in rows)} for k in keys},
                seeds=len(rows), sound_correct_and_controls_refused=as_expected)
    return 0 if as_expected else 1


if __name__ == "__main__":
    sys.exit(main())
