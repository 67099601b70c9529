"""The control of ``correct``, on the chip, at the cell's own size: for
each seed, what the SOUND program gives against the plain reference, and
what the CONTROL gives — the program's own int8 path (``ModelConfig.int8``
with ``int8_generator``) in the sound path's place. The limits in
``benchmark/reference/<config>.py`` are set from the two readings; the
benchmark's own runs never run this.

    python benchmark/tools/control.py --config reference_256 --kind train --seeds 12
    python benchmark/tools/control.py --config reference_256 --kind steps --seeds 12 --control_seeds 3

``train``: the generator path in train mode on the first seeded batch, at
the configuration's batch size, from the state ``create_train_state``
makes of the seed. ``steps``: the Trainer's
own compiled step through ``train_epoch`` for its first three steps
against ``benchmark/reference/train_step.py`` (the epoch is broken off
after them); the control is the Trainer with ``--int8 --int8_generator``,
on the first ``--control_seeds`` seeds.
"""

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--kind", choices=("train", "steps"),
                    required=True)
    ap.add_argument("--control_seeds", type=int, default=0)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first_seed", type=int, default=2147480000)
    ap.add_argument("--bench_file", default=None)
    ap.add_argument("--allow_cpu", action="store_true")
    args = ap.parse_args(argv)

    import numpy as np

    from benchmark import check, datagen, harness

    cell_name = f"{args.config}.train"
    rows = []
    for k in range(args.seeds):
        seed = args.first_seed + 7919 * k
        cell = harness.load_cell(cell_name, seed, 0.0, False,
                                 time.perf_counter(), args.bench_file,
                                 require_tpu=not args.allow_cpu)
        if k == 0:
            harness.prepare_jax_env(cell)
            import jax
            import jax.numpy as jnp

            device = harness.device_info(1, not args.allow_cpu)
            print(json.dumps({"device": device}), flush=True)
        reference = harness.load_by_path("reference",
                                         cell.config["reference"])
        if args.kind == "steps":
            row = {f"sound.{k}": v
                   for k, v in steps_numbers(cell, reference).items()}
            if k < args.control_seeds:
                try:
                    ctrl = steps_numbers(cell, reference,
                                         ("--int8", "--int8_generator"))
                except Exception as e:  # noqa: BLE001 - a control that
                    # crashes has failed, and sets no upper end
                    ctrl = {}
                    row["control_error"] = repr(e)[:300]
                row.update({f"control.{k}": v for k, v in ctrl.items()})
        else:
            row = train_row(cell, reference)
        row["seed"] = seed
        rows.append(row)
        print(json.dumps(row), flush=True)
    keys = sorted({k for r in rows for k in r if k.startswith(
        ("sound.", "control."))})
    summary = {k: {"min": min(r[k] for r in rows if k in r),
                   "max": max(r[k] for r in rows if k in r)} for k in keys}
    print(json.dumps({"summary": summary, "seeds": len(rows)}), flush=True)
    return 0


def _program_cfg(cell, batch_size):
    from p2p_tpu.cli import train as cli_train

    from benchmark.drivers import train as train_driver

    argv = train_driver.train_argv(cell, "unused", "unused")
    cfg = cli_train.config_from_flags(
        cli_train.build_parser().parse_args(argv))
    return cfg.replace(data=dataclasses.replace(cfg.data,
                                                batch_size=batch_size))


def train_row(cell, reference):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from p2p_tpu.train.state import create_train_state

    from benchmark import check, datagen
    from benchmark.drivers import train as train_driver

    cfgf = cell.config
    bs = cfgf["batch_size"]
    cfg = _program_cfg(cell, bs)
    hw = (cfgf["image_height"], cfgf["image_width"])
    first = np.stack(datagen.images(cell.seed, bs, hw))
    batch = {"target": first,
             "input": np.stack([datagen.compress_uint8(i, 3) for i in first])}
    dtype = jnp.bfloat16 if cfg.train.mixed_precision else None
    state = create_train_state(cfg, jax.random.key(cfg.train.seed), batch,
                               max(1, cfgf["dataset_pairs"] // bs), dtype)
    params = check.flatten_state(state)
    row = {}
    for label, int8 in (("sound", False), ("control", True)):
        pred, raw, code = jax.device_get(
            train_driver.program_generator_path(cfg, dtype, int8)(
                state, batch))
        nums = train_driver.generator_numbers(
            reference, params, batch, pred, raw, code, cfg.model.quant_bits)
        row.update({f"{label}.{k}": v for k, v in nums.items()})
    return row


class _FirstStepsDone(Exception):
    pass


def steps_numbers(cell, reference, extra_argv=()):
    """The Trainer's first steps, through its own ``train_epoch``, against
    the plain reference of the whole step."""
    from benchmark import check
    from benchmark.drivers import train as train_driver

    t0 = time.perf_counter()
    trainer, _ = train_driver.make_trainer(cell, {}, extra_argv)
    hyper = cell.config["train_reference"]
    tap = check.StepTap(trainer.train_step, trainer.state, hyper["steps"])

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
    numbers = train_driver.followed_steps(reference, hyper, tap, trainer)
    import jax

    numbers["device_peak_gb"] = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in jax.local_devices()) / 1e9
    numbers["program_s"] = t1 - t0
    numbers["reference_s"] = time.perf_counter() - t1
    return numbers


if __name__ == "__main__":
    sys.exit(main())
