"""``tools/control_labels.py`` for the inpainting cell (driver
``train_inpaint``), on the chip at the cell's own size: for each seed what
the SOUND program gives against the configuration's plain reference, and
what each CONTROL gives, each passed through ``check.verdict`` under the
limits of ``benchmark/reference/<config>.py``: every ``sound*`` row has to
come out correct and every control not (exit 1 otherwise). The limits
are set between the readings; the benchmark's own runs never run this.

    python benchmark/tools/control_inpaint.py --workload big_lama_places256.train --kind train --seeds 4
    python benchmark/tools/control_inpaint.py --workload big_lama_places256.train --kind steps --seeds 1
    python benchmark/tools/control_inpaint.py --workload big_lama_places256.train --kind chip_reference --seeds 1

``train``: the generator in train mode on the loader's first items, as
the step computes it and from the same modules at float32, with two
controls, each in the nearest precision below the one the configuration
states: ``control_lowp_kernels`` rounds every kernel of the generator to
3 mantissa bits (below its bf16 compute), ``control_bf16_fft`` is a
reference that rounds the operands of both transforms of every Fourier
unit to bfloat16 (below their float32), against which the sound program
reads what a program with that fault reads against the sound reference
(seen in the float32 program alone). ``steps``: the Trainer's own
compiled step on the first batches its loader feeds, against the configuration's ``StepReference``;
the controls are references that follow ANOTHER step, so that the sound
program reads against each what a program with that fault would read
against the sound reference: ``control_no_penalty`` (the R1 penalty left
out of D's loss), ``control_half_batch`` (a step that saw only half of
every batch: the second half replaced by the first) and
``control_mask_ignored_in_l1`` (the L1 over every pixel). And one
READING that has to come out correct, ``sound_float32_program``: the
program's step built at float32 with every product at HIGHEST (the same
modules, state and batches), followed against the same reference: what
is left of a gap there is not bfloat16's. Both programs' rows also print
G's FIRST Fourier unit's kernel's first gradient as a vector
(``first_grad_first_fu_kernel_diff_over_norm``; the cell judges the last
unit's).
``chip_reference``: the followed steps' float32 reference as the cell
runs it (on the accelerator, ``Precision.HIGHEST``) against the same
program on the HOST CPU, one step from the same start on the same batch:
every loss's relative gap and, per net, the widest leaf's distance
between the two first gradients over the host's norm; exit 1 where any
passes ``CHIP_REFERENCE_LIMIT`` (this chip has returned wrong float32
gradients before: ROADMAP R2).
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

#: the widest gap between the reference on the accelerator and on the host
#: that still reads as one program in two float32 arithmetics: the sound
#: readings are 0.024 - 0.026 on G's widest leaf (an EARLY leaf, whose
#: gradient has passed 36 FFCs' BatchNorms and ReLU masks; 0.0014 on D's,
#: 5e-5 in every loss), the wrong float32 gradients this chip has returned
#: (ROADMAP R2) read 0.1 - 1.0 (PERF.md section 2)
CHIP_REFERENCE_LIMIT = 5e-2
#: numbers of ``chip_reference`` that are printed and not judged
PRINTED = "chip_reference_printed_"


class _FirstStepsDone(Exception):
    pass


def train_row(cell, driver, reference):
    from benchmark.drivers import train as base

    trainer, cfg = driver.make_trainer(cell, {})
    dtype = base.train_dtype(cfg)
    batch = driver.first_batch(trainer, cfg.data.batch_size)
    import jax.numpy as jnp

    state = trainer.state
    want = driver.reference_image(reference, state, batch)
    sound = driver.program_images(cfg, dtype, state, batch)
    rows = {
        "sound": driver.generator_numbers(want, sound),
        "control_lowp_kernels": driver.generator_numbers(
            want, driver.program_images(cfg, dtype, state, batch,
                                        "lowp_kernels")),
        # the fault planted on the reference's side: the sound program
        # against a reference whose transforms read bfloat16 operands
        "control_bf16_fft": driver.generator_numbers(
            driver.reference_image(
                reference, state, batch,
                fft=reference.rounded_transforms(jnp.bfloat16)), sound),
    }
    trainer.close()
    return rows


def tapped_first_steps(cell, driver, steps: int, float32: bool = False):
    """The Trainer's own step through its loader's first ``steps``
    batches, under the driver's tap; the Trainer (closed) and the tap.
    ``float32``: the same step built at float32, its products at HIGHEST
    (process-wide while the epoch runs: the precision is part of the
    trace, which the first call makes, in whichever thread)."""
    import jax

    trainer, _ = driver.make_trainer(cell, {})
    if float32:
        trainer._dtype = None
        trainer._build_step_fns()
        jax.config.update("jax_default_matmul_precision", "highest")
    tap = driver.InpaintTap(trainer.train_step, trainer.state, steps)

    def tapped(state, batch):
        if len(tap.losses) >= tap.steps:
            raise _FirstStepsDone
        return tap(state, batch)

    trainer.train_step = tapped
    try:
        trainer.train_epoch(seed=trainer.epoch)
    except _FirstStepsDone:
        pass
    finally:
        jax.config.update("jax_default_matmul_precision", None)
    trainer.train_step = tap.inner
    trainer.close()
    return trainer, tap


def steps_row(cell, driver, reference):
    import numpy as np

    from benchmark import harness

    t0 = time.perf_counter()
    hyper = cell.config["train_reference"]
    trainer, tap = tapped_first_steps(cell, driver, hyper["steps"])
    start = driver.reference_start(tap, trainer)
    trainer, tap32 = tapped_first_steps(cell, driver, hyper["steps"],
                                        float32=True)
    start32 = driver.reference_start(tap32, trainer)
    # the same seed: the same start and the same feed
    assert all(np.array_equal(start32[k], v) for k, v in start.items())
    assert all(np.array_equal(a[k], b[k]) for a, b in zip(
        tap.batches, tap32.batches) for k in a)
    t1 = time.perf_counter()
    half = cell.config["batch_size"] // 2
    halved = [{k: np.concatenate([v[:half]] * 2) for k, v in fed.items()}
              for fed in tap.batches]
    followed = {
        "sound": reference.StepReference(hyper).follow(start, tap.batches),
        "control_no_penalty": reference.StepReference(
            hyper, use_penalty=False).follow(start, tap.batches),
        "control_half_batch": reference.StepReference(hyper).follow(
            start, halved),
        "control_mask_ignored_in_l1": reference.StepReference(
            hyper, ignore_mask_in_l1=True).follow(start, tap.batches),
    }
    harness.say(seconds={"programs": t1 - t0,
                         "references": time.perf_counter() - t1})
    rows = {label: driver.followed_steps(reference, hyper, tap, start, got)
            for label, got in followed.items()}
    rows["sound_float32_program"] = driver.followed_steps(
        reference, hyper, tap32, start, followed["sound"])
    # G's first Fourier unit's kernel as a vector, in both programs
    leaf = "params_g/block_0/conv1/g2g/fu/conv/kernel"
    want = followed["sound"][1][leaf].astype(np.float64)
    for label, t in (("sound", tap), ("sound_float32_program", tap32)):
        got = t.moments[leaf].astype(np.float64) / (1.0 - hyper["beta1"])
        rows[label]["first_grad_first_fu_kernel_diff_over_norm"] = float(
            np.linalg.norm(got - want) / np.linalg.norm(want))
    return rows


def chip_reference_row(cell, driver, reference):
    """One step of the reference where the cell runs it and on the host."""
    import numpy as np

    from benchmark import harness

    hyper = dict(cell.config["train_reference"], steps=1)
    trainer, tap = tapped_first_steps(cell, driver, 1)
    start = driver.reference_start(tap, trainer)
    sides = {}
    for host in (False, True):
        t0 = time.perf_counter()
        reference.HOST = host
        sides[host] = reference.StepReference(hyper).follow(
            start, tap.batches)
        harness.say(reference_on_host=host,
                    seconds=time.perf_counter() - t0)
    (chip_l, chip_g, _, chip_s), (host_l, host_g, _, host_s) = (
        sides[False], sides[True])
    norm = lambda a: float(np.linalg.norm(a.astype(np.float64)))  # noqa
    numbers = {f"chip_reference_{k}_rel_gap":
               abs(chip_l[0][k] - v) / max(abs(v), 1e-30)
               for k, v in host_l[0].items()}
    for net in ("params_g", "params_d"):
        keys = [k for k in host_g if k.startswith(net + "/")]
        median = float(np.median([norm(host_g[k]) for k in keys]))
        gap, leaf = max((norm(chip_g[k] - host_g[k])
                         / max(norm(host_g[k]), median, 1e-30), k)
                        for k in keys)
        numbers[f"chip_reference_first_grad_{net[-1]}_widest_diff"] = gap
        # the same over the leaf's OWN norm alone: printed, not judged (a
        # leaf whose gradient all but cancels reads anything there)
        own, own_leaf = max((norm(chip_g[k] - host_g[k])
                             / max(norm(host_g[k]), 1e-30), k) for k in keys)
        numbers[f"{PRINTED}first_grad_{net[-1]}_widest_diff_over_own"] = own
        harness.say(widest_leaf={net: leaf, "over_own_norm": own_leaf})
    numbers["chip_reference_stats_widest_diff"] = max(
        norm(chip_s[k] - v) / max(norm(v), 1e-30) for k, v in host_s.items())
    return {"sound": numbers}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--kind", choices=("train", "steps", "chip_reference"),
                    required=True)
    ap.add_argument("--seeds", type=int, default=4)
    ap.add_argument("--first_seed", type=int, default=2147480000)
    ap.add_argument("--bench_file", default=None)
    ap.add_argument("--allow_cpu", action="store_true")
    args = ap.parse_args(argv)

    from benchmark import check, harness

    row_of = {"train": train_row, "steps": steps_row,
              "chip_reference": chip_reference_row}[args.kind]
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
        groups = row_of(cell, driver, reference)
        # each group under the limits of the numbers it holds, as the
        # driver's own verdict holds them
        limits = dict(reference.LIMITS)
        if args.allow_cpu:
            limits.update({k: v for k, v in cell.config.get(
                "limits", {}).items() if k in limits})
        row = {"seed": seed}
        for label, numbers in groups.items():
            held = {k: v for k, v in limits.items() if k in numbers}
            if args.kind == "chip_reference":
                held = dict.fromkeys(
                    (k for k in numbers if not k.startswith(PRINTED)),
                    CHIP_REFERENCE_LIMIT)
            correct = check.verdict(numbers, held, harness.say)
            row[f"{label}.correct"] = correct
            as_expected = as_expected and correct == label.startswith(
                "sound")
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
