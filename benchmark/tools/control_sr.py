"""``tools/control_labels.py`` for the super-resolution cell (driver
``train_sr``), on the chip at the cell's own size: for each seed what the
SOUND program gives against the configuration's plain reference, and what
each CONTROL gives, each passed through ``check.verdict`` under the
limits of ``benchmark/reference/<config>.py``: the sound program has to
come out correct and every control not (exit 1 otherwise). The limits
are set between the readings; the benchmark's own runs never run this.

    python benchmark/tools/control_sr.py --workload swinir_m_realsr_x4_gan.train --kind train --seeds 12
    python benchmark/tools/control_sr.py --workload swinir_m_realsr_x4_gan.train --kind steps --seeds 1

Both kinds start from the CHECK's state, the seeded start off its init
(``drivers/train_sr.widened``), as the cell's own runs do.

``train``: the generator with stochastic depth off on the first seeded
batch, as the step computes it and from the same modules at float32, with
three controls, each in the nearest precision below the one the
configuration states: ``control_int8`` rounds every kernel of the
generator to 8-bit integers (below its bf16 compute), ``control_bf16_softmax``
keeps every intermediate of the softmax in bfloat16 and
``control_bf16_norm`` those of LayerNorm (below their float32); the x4
image's error in 8-bit levels must refuse each. ``steps``: the Trainer's own compiled step on the first
batches its loader feeds, against the configuration's ``StepReference``
with the masks the program drew, through all the followed steps; the
control is ``control_half_batch``, a step that saw only HALF of every
batch (the reference follows the batches with their second half replaced
by their first, masks too). The step compiled anew with the softmax and
LayerNorm's moments in bfloat16, on the same state, feed and masks, is
printed as ``reading_bf16_softmax`` with its verdict and not judged: in a
step whose products read bf16 operands it gives the sound step's numbers
(PERF.md section 6, PR 38), which is why the generator check holds that
float32 in the float32 program. The loop over
seeds, the verdicts and the summary are ``control_labels.main``'s.
"""

import importlib.util
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


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

    from benchmark.drivers import train as base

    cfgf = cell.config
    cfg = cli_train.config_from_flags(cli_train.build_parser().parse_args(
        base.train_argv(cell, "unused", "unused")))
    bs = cfgf["batch_size"]
    dtype = base.train_dtype(cfg)
    batch = driver.first_batch(cell, bs)
    state = driver.widened(create_train_state(
        cfg, jax.random.key(cfg.train.seed), batch,
        max(1, cfgf["dataset_pairs"] // bs), dtype))
    want = driver.reference_image(reference, state, batch)
    return {f"control_{control}" if control else "sound":
            driver.generator_numbers(want, cfg, dtype, state, batch, control)
            for control in ("",) + driver.CONTROLS}


def steps_row(cell, driver, reference, tool):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from p2p_tpu.train import step as step_module

    from benchmark import check, harness
    from benchmark.drivers import train as base

    t0 = time.perf_counter()
    hyper = cell.config["train_reference"]
    trainer, cfg = driver.make_trainer(cell, {})
    shardings = jax.tree_util.tree_map(lambda x: x.sharding, trainer.state)
    start_host = driver.widened(trainer.state)
    feed = driver.FeedTap(trainer.train_step, hyper["steps"])

    def fed(state, batch):
        if len(feed.batches) >= feed.steps:
            raise tool._FirstStepsDone
        return feed(state, batch)

    trainer.train_step = fed
    try:
        trainer.train_epoch(seed=trainer.epoch)
    except tool._FirstStepsDone:
        pass
    trainer.train_step = feed.inner
    tap = driver.followed_tap(trainer, start_host, shardings, feed.batches)
    # the same step with the softmax and LayerNorm's moments in bfloat16
    build_models = step_module.build_models

    def narrow_models(cfg, dtype=None):
        g, d, c = build_models(cfg, dtype)
        return g.clone(softmax_dtype=jnp.bfloat16,
                       norm_dtype=jnp.bfloat16), d, c

    step_module.build_models = narrow_models
    try:
        trainer._build_step_fns()
    finally:
        step_module.build_models = build_models
    narrow = driver.followed_tap(trainer, start_host, shardings,
                                 feed.batches)
    bs = cfg.data.batch_size
    keeps = [driver.keep_masks(cfg, base.train_dtype(cfg), tap.noise_seed,
                               tap.first_step + i, bs)
             for i in range(hyper["steps"])]
    trainer.close()
    t1 = time.perf_counter()
    start = driver.reference_start(tap, trainer)
    follow = reference.StepReference(hyper).follow
    followed = follow(start, tap.batches, keeps)
    t2 = time.perf_counter()
    half = bs // 2
    first_half = lambda v, axis: np.concatenate(  # noqa: E731
        [np.take(v, range(half), axis)] * 2, axis)
    halved = follow(start,
                    [{k: first_half(v, 0) for k, v in fed.items()}
                     for fed in tap.batches],
                    [first_half(keep, 1) for keep in keeps])
    harness.say(seconds={"program": t1 - t0, "reference": t2 - t1,
                         "half_batch": time.perf_counter() - t2})
    numbers = lambda tap, followed: driver.followed_steps(  # noqa: E731
        reference, hyper, tap, start, keeps, followed)
    reading = numbers(narrow, followed)
    harness.say(reading_bf16_softmax=reading, seed=cell.seed,
                correct=check.verdict(reading, {
                    k: v for k, v in reference.LIMITS.items()
                    if k in reading}, lambda **_: None))
    return {"sound": numbers(tap, followed),
            "control_half_batch": numbers(tap, halved)}


def main(argv=None) -> int:
    tool = _labels_tool()
    tool.train_row = train_row
    tool.steps_row = lambda *a: steps_row(*a, tool)
    return tool.main(argv)


if __name__ == "__main__":
    sys.exit(main())
