"""A benchmark cell's window alone, read from the program's own records.

Builds the cell's Trainer as its benchmark driver does (``make_trainer``
of ``benchmark/drivers/<driver>.py``), runs the warm-up epoch and then
``--epochs`` whole epochs through ``train_epoch()`` between two fences,
with no output check, no reference and no trace, and prints the window's
img/s and the step clock's fields of each epoch record
(docs/OBSERVABILITY.md): the host's seconds a step, the wait, the median
completion-to-completion interval, the steps the host was behind in.
A cell's full run holds a chip 5-9 min, most of it the check's
reference; this takes 1-5 (the step's compile is the cold part).

``--blocks N`` then runs N more epochs with what the loop calls inside
``step_bookkeeping`` wrapped in ``perf_counter`` (and every eager
``jnp.add`` / ``jnp.where`` / ``jnp.zeros_like``), and prints a middle
step's calls in order: which call holds the host (PR 37 found the last
eager add of the epoch's sums, 165 ms of every step).

    chiprun -- python scripts/window_records.py \
        --workload vqgan_imagenet_f16_16384.train --seed 2147700001

``--root`` reads another checkout (a ``git archive`` copy of the
parent); run it from anywhere.
"""
import argparse
import json
import os
import sys
import time

T_START = time.perf_counter()

KEYS = ["epoch", "steps", "dur_s", "host_s", "device_wait_s",
        "step_interval_median_s", "host_bound_steps", "epoch_start_s",
        "epoch_drain_s", "drain_device_wait_s", "step_bookkeeping_s",
        "train_dispatch_s", "feed_next_s", "first_feed_next_s",
        "first_step_late_s", "device_starved_s", "device_slow_s",
        "slowest_step_interval_s", "slowest_step_interval_phase",
        "cpu_s", "compiles"]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--epochs", type=int, default=4)
    ap.add_argument("--blocks", type=int, default=0)
    ap.add_argument("--tag", default="")
    ap.add_argument("--bench_file", default=None)
    ap.add_argument("--allow_cpu", action="store_true")
    ap.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), help="the checkout to read")
    args = ap.parse_args()
    sys.path.insert(0, args.root)
    from benchmark import harness

    cell = harness.load_cell(args.workload, args.seed, 10.0, False, T_START,
                             bench_file=args.bench_file,
                             require_tpu=not args.allow_cpu)
    harness.prepare_jax_env(cell)
    import jax

    driver = harness.load_by_path("drivers", cell.workload["driver"])
    device = harness.device_info(cell.entry["chips"], cell.require_tpu)
    marks = {}
    trainer, cfg = driver.make_trainer(cell, marks)

    def say(**fields):
        print(json.dumps(dict(fields, tag=args.tag, workload=args.workload)),
              flush=True)

    say(device=device, batch=cfg.data.batch_size,
        steps_per_epoch=trainer.steps_per_epoch)
    t0 = time.perf_counter()
    warm = trainer.train_epoch(seed=trainer.epoch)
    say(warm_epoch_s=time.perf_counter() - t0,
        metric_keys=sorted(k for k in warm if k != "img_per_sec"))
    jax.block_until_ready(trainer.state)
    step_before = int(trainer.state.step)
    t0 = time.perf_counter()
    means = []
    for _ in range(args.epochs):
        trainer.epoch += 1
        means.append(trainer.train_epoch(seed=trainer.epoch))
    jax.block_until_ready(trainer.state)
    elapsed = time.perf_counter() - t0
    steps = int(trainer.state.step) - step_before
    say(window={"epochs": args.epochs, "steps": steps, "elapsed_s": elapsed,
                "img_per_s": steps * cfg.data.batch_size / elapsed,
                "ms_per_step": 1e3 * elapsed / steps},
        last_means={k: float(v) for k, v in means[-1].items()})
    for rec in [s for s in trainer.spans.spans
                if s["name"] == "train_epoch"][-args.epochs:]:
        say(epoch_record={k: rec.get(k) for k in KEYS})
    if args.blocks:
        timed_blocks(trainer, args.blocks, say)
    trainer.close()


def timed_blocks(trainer, epochs, say):
    """Wrap what ``run()`` calls inside ``step_bookkeeping`` and print, a
    step, the seconds of each call in call order."""
    import jax.numpy as jnp

    from p2p_tpu.train import loop as loop_mod

    log = []

    def timed(name, fn):
        def wrapper(*a, **kw):
            t0 = time.perf_counter()
            try:
                return fn(*a, **kw)
            finally:
                log.append((name, time.perf_counter() - t0))
        return wrapper

    patched = []

    def patch(obj, attr, name):
        if hasattr(obj, attr):
            patched.append((obj, attr, getattr(obj, attr)))
            setattr(obj, attr, timed(name, getattr(obj, attr)))

    patch(loop_mod, "queue_health_observation", "health_queue_and_read")
    patch(loop_mod, "mask_skipped_metrics", "mask_skipped_metrics")
    patch(loop_mod, "accumulate_metrics", "accumulate_metrics")
    patch(loop_mod, "poll_preempt", "poll_preempt")
    patch(jnp, "add", "jnp.add")
    patch(jnp, "where", "jnp.where")
    patch(jnp, "zeros_like", "jnp.zeros_like")
    inner = trainer.train_step
    trainer.train_step = timed("train_step_call", inner)
    try:
        for _ in range(epochs):
            del log[:]
            trainer.epoch += 1
            trainer.train_epoch(seed=trainer.epoch)
            # split the flat log into steps at each train_step_call
            steps, cur = [], None
            for name, secs in log:
                if name == "train_step_call":
                    cur = []
                    steps.append(cur)
                if cur is not None:
                    cur.append((name, secs))
            mid = steps[len(steps) // 2]
            say(blocks_of_a_middle_step=[[n, round(s * 1e3, 3)] for n, s in mid])
            totals = {}
            for st in steps[2:]:
                for n, s in st:
                    totals[n] = totals.get(n, 0.0) + s
            say(ms_a_step_by_call={
                n: round(1e3 * v / max(len(steps) - 2, 1), 3)
                for n, v in totals.items()}, steps=len(steps))
            # which eager call of a step is the first to take over 5 ms
            firsts = []
            for st in steps[2:]:
                eager = [(n, s) for n, s in st if n.startswith("jnp.")]
                firsts.append(next((i for i, (n, s) in enumerate(eager)
                                    if s > 0.005), None))
            say(first_eager_call_over_5ms_by_step=firsts,
                eager_calls_a_step=sum(n.startswith("jnp.") for n, _ in mid))
    finally:
        trainer.train_step = inner
        for obj, attr, old in reversed(patched):
            setattr(obj, attr, old)


if __name__ == "__main__":
    main()
