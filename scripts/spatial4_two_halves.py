"""The comparison that decides ``correct`` in the four-chip cell
``pix2pixhd_2048x1024.train_spatial4``, made in two halves, for a budget
that one cold run of the cell (~14 min on four chips) does not fit:

    chiprun --timeout 900 -- python scripts/spatial4_two_halves.py ref <seed>
    chiprun --chips 4 --timeout 340 -- python scripts/spatial4_two_halves.py prog <seed>
    python scripts/spatial4_two_halves.py join <seed>        # no chip

``ref`` (ONE chip, where the driver runs it too): the plain reference,
``benchmark/reference/train_step.py`` with the configuration's module,
follows the cell's first steps from the program's own seeded init (a
Trainer built with ``--mesh data=1``; the step itself is never run).
``prog`` (FOUR chips): the Trainer's own step through ``train_epoch``,
tapped by ``check.StepTap`` as ``benchmark/drivers/train.py`` taps it, and
the driver's generator check as it makes it there (its own jit, outside
the mesh). Each half writes per-leaf norms, losses and the sums of its
batches under ``chiprun_out/spatial4/``; ``join`` holds the two to each
other by ``check.py``'s rules (the numbers compare norms by leaf, so the
norms are all it needs) and prints each beside its limit. The halves must
start from the same state and the same batches: ``join`` refuses otherwise.

Read in PR 25 (my chip runs 8 and 9, seed 2147483777; PERF.md section 6):
``first_grad_g_worst_leaf_gap`` 0.0229 where the cell had read 1.118
against a reference whose pool backward the chip miscomputed.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
OUT = "chiprun_out/spatial4"
CELL = "pix2pixhd_2048x1024.train_spatial4"
T0 = time.time()


def log(**fields):
    print(json.dumps({"t": round(time.time() - T0, 1), **fields}), flush=True)


def norms(tree):
    import numpy as np

    return {k: float(np.sqrt(np.sum(np.square(np.asarray(v, np.float64)))))
            for k, v in tree.items()}


def sums(batch):
    import numpy as np

    return {k: int(np.asarray(v, np.int64).sum()) for k, v in batch.items()}


def load(seed, bench_file):
    """``bench_file``: a rehearsal's benchmark file (its first cell, on the
    CPU); None = the cell, on the chips."""
    from benchmark import harness

    name = CELL
    if bench_file:
        with open(bench_file) as f:
            name = json.load(f)["workloads"][0]["name"]
    cell = harness.load_cell(name, seed, 10.0, False, time.perf_counter(),
                             bench_file, require_tpu=not bench_file)
    harness.prepare_jax_env(cell)
    return cell, harness.load_by_path("reference", cell.config["reference"])


def write(name, out):
    os.makedirs(OUT, exist_ok=True)
    with open(f"{OUT}/{name}", "w") as f:
        json.dump(out, f, default=str)
    log(wrote=name)


def ref_half(seed, bench_file=None):
    cell, reference = load(seed, bench_file)
    import jax
    import numpy as np

    from benchmark import check
    from benchmark.drivers import train as driver
    from benchmark.reference.train_step import TrainReference
    from p2p_tpu.data.pipeline import make_loader

    trainer, cfg = driver.make_trainer(cell, {}, ["--mesh", "data=1"])
    hyper = cell.config["train_reference"]
    # the first batches as ``Trainer.train_epoch`` draws them
    epoch_seed = cfg.train.seed + trainer.epoch
    trainer.train_ds.aug_seed = epoch_seed
    batches = []
    for b in make_loader(trainer.train_ds, trainer.local_bs, shuffle=True,
                         seed=epoch_seed, num_workers=0):
        batches.append({k: np.asarray(v) for k, v in b.items()})
        if len(batches) == hyper["steps"]:
            break
    state0 = check.flatten_state(trainer.state, check.TRAIN_FIELDS)
    start = dict(state0)
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            trainer.vgg_params or {})[0]:
        start[check.leaf_key("vgg", path)] = np.asarray(jax.device_get(leaf))
    trainer.close()
    for leaf in jax.tree_util.tree_leaves(trainer.state):
        leaf.delete()
    log(trainer="built", batches=[sums(b) for b in batches])
    t = time.time()
    losses, grads, params = TrainReference(reference, hyper).follow(
        start, batches)
    log(reference_seconds=time.time() - t, losses=losses)
    write(f"ref_{seed}.json", {
        "seed": seed, "losses": losses, "grads": norms(grads),
        "moved": norms({k: params[k] - state0[k] for k in params}),
        "state0": norms(state0), "batches": [sums(b) for b in batches],
        "reference_seconds": time.time() - t,
        "device": str(jax.devices()[0])})


class _FirstStepsDone(Exception):
    pass


def prog_half(seed, bench_file=None):
    cell, reference = load(seed, bench_file)
    import jax
    import numpy as np

    from benchmark import check, datagen, harness
    from benchmark.drivers import train as driver

    device = harness.device_info(cell.entry["chips"], not bench_file)
    trainer, cfg = driver.make_trainer(cell, {})
    log(device=device, mesh=dict(trainer.mesh.shape))
    hyper = cell.config["train_reference"]
    tap = check.StepTap(trainer.train_step, trainer.state, hyper["steps"])

    def tapped(state, batch):
        if len(tap.losses) >= tap.steps:
            raise _FirstStepsDone
        return tap(state, batch)

    trainer.train_step = tapped
    t = time.time()
    try:
        trainer.train_epoch(seed=trainer.epoch)
    except _FirstStepsDone:
        pass
    trainer.train_step = tap.inner
    write(f"prog_{seed}.json", {
        "seed": seed, "losses": tap.losses,
        "grads": norms({k: v.astype(np.float32) / (1.0 - hyper["beta1"])
                        for k, v in tap.moments.items()}),
        "moved": norms({k: tap.params[k] - tap.state0[k]
                        for k in tap.params}),
        "state0": norms(tap.state0),
        "batches": [sums(b) for b in tap.batches],
        "steps_seconds": time.time() - t, "device": device,
        "gauges": {k: v for k, v in trainer.obs.snapshot().items()
                   if k.startswith("step_")},
        "memory": [d.memory_stats() for d in jax.local_devices()]})
    # the driver's generator check as it makes it: its own jit, outside the
    # Trainer's mesh, replicated state (two steps on by now: these numbers
    # say that the path runs, not where the limits lie), whole host batch
    t = time.time()
    hw = (cell.config["image_height"], cell.config["image_width"])
    first = np.stack(datagen.images(cell.seed, cfg.data.batch_size, hw))
    batch = {"target": first, "input": np.stack(
        [datagen.compress_uint8(i, 3) for i in first])}
    fn = driver.program_generator_path(cfg, driver.train_dtype(cfg))
    kernels = fn.lower(trainer.state, batch).as_text().count(
        "tpu_custom_call")
    pred, raw, code = jax.device_get(fn(trainer.state, batch))
    numbers = driver.generator_numbers(
        reference, check.flatten_state(trainer.state), batch, pred, raw,
        code, cfg.model.quant_bits)
    write(f"prog_{seed}.generator.json", dict(
        numbers, kernel_calls=kernels, seconds=time.time() - t))


def join(seed, bench_file=None):
    import numpy as np

    from benchmark import harness

    with open(f"{OUT}/ref_{seed}.json") as f:
        ref = json.load(f)
    with open(f"{OUT}/prog_{seed}.json") as f:
        prog = json.load(f)
    if ref["batches"] != prog["batches"] or ref["state0"] != prog["state0"]:
        raise SystemExit("the halves did not start from the same state and "
                         "batches")
    rel = lambda got, want: abs(got - want) / max(abs(want), 1e-30)  # noqa
    numbers, leaves = {}, {}
    for name in ("loss_d", "loss_g"):
        gaps = [rel(p[name], r[name])
                for p, r in zip(prog["losses"], ref["losses"])]
        numbers[f"step1_{name}_rel_gap"] = gaps[0]
        numbers[f"later_{name}_rel_gap"] = max(gaps[1:])
    # check.worst_leaf_gap, on norms that are already taken
    for what, tag in (("grads", "first_grad"), ("moved", "params_change")):
        got, want = prog[what], ref[what]
        for net in ("params_g", "params_d"):
            keys = [k for k in want if k.startswith(net + "/")]
            median = float(np.median([want[k] for k in keys]))
            gap, leaf = max((abs(got.get(k, 0.0) - want[k])
                             / max(want[k], median, 1e-30), k) for k in keys)
            numbers[f"{tag}_{net[-1]}_worst_leaf_gap"] = gap
            leaves[f"{tag}_{net[-1]}"] = leaf
    cell, reference = load(seed, bench_file)
    limits = dict(reference.LIMITS)
    if bench_file:
        limits.update(cell.config.get("limits", {}))
    for k, v in sorted(numbers.items()):
        print(json.dumps({"number": k, "value": v, "limit": limits[k],
                          "holds": v <= limits[k]}))
    print(json.dumps({"worst_leaves": leaves, "program": prog["losses"],
                      "reference": ref["losses"]}))


if __name__ == "__main__":
    half = {"ref": ref_half, "prog": prog_half, "join": join}[sys.argv[1]]
    half(int(sys.argv[2]), *sys.argv[3:4])
