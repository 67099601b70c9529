"""The comparison that decides ``correct``: what the system produced
against the plain reference, number by number, each beside its limit.

All image errors are in 8-bit LEVELS (1 level = 1/127.5 of the [-1, 1]
range), so the limits mean the same for a float tensor read off the
device and for a PNG a client received.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

LEVEL = 127.5


def image_errors(got: np.ndarray, want: np.ndarray) -> Dict[str, float]:
    """``got`` / ``want``: float images in [-1, 1], or uint8 levels (both
    the same kind). Mean, 99th percentile and maximum of the absolute
    error, in levels, over every value of every image."""
    if got.shape != want.shape:
        raise ValueError(f"shape {got.shape} against {want.shape}")
    if got.dtype == np.uint8:
        err = np.abs(got.astype(np.float32) - want.astype(np.float32))
    else:
        err = np.abs(got.astype(np.float32) - want.astype(np.float32)) * LEVEL
    return {"mean_abs_levels": float(err.mean()),
            "p99_abs_levels": float(np.percentile(err, 99)),
            "max_abs_levels": float(err.max())}


def code_agreement(code: np.ndarray, ref_pre: np.ndarray,
                   bits: int) -> Dict[str, float]:
    """The quantizer's output (a discrete code, levels k / (2^b - 1)) held
    against the code the reference's own value BEFORE its quantizer rounds
    to. Where that value lies within a rounding error of a boundary either
    neighbour is a faithful rounding, so a small share may differ by ONE
    level; none may differ by more."""
    n = float(2 ** bits - 1)
    ref_code = np.round(np.clip(ref_pre.astype(np.float64), 0.0, 1.0) * n)
    got_code = np.round(code.astype(np.float64) * n)
    off = np.abs(got_code - ref_code)
    return {"code_differs_share": float((off > 0).mean()),
            "code_off_by_more_than_one_share": float((off > 1).mean())}


def verdict(numbers: Dict[str, float], limits: Dict[str, float],
            say) -> bool:
    """Print every number compared beside its limit; True when all hold.
    A number without a limit is printed and not judged; a limit without a
    number fails (the path that should have produced it did not run)."""
    ok = True
    rows: List[dict] = []
    for name in sorted(set(numbers) | set(limits)):
        value, limit = numbers.get(name), limits.get(name)
        row = {"number": name, "value": value, "limit": limit}
        if limit is not None:
            row["holds"] = bool(value is not None and np.isfinite(value)
                                and value <= limit)
            ok = ok and row["holds"]
        rows.append(row)
    say(check="correct", rows=rows, correct=ok)
    return ok


def leaf_key(field: str, path) -> str:
    """``params_g/ConvLayer_0/Conv_0/kernel`` from a state field's name and
    a jax tree path inside it."""
    return f"{field}/" + "/".join(str(getattr(k, "key", k)) for k in path)


def flatten_state(state, fields=("params_g", "batch_stats_g", "params_c",
                                 "batch_stats_c")) -> Dict[str, np.ndarray]:
    """The generator-side leaves of a train or serving state as the flat
    ``{"params_g/.../kernel": ndarray}`` dict the references read."""
    import jax

    out: Dict[str, np.ndarray] = {}
    for field in fields:
        tree = getattr(state, field, None)
        if tree is None:
            continue
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
            out[leaf_key(field, path)] = np.asarray(jax.device_get(leaf))
    return out


# ------------------------------------------------- the whole train step

#: the state fields the train-step reference starts from
TRAIN_FIELDS = ("params_g", "params_d", "params_c", "spectral_d")
NETS = ("params_g", "params_d", "params_c")


def first_moments(state) -> Dict[str, np.ndarray]:
    """Adam's first moment of every trainable leaf, found in the
    optimizer states by the name optax gives it (``.mu``), under the
    leaf's own path: ``params_g/ConvLayer_0/Conv_0/kernel``."""
    import jax

    out: Dict[str, np.ndarray] = {}
    for net in NETS:
        opt = getattr(state, "opt_" + net[-1], None)
        if opt is None:
            continue
        for path, leaf in jax.tree_util.tree_flatten_with_path(opt)[0]:
            names = [getattr(k, "name", None) for k in path]
            if "mu" in names:
                rest = path[names.index("mu") + 1:]
                out[leaf_key(net, rest)] = np.asarray(jax.device_get(leaf))
    return out


class StepTap:
    """Sits on the Trainer's own compiled step while ``train_epoch`` drives
    it through its first ``steps`` steps, and keeps what the comparison
    with the plain reference needs: the state before the first step, every
    batch as it was fed, every step's losses, Adam's first moments after
    step one (the first gradient as the optimizer got it, times 1 - beta1)
    and the parameters after the last. The step itself, its state and its
    feed are the Trainer's; after ``steps`` calls the tap only passes on.
    ``seconds`` is the host time the copies took (not set-up's)."""

    def __init__(self, step, state, steps: int):
        import time

        self.inner, self.steps, self._clock = step, steps, time.perf_counter
        t0 = self._clock()
        self.state0 = flatten_state(state, TRAIN_FIELDS)
        self.batches: List[Dict[str, np.ndarray]] = []
        self.losses: List[Dict[str, float]] = []
        self.moments: Dict[str, np.ndarray] = {}
        self.params: Dict[str, np.ndarray] = {}
        self.seconds = self._clock() - t0

    def __call__(self, state, batch):
        if len(self.losses) >= self.steps:
            return self.inner(state, batch)
        import jax

        t0 = self._clock()
        self.batches.append({k: np.asarray(v)
                             for k, v in jax.device_get(batch).items()})
        t_step = self._clock()
        state, metrics = self.inner(state, batch)
        t1 = self._clock()
        self.losses.append({k: float(v)
                            for k, v in jax.device_get(metrics).items()})
        if len(self.losses) == 1:
            self.moments = first_moments(state)
        if len(self.losses) == self.steps:
            self.params = flatten_state(state, NETS)
        self.seconds += (t_step - t0) + (self._clock() - t1)
        return state, metrics


def _leaf_norms(tree: Dict[str, np.ndarray]) -> Dict[str, float]:
    return {k: float(np.sqrt(np.sum(np.square(v.astype(np.float64)))))
            for k, v in tree.items()}


def worst_leaf_gap(got: Dict[str, np.ndarray],
                   want: Dict[str, np.ndarray]) -> Dict[str, tuple]:
    """Per net, the worst leaf's gap between the program's norm and the
    reference's (the gap of the norms, not the norm of the difference),
    against the reference's norm of that leaf or of the net's median leaf,
    whichever is larger: some gradients are all but zero. Returns
    ``{"g": (gap, leaf), ...}``."""
    ng, nw = _leaf_norms(got), _leaf_norms(want)
    out: Dict[str, tuple] = {}
    for net in NETS:
        keys = [k for k in nw if k.startswith(net + "/")]
        if not keys:
            continue
        median = float(np.median([nw[k] for k in keys]))
        out[net[-1]] = max(
            (abs(ng.get(k, 0.0) - nw[k]) / max(nw[k], median, 1e-30), k)
            for k in keys)
    return out


def train_step_numbers(tap: StepTap, ref_losses, ref_grads, ref_params,
                       beta1: float, say) -> Dict[str, float]:
    """The program's first steps against the reference's. Each loss's
    relative gap at step one, where both stand on the same state, and its
    widest over the later steps, where two precisions have begun to part
    (a GAN's start is steep: the losses halve from step to step, so a
    frozen or mis-scaled update reads some tens of percent there). Per
    net the worst-leaf gap of the first gradient and of the parameters'
    change."""
    numbers: Dict[str, float] = {}
    rel = lambda got, want: abs(got - want) / max(abs(want), 1e-30)  # noqa
    for name in ("loss_d", "loss_g", "loss_c"):
        if name not in ref_losses[0]:
            continue
        gaps = [rel(got[name], want[name])
                for got, want in zip(tap.losses, ref_losses)]
        numbers[f"step1_{name}_rel_gap"] = gaps[0]
        numbers[f"later_{name}_rel_gap"] = max(gaps[1:])
    got_grads = {k: v.astype(np.float32) / (1.0 - beta1)
                 for k, v in tap.moments.items()}
    worst = {}
    for net, (gap, leaf) in worst_leaf_gap(got_grads, ref_grads).items():
        numbers[f"first_grad_{net}_worst_leaf_gap"] = gap
        worst[f"first_grad_{net}"] = leaf
    moved = lambda after: {k: after[k] - tap.state0[k]  # noqa: E731
                           for k in after}
    for net, (gap, leaf) in worst_leaf_gap(moved(tap.params),
                                           moved(ref_params)).items():
        numbers[f"params_change_{net}_worst_leaf_gap"] = gap
        worst[f"params_change_{net}"] = leaf
    say(train_steps={"program": tap.losses, "reference": ref_losses,
                     "worst_leaves": worst})
    return numbers
