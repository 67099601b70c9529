"""The loops' per-dispatch metric bookkeeping as ONE compiled call
(``train.loop.accumulate_metrics``): the same arithmetic as the eager
composition it replaced, bit for bit; one call on device arrays between
two dispatches whatever the number of metric keys; compiled once a
dispatch kind.

Why it matters: a runtime holds a bounded number of computations in
flight a device (32 here, the CPU backend too). Issued eagerly, ~3 a
metric key, an 11-key step's bookkeeping reached the bound behind the
running step and the call BLOCKED until the step finished (PERF.md
section 6, PR 37)."""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from p2p_tpu.train import loop as loop_mod
from p2p_tpu.train.loop import (
    accumulate_metrics,
    epoch_metric_means,
    mask_skipped_metrics,
)
from tests.test_step_clock import ToySteps, last_record, toy_trainer


# ------------------------------------------------- (a) the same arithmetic
def dispatches_of(k, health, n=4, keys=5, seed=0):
    """``n`` dispatches' metrics of ``k`` steps each: float32 of mixed
    magnitudes (the order of the adds shows), one SKIPPED step a dispatch
    that carries a NaN (and, without ``health_ok``, nothing masks it)."""
    rng = np.random.default_rng(seed)
    out = []
    for d in range(n):
        shape = (k,) if k > 1 else ()
        m = {f"loss_{i}": np.asarray(
            rng.standard_normal(shape) * 10.0 ** rng.integers(-4, 5),
            np.float32) for i in range(keys)}
        ok = np.ones(shape, np.float32)
        skipped = (d % k,) if k > 1 else ()
        if d % 2 == 1 or k > 1:
            ok[skipped] = 0.0
            m["loss_0"][skipped] = np.nan
        if health:
            m["health_ok"] = ok
        out.append({key: jnp.asarray(v) for key, v in m.items()})
    return out


def eager_epoch(dispatches, k):
    """The parent's composition: ``mask_skipped_metrics`` and one
    ``jnp.add`` a key, eagerly, and ``v[-1]`` for the last step."""
    sums = None
    for metrics in dispatches:
        step_metrics = mask_skipped_metrics(metrics, k)
        sums = step_metrics if sums is None else jax.tree_util.tree_map(
            jnp.add, sums, step_metrics)
    last = (jax.tree_util.tree_map(lambda v: v[-1], metrics) if k > 1
            else metrics)
    return sums, last


def bits(tree):
    return {key: np.asarray(v).tobytes() for key, v in tree.items()}


@pytest.mark.parametrize("health", [True, False],
                         ids=["health_ok", "no_health_ok"])
@pytest.mark.parametrize("k", [1, 3])
def test_the_compiled_call_is_the_eager_composition_bit_for_bit(k, health):
    dispatches = dispatches_of(k, health)
    want_sums, want_last = eager_epoch(dispatches, k)
    sums = None
    for metrics in dispatches:
        sums, last = accumulate_metrics(sums, metrics, k)
    assert bits(sums) == bits(want_sums)
    assert bits(last) == bits(want_last)
    if k == 1:
        assert last is dispatches[-1]    # what the step returned, no copy
    count = k * len(dispatches)
    got = epoch_metric_means(jax.device_get(sums), count)
    want = epoch_metric_means(jax.device_get(want_sums), count)
    assert {key: np.float64(v).tobytes() for key, v in got.items()} == \
        {key: np.float64(v).tobytes() for key, v in want.items()}
    # the NaN of a skipped step reaches the means only where nothing masks
    assert np.isfinite(got["loss_0"]) == health
    # the dtypes are the step's own: nothing was widened or narrowed
    assert {v.dtype for v in sums.values()} == {jnp.dtype("float32")}


def test_donated_sums_never_alias_what_the_health_queue_holds():
    """The epoch's first call returns the metrics it was handed (all of
    them without ``health_ok``, that key with it) as ITS sums, which the
    next call donates; the step's own arrays, still queued for the
    delayed read, must outlive that."""
    for first in ({"loss_g": jnp.float32(1.5)},
                  {"loss_g": jnp.float32(1.5), "health_ok": jnp.float32(1)}):
        sums, last = accumulate_metrics(None, first, 1)
        assert last is first
        more = {key: jnp.float32(2.0) for key in first}
        sums2, _ = accumulate_metrics(sums, more, 1)
        assert all(v.is_deleted() for v in sums.values())   # donated
        assert {key: float(v) for key, v in first.items()} == {
            key: 1.5 if key == "loss_g" else 1.0 for key in first}
        assert float(sums2["loss_g"]) == 3.5


# ------------------------------- (b) one call a dispatch, whatever the keys
class ManyKeySteps(ToySteps):
    """``ToySteps`` whose step returns ``n_keys`` metrics, ``health_ok``
    among them, and notes when each call began and returned."""

    def __init__(self, n_keys, **kw):
        super().__init__(**kw)
        self.on_call = self.on_return = lambda: None

        @jax.jit
        def metrics_of(loss):
            # one execution, as a real step's metrics are its own outputs
            return {"loss_g": loss, "loss_d": loss * 2.0,
                    "health_ok": jnp.ones_like(loss),
                    **{f"g_part{i}": loss + float(i)
                       for i in range(n_keys - 3)}}

        self.metrics_of = metrics_of

    def train_step(self, state, batch):
        self.on_call()
        metrics = self.metrics_of(self._loss())
        self.issued.append(metrics)
        state = state.replace(step=state.step + 1)
        self.on_return()
        return state, metrics


class DeviceCallCounter:
    """Counts, on the loop's thread and only while ``self.open``, what the
    loop can issue to the device from Python: calls of the compiled
    ``loop._accumulate`` (a spy), and EAGER calls on concrete device
    arrays of any public ``jax.numpy`` function or of an operator /
    indexing method of ``jax.Array`` (every one of those is one XLA
    execution or more). A call under a trace (its arguments are tracers)
    is not counted: it issues nothing."""

    OPERATORS = ("__getitem__", "__add__", "__radd__", "__sub__", "__rsub__",
                 "__mul__", "__rmul__", "__truediv__", "__rtruediv__",
                 "__ge__", "__gt__", "__le__", "__lt__", "__eq__", "__ne__",
                 "__neg__", "sum", "mean", "astype", "reshape")

    def __init__(self, monkeypatch):
        self.open = False
        self.thread = threading.get_ident()
        self.calls = []
        monkeypatch.setattr(loop_mod, "_accumulate",
                            self.counted("_accumulate", loop_mod._accumulate,
                                         eager_only=False))
        for name, fn in vars(jnp).items():
            if callable(fn) and not name.startswith("_") \
                    and not isinstance(fn, type):
                monkeypatch.setattr(jnp, name, self.counted(f"jnp.{name}", fn))
        array_type = type(jnp.float32(0))
        for name in self.OPERATORS:
            monkeypatch.setattr(array_type, name, self.counted(
                f"Array.{name}", getattr(array_type, name)))

    def counted(self, name, fn, eager_only=True):
        def wrapper(*args, **kwargs):
            if self.open and threading.get_ident() == self.thread:
                leaves = jax.tree_util.tree_leaves((args, kwargs))
                traced = any(isinstance(v, jax.core.Tracer) for v in leaves)
                on_device = any(isinstance(v, jax.Array) for v in leaves)
                if not traced and (on_device or not eager_only):
                    self.calls.append(name)
            return fn(*args, **kwargs)
        return wrapper


def calls_between_dispatches(tmp_path, monkeypatch, n_keys):
    """A toy Trainer's second epoch: the counted calls of each gap from
    one step's return to the next step's call."""
    steps = ManyKeySteps(n_keys, work=0)
    tr = toy_trainer(tmp_path / str(n_keys), steps)
    try:
        tr.train_epoch()            # compiles the accumulate, both kinds
        counter = DeviceCallCounter(monkeypatch)
        gaps = []

        def on_return():
            counter.calls, counter.open = [], True

        def on_call():
            if counter.open:
                gaps.append(counter.calls)
            counter.open = False

        steps.on_return, steps.on_call = on_return, on_call
        tr.epoch += 1
        means = tr.train_epoch()
        counter.open = False
        monkeypatch.undo()
    finally:
        tr.close()
    assert len(means) == n_keys + 1 and means["health_ok"] == 1.0
    assert len(gaps) == 7           # 8 dispatches
    return gaps


def test_one_device_call_between_two_dispatches_whatever_the_keys(
        tmp_path, monkeypatch):
    few = calls_between_dispatches(tmp_path, monkeypatch, 3)
    many = calls_between_dispatches(tmp_path, monkeypatch, 30)
    assert few == many
    assert all(gap == ["_accumulate"] for gap in many), many


def test_the_counter_sees_an_eager_composition(monkeypatch):
    """The instrument of the test above, held against the parent's
    bookkeeping: three calls a key less one, and an operator's one."""
    counter = DeviceCallCounter(monkeypatch)
    metrics = {"loss_g": jnp.float32(1.0), "loss_d": jnp.float32(2.0),
               "health_ok": jnp.float32(1.0)}
    counter.open = True
    eager_epoch([metrics, metrics], 1)
    counter.open = False
    monkeypatch.undo()
    assert sorted(counter.calls) == sorted(
        2 * ["Array.__ge__"] + 4 * ["jnp.where"] + 4 * ["jnp.zeros_like"]
        + 3 * ["jnp.add"])


def test_thirty_keys_do_not_hold_the_host_behind_a_running_step(tmp_path):
    """What the eager bookkeeping cost: with 30 keys it queued ~90
    computations behind the running step, the runtime blocked the call at
    its bound, and the read then found the device already done in every
    step. One call a dispatch: the host waits in ``device_wait`` alone."""
    tr = toy_trainer(tmp_path, ManyKeySteps(30, work=150))
    try:
        tr.train_epoch()
        tr.epoch += 1
        tr.train_epoch()
        rec = last_record(tr)
        assert rec["steps"] == 8
        assert rec["host_bound_steps"] == 0
        assert rec["device_wait_s"] > rec["host_s"]
    finally:
        tr.close()


# --------------------------------------- (c) compiled once a dispatch kind
def test_a_scanned_run_compiles_the_call_once_a_dispatch_kind(tmp_path):
    """``scan_steps`` 3 over 8 batches: two scanned dispatches and two
    single-step remainders an epoch. The first epoch compiles the call for
    (k = 3, k = 1) x (the epoch's first dispatch, a later one) at most;
    the second compiles nothing."""
    loop_mod._accumulate.clear_cache()
    tr = toy_trainer(tmp_path, ToySteps(work=0), scan_steps=3)
    try:
        tr.train_epoch()
        kinds = loop_mod._accumulate._cache_size()
        assert kinds == 3       # (None, 3), (sums, 3), (sums, 1)
        before = tr.retrace.compiles
        tr.epoch += 1
        means = tr.train_epoch()
        assert tr.retrace.compiles == before
        assert loop_mod._accumulate._cache_size() == kinds
        assert last_record(tr)["steps"] == 8 and last_record(tr)["compiles"] == 0
        assert set(means) >= {"loss_g", "loss_d"}
    finally:
        tr.close()
