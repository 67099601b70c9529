"""The Trainer's step clock (obs.StepClock): when each dispatch finished
as the delayed read learns it, how long the host waited for it, and which
phase starved the device where an interval ran long.

Two kinds of test: ``epoch_fields`` on hand-made stamps (exact), and a
real 16x16 Trainer whose step is a jitted toy that takes device time
asynchronously, as the chip's does (wall-clock, loose bounds)."""

import dataclasses
import gc
import json
import statistics
import time

import jax
import jax.numpy as jnp
import pytest

from p2p_tpu import obs
from p2p_tpu.core.config import get_preset
from p2p_tpu.data.synthetic import make_synthetic_dataset
from p2p_tpu.train import loop as loop_mod
from p2p_tpu.train.loop import Trainer

CLOCK_FIELDS = {
    "device_wait_s", "drain_device_wait_s", "host_s", "first_step_s",
    "first_step_late_s", "step_interval_median_s", "slowest_step_interval_s",
    "slowest_step_interval_step", "slowest_step_interval_phase",
    "host_bound_steps", "device_starved_s", "device_slow_s",
    "starved_in_feed_next_s", "starved_in_train_dispatch_s",
    "starved_in_step_bookkeeping_s", "cpu_s", "gc_pause_s", "compiles"}


# ------------------------------------------------------------ hand-made
def timeline(step=1.0, n=6, k=1, feed=0.01, disp=0.02, book=0.03,
             stall=None, slow=None, late_read=None):
    """Stamps of an epoch of ``n`` dispatches of ``k`` steps whose device
    step takes ``step`` seconds, as the loop would take them: iteration
    ``i`` = feed_next, train_dispatch, bookkeeping in which the read of
    dispatch ``i - 1`` waits until the device is done with it. ``stall``
    = (iteration, phase, seconds) of host work added; ``slow`` =
    (dispatch, seconds) of device time added; ``late_read`` = (dispatch,
    seconds) its read came back after the device was done. Returns the clock with its
    epoch closed, what the Trainer hands ``epoch_fields``, and the seconds
    the device stood idle between two dispatches."""
    clock = obs.StepClock(obs.MetricsRegistry())
    now, free = 0.0, 0.0          # the host's clock; when the device frees
    idle = 0.0
    finished, dispatches, feeds = [], [], []
    for i in range(n):
        extra = {"feed_next": 0.0, "train_dispatch": 0.0,
                 "step_bookkeeping": 0.0}
        if stall and stall[0] == i:
            extra[stall[1]] = stall[2]
        feeds.append(feed + extra["feed_next"])
        now += feeds[-1]
        secs = disp + extra["train_dispatch"]
        dispatches.append((i * k, now, secs))
        now += secs
        if i:
            idle += max(now - free, 0.0)
        free = max(free, now) + k * step + (
            slow[1] if slow and slow[0] == i else 0.0)
        finished.append(free)
        now += book / 2 + extra["step_bookkeeping"]
        if i:
            waited = max(finished[i - 1] - now, 0.0) + (
                late_read[1] if late_read and late_read[0] == i - 1 else 0.0)
            now += waited
            clock._open.append((now, waited, k))
        now += book / 2
    feeds.append(feed)            # the terminal feed_next
    now += feed
    drain_t0 = now
    now += 0.001                  # epoch_drain up to its read
    waited = max(finished[-1] - now, 0.0)
    clock._open.append((now + waited, waited, k))
    clock.close_epoch()
    return clock, (dispatches, feeds, drain_t0), idle


@pytest.mark.parametrize("case, kw, want", [
    ("steady", {}, dict(phase="device", host_bound=0, slow=0.0)),
    ("feed_stall", dict(stall=(3, "feed_next", 2.5)), dict(
        phase="feed_next", step=3, host_bound=1, slow=0.0, slowest=2.55)),
    ("dispatch_stall", dict(stall=(2, "train_dispatch", 3.0)), dict(
        phase="train_dispatch", step=2, host_bound=1, slow=0.0)),
    # after its dispatch, before its read: the next dispatch was queued
    # already, so a step's length of the stall cost the device nothing
    ("bookkeeping_stall", dict(stall=(2, "step_bookkeeping", 3.0)), dict(
        phase="step_bookkeeping", step=2, host_bound=2, slow=0.0)),
    ("short_bookkeeping_stall", dict(stall=(2, "step_bookkeeping", 1.5)),
     dict(phase="step_bookkeeping", step=2, host_bound=1, slow=0.0,
          starved=False)),
    ("slow_device_step", dict(slow=(3, 4.0)), dict(
        phase="device", step=3, host_bound=0, slow=4.0, slowest=5.0)),
    # the read of dispatch 2 came back 0.4 s late, the host waiting all
    # the while: the next interval is as much shorter, the device lost
    # nothing and nothing is called slow
    ("late_read", dict(late_read=(2, 0.4)), dict(
        phase="device", host_bound=0, slow=0.0, slowest=1.0)),
    ("scanned", dict(k=4, slow=(2, 2.0)), dict(
        phase="device", step=8, host_bound=0, slow=2.0, slowest=1.5)),
    ("scanned_feed_stall", dict(k=2, stall=(4, "feed_next", 5.0)), dict(
        phase="feed_next", step=8, host_bound=2, slow=0.0)),
])
def test_epoch_fields_on_a_made_timeline(case, kw, want):
    clock, handed, idle = timeline(**kw)
    f = clock.epoch_fields(*handed)
    assert f["step_interval_median_s"] == pytest.approx(1.0, abs=1e-5)
    assert f["slowest_step_interval_phase"] == want["phase"]
    if "step" in want:
        assert f["slowest_step_interval_step"] == want["step"]
    if "slowest" in want:
        assert f["slowest_step_interval_s"] == pytest.approx(
            want["slowest"], abs=0.02)
    assert f["host_bound_steps"] == want["host_bound"]
    # what the stamps say the host cost is what the device stood idle for
    # (to the 0.06 s of host work an iteration that follows a catch-up)
    assert f["device_starved_s"] == pytest.approx(idle, abs=0.07)
    starved = want.get("starved", bool(want["host_bound"]))
    assert starved == (idle > 0.5)
    assert f["device_slow_s"] == pytest.approx(want["slow"], abs=0.07)
    by_phase = {p: f[f"starved_in_{p}_s"] for p in clock.HOST_PHASES}
    assert sum(by_phase.values()) == pytest.approx(f["device_starved_s"],
                                                   abs=1e-5)
    if starved:
        # all of it under the phase that stalled, and on the counter
        assert by_phase[want["phase"]] == f["device_starved_s"] > 0
        assert clock._registry.counter(
            "device_starved_secs_total", phase=want["phase"]
        ).value == pytest.approx(f["device_starved_s"], abs=1e-5)
    # the first dispatch ran on an idle device: its start to its stamp is
    # a step and what the host added before it could read
    assert f["first_step_s"] >= 1.0
    assert f["first_step_late_s"] == pytest.approx(
        f["first_step_s"] - 1.0, abs=1e-5)
    assert f["device_wait_s"] + f["drain_device_wait_s"] == pytest.approx(
        sum(w for _, w, _ in clock.closed), abs=1e-5)
    assert len(clock.closed) == len(handed[0])


def test_the_host_behind_share_is_pinned():
    """docs/OBSERVABILITY.md states it: a read that waited under a
    twentieth of the epoch's median interval found the device done."""
    assert obs.StepClock.HOST_BEHIND_SHARE == 0.05
    # a stall that leaves the read waiting 6% of a step is the device's
    clock, handed, _ = timeline(
        feed=0.0, disp=0.0, book=0.0, stall=(3, "feed_next", 0.94))
    f = clock.epoch_fields(*handed)
    assert f["host_bound_steps"] == 0 and f["device_starved_s"] == 0
    # one that leaves 4% is the host's, though nothing was lost yet
    clock, handed, idle = timeline(
        feed=0.0, disp=0.0, book=0.0, stall=(3, "feed_next", 0.96))
    f = clock.epoch_fields(*handed)
    assert f["host_bound_steps"] == 1
    assert f["device_starved_s"] == pytest.approx(idle, abs=1e-6) and not idle


@pytest.mark.parametrize("case", ["no_reads", "a_read_short", "one_dispatch"])
def test_epoch_fields_without_a_whole_epoch_of_reads(case):
    clock, handed, _ = timeline(n=1 if case == "one_dispatch" else 4)
    if case == "no_reads":        # health off: nothing was ever stamped
        clock.closed = []
    elif case == "a_read_short":  # a rollback dropped the pending slot
        clock.closed = clock.closed[:-1]
    f = clock.epoch_fields(*handed)
    if case == "one_dispatch":
        # no interval, so no median: the wait and the first step alone
        assert set(f) == {"device_wait_s", "drain_device_wait_s",
                          "first_step_s"}
        assert f["device_wait_s"] == 0 and f["drain_device_wait_s"] > 0
    else:
        assert f == {}


# ------------------------------------------------------- a real Trainer
@jax.jit
def _device_work(x, n):
    """~0.3 ms an iteration on the CPU backend, dispatched asynchronously;
    ``n`` is traced, so a longer step is the same program."""
    y = jax.lax.fori_loop(0, n, lambda i, a: jnp.tanh(a @ a) * 0.5, x)
    return jnp.sum(y)


class ToySteps:
    """(train_step, multi_step) that advance ``state.step`` and take
    device time: ``work`` iterations a step, ``slow`` more in call
    ``slow_call`` of the epoch. ``issued`` keeps every call's metrics
    tree, in order."""

    def __init__(self, work=300, slow_call=None, slow=0):
        self.work, self.slow_call, self.slow = work, slow_call, slow
        self.x = jnp.ones((256, 256), jnp.float32)
        self.issued = []
        _device_work(self.x, 1).block_until_ready()

    def _loss(self, k=1):
        n = self.work * k + (self.slow if len(self.issued) ==
                             self.slow_call else 0)
        return _device_work(self.x, n) if n else jnp.float32(1.0)

    def train_step(self, state, batch):
        loss = self._loss()
        metrics = {"loss_g": loss, "loss_d": loss * 2.0}
        self.issued.append(metrics)
        return state.replace(step=state.step + 1), metrics

    def multi_step(self, state, batches):
        k = next(iter(batches.values())).shape[0]
        loss = self._loss(k)
        metrics = {"loss_g": jnp.full((k,), loss),
                   "loss_d": jnp.full((k,), loss * 2.0)}
        self.issued.append(metrics)
        return state.replace(step=state.step + k), metrics


def toy_trainer(tmp_path, steps, n_train=16, scan_steps=1, health=True):
    root = str(tmp_path / "ds")
    make_synthetic_dataset(root, n_train=n_train, n_test=2, size=16)
    cfg = get_preset("facades")
    cfg = cfg.replace(
        name="clock",
        model=dataclasses.replace(cfg.model, ngf=4, ndf=4),
        data=dataclasses.replace(cfg.data, batch_size=2, image_size=16,
                                 threads=0),
        train=dataclasses.replace(cfg.train, mixed_precision=False,
                                  scan_steps=scan_steps, log_every=1000),
        health=dataclasses.replace(cfg.health, enabled=health,
                                   spike_zscore=1e9),
    )
    tr = Trainer(cfg, data_root=root, workdir=str(tmp_path))
    tr.train_step = steps.train_step
    tr.multi_step = steps.multi_step if scan_steps > 1 else None
    return tr


def last_record(tr):
    return [s for s in tr.spans.spans if s["name"] == "train_epoch"][-1]


def test_record_fields_tile_and_reach_the_jsonl(tmp_path):
    """(a) ``host_s + device_wait_s`` is the three per-step phases' sum
    (the first ``feed_next`` is the epoch start's), one interval fewer
    than dispatches, every field in the JSONL's ``kind="span"`` line."""
    tr = toy_trainer(tmp_path, ToySteps(work=60))
    try:
        tr.train_epoch()
        rec = last_record(tr)
        assert rec["steps"] == 8
        assert CLOCK_FIELDS <= set(rec)
        per_step = (rec["feed_next_s"] - rec["first_feed_next_s"]
                    + rec["train_dispatch_s"] + rec["step_bookkeeping_s"])
        assert rec["host_s"] + rec["device_wait_s"] == pytest.approx(
            per_step, abs=1e-5)
        assert 0 <= rec["device_wait_s"] <= rec["step_bookkeeping_s"]
        assert 0 <= rec["drain_device_wait_s"] <= rec["epoch_drain_s"]
        assert 0 < rec["host_s"] and 0 < rec["cpu_s"]
        assert rec["gc_pause_s"] >= 0 and rec["compiles"] >= 0
        # five phases still tile the call: device_wait nests inside two
        children = sum(rec[f"{p}_s"] for p in (
            "epoch_setup", "feed_next", "train_dispatch",
            "step_bookkeeping", "epoch_drain"))
        assert 0.9 * rec["dur_s"] <= children <= rec["dur_s"]
        assert tr.obs.histogram("device_wait_secs").count == 8
        assert tr.obs.histogram("step_interval_secs").count == 7
        # a second epoch: no interval spans the boundary
        tr.epoch += 1
        tr.train_epoch()
        assert tr.obs.histogram("step_interval_secs").count == 14
        lines = [json.loads(x)
                 for x in open(tmp_path / "metrics_clock.jsonl")]
        spans = [r for r in lines if r.get("kind") == "span"]
        assert len(spans) == 2
        assert all(CLOCK_FIELDS <= set(r) for r in spans)
        assert spans[0]["slowest_step_interval_phase"] in (
            "device",) + obs.StepClock.HOST_PHASES
    finally:
        tr.close()


def test_a_sleeping_loader_names_itself(tmp_path, monkeypatch):
    """(b) the loader sleeps before batch 5: with two batches in the
    prefetch that is the ``feed_next`` at step 4. The device runs dry for
    the sleep less the step it was still busy with."""
    sleep, at = 0.6, 4
    real = loop_mod.make_loader

    def sleepy(*a, **kw):
        for i, batch in enumerate(real(*a, **kw)):
            if i == at + 1:
                time.sleep(sleep)
            yield batch

    monkeypatch.setattr(loop_mod, "make_loader", sleepy)
    tr = toy_trainer(tmp_path, ToySteps(work=150))
    try:
        tr.train_epoch()    # warm: Grain's start, the loop's first use
        tr.epoch += 1
        tr.train_epoch()
        rec = last_record(tr)
        median = rec["step_interval_median_s"]
        assert median < sleep / 3
        assert rec["slowest_feed_next_step"] == at
        assert rec["host_bound_steps"] >= 1
        assert sleep - 3 * median <= rec["device_starved_s"] <= sleep + 0.2
        assert rec["starved_in_feed_next_s"] == pytest.approx(
            rec["device_starved_s"], abs=0.03)
        assert rec["slowest_step_interval_step"] == at
        assert rec["slowest_step_interval_phase"] == "feed_next"
        # as above: what was queued when the loader fell asleep still ran
        assert rec["slowest_step_interval_s"] >= sleep - 3 * median
        assert tr.obs.counter(
            "device_starved_secs_total", phase="feed_next"
        ).value >= rec["starved_in_feed_next_s"]
    finally:
        tr.close()


def test_a_slow_device_step_is_not_the_hosts(tmp_path):
    """(c) one call of the step takes longer ON THE DEVICE (more trips of
    the same compiled loop): the host waits in ``device_wait`` throughout,
    so nothing is called starved and the interval names ``device``."""
    steps = ToySteps(work=150, slow_call=5, slow=1500)
    tr = toy_trainer(tmp_path, steps)
    try:
        tr.train_epoch()
        tr.epoch += 1
        steps.issued.clear()
        tr.train_epoch()
        rec = last_record(tr)
        median = rec["step_interval_median_s"]
        assert rec["slowest_step_interval_phase"] == "device"
        assert rec["slowest_step_interval_step"] == 5
        assert rec["slowest_step_interval_s"] > 3 * median
        assert rec["host_bound_steps"] == 0
        assert rec["device_starved_s"] == 0
        assert rec["device_slow_s"] >= rec["slowest_step_interval_s"] - median
    finally:
        tr.close()


def test_a_scanned_dispatch_divides_by_its_steps(tmp_path, monkeypatch):
    """(d) ``scan_steps`` 2 over 8 batches: 4 dispatches, 3 intervals, each
    divided by the dispatch's two steps. Held against the SAME run's
    undivided stamps (a dispatch's start, each read's return), not against
    ratios of wall-clock spans: those did not hold on a loaded machine."""
    tr = toy_trainer(tmp_path, ToySteps(work=150), scan_steps=2)
    handed = []
    fields = tr.step_clock.epoch_fields

    def epoch_fields(dispatches, feeds, drain_t0):
        handed.append(dispatches)
        return fields(dispatches, feeds, drain_t0)

    monkeypatch.setattr(tr.step_clock, "epoch_fields", epoch_fields)
    try:
        tr.train_epoch()
        tr.epoch += 1
        tr.train_epoch()
        rec = last_record(tr)
        assert rec["steps"] == 8
        assert tr.obs.histogram("device_wait_secs").count == 8
        assert tr.obs.histogram("step_interval_secs").count == 6
        # the epoch's four reads, each of a dispatch of two steps
        done = tr.step_clock.closed
        assert [k for _, _, k in done] == [2, 2, 2, 2]
        dispatches = handed[-1]
        assert [at for at, _, _ in dispatches] == [0, 2, 4, 6]
        # completion to completion, a DISPATCH: two steps each
        undivided = [b[0] - a[0] for a, b in zip(done, done[1:])]
        assert all(span > 0 for span in undivided)
        assert rec["step_interval_median_s"] == pytest.approx(
            statistics.median(undivided) / 2, abs=2e-6)
        assert rec["slowest_step_interval_s"] <= max(undivided) / 2 + 2e-6
        # the first dispatch's start to its completion: two steps too
        first = done[0][0] - dispatches[0][1]
        assert rec["first_step_s"] == pytest.approx(first / 2, abs=2e-6)
        # those spans lie inside the epoch, one after the other
        assert first + sum(undivided) <= rec["dur_s"]
    finally:
        tr.close()


def test_without_the_health_queue_the_record_has_no_clock(tmp_path):
    """(e) no delayed read, so no view of completion, and no fence is
    invented for one."""
    tr = toy_trainer(tmp_path, ToySteps(work=0), health=False)
    try:
        assert tr.health is None
        tr.train_epoch()
        rec = last_record(tr)
        assert rec["steps"] == 8 and "step_bookkeeping_s" in rec
        assert not CLOCK_FIELDS & set(rec)
        assert tr.obs.histogram("device_wait_secs").count == 0
        assert tr.obs.histogram("step_interval_secs").count == 0
    finally:
        tr.close()


def test_close_removes_the_collectors_hook(tmp_path):
    """(f) a closed Trainer no longer counts the process's collections."""
    tr = toy_trainer(tmp_path, ToySteps(work=0))
    hook = tr.gc_pauses._on_gc
    try:
        assert gc.callbacks.count(hook) == 1
        before = tr.gc_pauses.seconds
        gc.collect()
        assert tr.gc_pauses.seconds > before
    finally:
        tr.close()
    assert hook not in gc.callbacks
    other = obs.GcPauseMeter()
    other.install()
    try:
        closed_at = tr.gc_pauses.seconds
        gc.collect()
        assert other.seconds > 0
        assert tr.gc_pauses.seconds == closed_at
    finally:
        other.remove()
    assert other._on_gc not in gc.callbacks
    tr.close()  # idempotent


@pytest.mark.parametrize("epochs", [1, 3])
def test_the_first_epoch_settles_the_collector(tmp_path, epochs):
    """(g) once a run, after its first epoch: one full collection, what
    set-up built frozen out of every later one, one ``kind="gc_settle"``
    line; the collection lies outside every epoch's ``gc_pause_s``; a
    closed Trainer thaws."""
    frozen_before = gc.get_freeze_count()
    tr = toy_trainer(tmp_path, ToySteps(work=0))
    try:
        assert gc.get_freeze_count() == frozen_before
        for _ in range(epochs):
            tr.train_epoch()
            tr.epoch += 1
        assert gc.get_freeze_count() > frozen_before + 10_000
        lines = [json.loads(x)
                 for x in open(tmp_path / "metrics_clock.jsonl")]
        settles = [r for r in lines if r.get("kind") == "gc_settle"]
        assert len(settles) == 1 and settles[0]["sec"] > 0
        spans = [r for r in lines if r.get("kind") == "span"]
        assert len(spans) == epochs
        # a frozen heap: a full collection now walks the young objects only
        assert all(r["gc_pause_s"] < settles[0]["sec"] for r in spans[1:])
    finally:
        tr.close()
    assert gc.get_freeze_count() == 0
    tr.close()  # idempotent: thaws once


def test_the_clock_adds_no_fence_and_reads_one_dispatch_late(
        tmp_path, monkeypatch):
    """(g) the read of dispatch ``j`` comes after dispatch ``j + 1`` was
    issued, fetches the very tree the step returned, and nothing else of
    the loop waits for the device before the epoch's drain."""
    steps = ToySteps(work=0)
    tr = toy_trainer(tmp_path, steps)
    fetched = []
    real_get = jax.device_get

    def device_get(tree):
        fetched.append((tree, len(steps.issued)))
        return real_get(tree)

    def no_fence(*a, **kw):
        raise AssertionError("the loop fenced the device")

    monkeypatch.setattr(jax, "device_get", device_get)
    monkeypatch.setattr(jax, "block_until_ready", no_fence)
    try:
        tr.train_epoch()
    finally:
        tr.close()
    n = len(steps.issued)
    assert n == 8
    # one read a dispatch, then the epoch's loss sums: as before the clock
    assert len(fetched) == n + 1
    for j, (tree, issued) in enumerate(fetched[:n]):
        assert tree is steps.issued[j]
        assert issued == min(j + 2, n)
    stamps = [t for t, _, _ in tr.step_clock.closed]
    assert len(stamps) == n and stamps == sorted(stamps)
