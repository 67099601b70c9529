"""Span tracing: wall-clock phases paired with device-trace annotations.

A span is a named host-side interval (epoch, eval, checkpoint). Each
``span(...)`` does three things at once:

1. times the block on the host clock and keeps the (name, ts, dur, depth)
   tuple in a :class:`SpanRecorder` ring;
2. enters a ``jax.profiler.TraceAnnotation`` so the same name shows up on
   the device timeline when a ``trace()`` capture is running;
3. optionally emits a ``kind="span"`` record into a registry (→ JSONL).

:meth:`SpanRecorder.export_perfetto` writes the collected spans as a
Chrome-trace JSON that https://ui.perfetto.dev loads directly — the
host-side complement of the XPlane trace ``trace()`` captures.
"""

from __future__ import annotations

import contextlib
import gc
import json
import os
import statistics
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax

# Bound at import: span timing must not be hijacked when a test (or tool)
# monkeypatches time.perf_counter to drive the TRAIN LOOP's accounting
# clock (tests/test_loop.py's FakeClock patches the module attribute,
# which is global) — spans would otherwise consume fake ticks and skew
# the loop's hand-computed throughput traces.
_perf_counter = time.perf_counter
_wall_clock = time.time


@contextlib.contextmanager
def trace(logdir: str):
    """Capture a device+host ``jax.profiler`` trace for the enclosed block
    (XPlane; view in TensorBoard/XProf or convert for Perfetto)."""
    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


class timed_annotation:
    """The hot-path span: a ``TraceAnnotation`` of ``name`` (so the name
    lands on the profiler's clock beside the device ops whenever a capture
    runs) plus an optional histogram observation (so the number exists
    when none does), and NO entry in a recorder ring — a per-step span
    there would flood the exported trace. After the block ``t0`` holds
    its start on the span clock and ``secs`` its duration; the trainers
    sum those into the epoch record."""

    __slots__ = ("_annotation", "_histogram", "t0", "secs")

    def __init__(self, name: str, histogram=None):
        self._annotation = jax.profiler.TraceAnnotation(name)
        self._histogram = histogram
        self.t0 = self.secs = 0.0

    def __enter__(self):
        self.t0 = _perf_counter()
        self._annotation.__enter__()
        return self

    def __exit__(self, *exc):
        self._annotation.__exit__(*exc)
        self.secs = _perf_counter() - self.t0
        if self._histogram is not None:
            self._histogram.observe(self.secs)
        return False


class GcPauseMeter:
    """Seconds the garbage collector held the interpreter, summed in
    ``seconds``: a ``gc.callbacks`` hook, which costs nothing between
    collections. The hook is process-global, so its owner removes it
    (``close_trainer_obs``) or it keeps counting for a run that is over."""

    def __init__(self):
        self.seconds = 0.0
        self._t0: Optional[float] = None

    def install(self) -> None:
        if self._on_gc not in gc.callbacks:
            gc.callbacks.append(self._on_gc)

    def remove(self) -> None:
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = _perf_counter()
        elif self._t0 is not None:
            self.seconds += _perf_counter() - self._t0
            self._t0 = None


class StepClock:
    """When each dispatch FINISHED, as the host learns it: the trainers'
    delayed read of a dispatch's metrics (``consume_health_observation``)
    blocks until the device is done with it, so the moment that read
    returns is a completion stamp that costs no fence and no device work.

    ``device_wait(k)`` wraps the read: a ``device_wait`` annotation, the
    ``device_wait_secs`` histogram, the stamp, and from the second stamp
    of an epoch on the ``step_interval_secs`` histogram (completion to
    completion, a step of the ``k`` the dispatch held). ``close_epoch()``
    hands the epoch's stamps over as ``closed``; ``epoch_fields`` turns
    them and the loop's own phase times into the epoch record's fields."""

    #: a read that waited less than this share of the epoch's median step
    #: interval (times its dispatch's steps) found the device already
    #: done: the host was behind (docs/OBSERVABILITY.md)
    HOST_BEHIND_SHARE = 0.05
    HOST_PHASES = ("feed_next", "train_dispatch", "step_bookkeeping")

    def __init__(self, registry):
        self._registry = registry
        self._wait_hist = registry.histogram("device_wait_secs")
        self._interval_hist = registry.histogram("step_interval_secs")
        # (done, waited, steps) of each dispatch read so far this epoch
        self._open: List[Tuple[float, float, int]] = []
        self.closed: List[Tuple[float, float, int]] = []

    @contextlib.contextmanager
    def device_wait(self, k: int):
        with timed_annotation("device_wait", self._wait_hist) as wait:
            yield
        done = wait.t0 + wait.secs
        if self._open:
            self._interval_hist.observe((done - self._open[-1][0]) / k)
        self._open.append((done, wait.secs, k))

    def close_epoch(self) -> None:
        self.closed, self._open = self._open, []

    def epoch_fields(self, dispatches: Sequence[Tuple[int, float, float]],
                     feeds: Sequence[float], drain_t0: float
                     ) -> Dict[str, Any]:
        """The closed epoch's fields for its record. ``dispatches`` holds
        ``(steps done before it, start, seconds)`` of each
        ``train_dispatch``, ``feeds`` the seconds of each ``feed_next``
        (the terminal one too where the feed ran out), ``drain_t0`` the
        start of ``epoch_drain``. Empty where the epoch's dispatches were
        not each read once (health off: there is no delayed read).

        The interval that ends with dispatch ``j``'s stamp holds the
        ``feed_next`` and ``train_dispatch`` of dispatch ``j + 1``, the
        wait for ``j`` and, as the rest, the host's bookkeeping on both
        sides of the read. Its excess over ``k`` median intervals is time
        the device did not spend on a step of the usual length: the
        host's where the read did not wait (it names the host phase whose
        own excess over its epoch median is largest, and the dispatch that
        was issued late), the device's where it did (the dispatch that
        took long). A stamp is never early, only late, and by as much as
        the intervals after it come out short, so that shortfall is taken
        off an excess before it counts: where the dispatch after was
        already queued the device lost less than a late read says, and a
        read that came BACK late lost it nothing. The slowest interval is
        the median plus the largest excess so left, a step."""
        done = self.closed
        n = len(dispatches)
        if not done or len(done) != n:
            return {}
        in_loop = sum(w for t, w, _ in done if t - w < drain_t0)
        first = (done[0][0] - dispatches[0][1]) / done[0][2]
        out: Dict[str, Any] = {
            "device_wait_s": in_loop,
            "drain_device_wait_s": sum(w for _, w, _ in done) - in_loop,
            "first_step_s": first,
        }
        if n > 1:
            out.update(self._interval_fields(done, dispatches, feeds))
            out["first_step_late_s"] = max(
                first - out["step_interval_median_s"], 0.0)
            for phase in self.HOST_PHASES:
                self._registry.counter(
                    "device_starved_secs_total", phase=phase
                ).inc(out[f"starved_in_{phase}_s"])
        return {name: round(v, 6) if isinstance(v, float) else v
                for name, v in out.items()}

    def _interval_fields(self, done, dispatches, feeds) -> Dict[str, Any]:
        # one entry an interval: the one that ends with done[j], j >= 1
        spans = [b[0] - a[0] for a, b in zip(done, done[1:])]
        waits = [w for _, w, _ in done[1:]]
        steps = [k for _, _, k in done[1:]]
        median = statistics.median(s / k for s, k in zip(spans, steps))
        # the host's phases inside it: those of dispatch j + 1 (after the
        # last dispatch: the terminal feed_next, no dispatch, the drain)
        phases = {
            "feed_next": (list(feeds[2:]) + [0.0])[:len(spans)],
            "train_dispatch": [d[2] for d in dispatches[2:]] + [0.0],
        }
        phases["step_bookkeeping"] = [
            s - w - f - d for s, w, f, d in zip(
                spans, waits, phases["feed_next"], phases["train_dispatch"])]
        usual = {p: statistics.median(v) for p, v in phases.items()}
        # steps the epoch had done when the dispatch at fault began: the
        # one issued late (after the last: the drain), the one that took long
        late_at = [d[0] for d in dispatches[2:]] + [
            dispatches[-1][0] + done[-1][2]]
        long_at = [d[0] for d in dispatches[1:]]
        host_bound = 0
        # [excess, steps, step at fault, phase] of each interval over its
        # medians. A stamp is never early, only late (the host came to
        # its read late, or the read came back late), and by as much as
        # the intervals after it then come out SHORT: that shortfall is
        # taken off the excess, and what is left is what the device lost
        over: List[list] = []
        for i, (span, k, wait) in enumerate(zip(spans, steps, waits)):
            excess = span - k * median
            if wait < self.HOST_BEHIND_SHARE * k * median:
                phase = max(self.HOST_PHASES,
                            key=lambda p: phases[p][i] - usual[p])
                at = late_at[i]
                host_bound += k
            else:
                phase, at = "device", long_at[i]
            if excess > 0:
                over.append([excess, k, at, phase])
            elif over:
                over[-1][0] = max(over[-1][0] + excess, 0.0)
        starved = dict.fromkeys(self.HOST_PHASES, 0.0)
        slow = 0.0
        slowest = (median, long_at[0], "device")
        for excess, k, at, phase in over:
            if phase == "device":
                slow += excess
            else:
                starved[phase] += excess
            if median + excess / k > slowest[0]:
                slowest = (median + excess / k, at, phase)
        return {
            "step_interval_median_s": median,
            "slowest_step_interval_s": slowest[0],
            "slowest_step_interval_step": slowest[1],
            "slowest_step_interval_phase": slowest[2],
            "host_bound_steps": host_bound,
            "device_starved_s": sum(starved.values()),
            "device_slow_s": slow,
            **{f"starved_in_{p}_s": secs for p, secs in starved.items()},
        }


class SpanRecorder:
    """Collects finished spans, bounded; the ring drops OLDEST first, so
    after a long run the exported trace shows the most recent window —
    the part you want when debugging a late-run slowdown."""

    def __init__(self, max_spans: int = 200_000):
        import collections

        self.max_spans = max_spans
        self.spans: Any = collections.deque(maxlen=max_spans)
        self._total = 0
        self._lock = threading.Lock()
        self._tls = threading.local()

    @property
    def dropped(self) -> int:
        return max(0, self._total - len(self.spans))

    def _depth(self) -> int:
        return getattr(self._tls, "depth", 0)

    @contextlib.contextmanager
    def span(self, name: str, registry=None, force: bool = False,
             histogram=None, **attrs):
        """Time the block; pair with a TraceAnnotation; record on exit.

        ``attrs`` (e.g. epoch=3) ride along into the span record and the
        optional registry record, and the block may add to them: they are
        the dict the ``with`` statement binds. ``histogram`` additionally
        receives the duration."""
        depth = self._depth()
        self._tls.depth = depth + 1
        ts = _wall_clock()
        t0 = _perf_counter()
        try:
            with jax.profiler.TraceAnnotation(name):
                yield attrs
        finally:
            dur = _perf_counter() - t0
            self._tls.depth = depth
            rec = {"name": name, "ts": ts, "dur_s": dur, "depth": depth,
                   **attrs}
            with self._lock:
                self.spans.append(rec)  # deque(maxlen): oldest falls out
                self._total += 1
            if histogram is not None:
                histogram.observe(dur)
            if registry is not None:
                registry.record(
                    {"kind": "span", "span": name, "sec": round(dur, 6),
                     **attrs},
                    force=force,
                )

    def export_perfetto(self, path: str) -> str:
        """Write the spans as Chrome-trace JSON (Perfetto-loadable).

        Complete events ("ph": "X") with microsecond wall-clock timestamps;
        nesting falls out of the ts/dur containment, matching the recorded
        depths."""
        pid = os.getpid()
        with self._lock:
            spans = list(self.spans)
            dropped = self.dropped
        events = [
            {
                "name": "process_name", "ph": "M", "pid": pid,
                "args": {"name": "p2p_tpu host spans"},
            }
        ]
        for s in spans:
            events.append({
                "name": s["name"], "ph": "X", "cat": "obs",
                "ts": int(s["ts"] * 1e6), "dur": max(int(s["dur_s"] * 1e6), 1),
                "pid": pid, "tid": 0,
                "args": {k: v for k, v in s.items()
                         if k not in ("name", "ts", "dur_s")},
            })
        doc = {"traceEvents": events, "displayTimeUnit": "ms"}
        if dropped:
            doc["p2p_tpu_dropped_spans"] = dropped
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f)
        os.replace(tmp, path)
        return path


_default_recorder: Optional[SpanRecorder] = None
_default_lock = threading.Lock()


def get_recorder() -> SpanRecorder:
    global _default_recorder
    with _default_lock:
        if _default_recorder is None:
            _default_recorder = SpanRecorder()
        return _default_recorder


def span(name: str, recorder: Optional[SpanRecorder] = None, registry=None,
         **attrs):
    """Module-level convenience: span on the process-default recorder."""
    return (recorder or get_recorder()).span(name, registry=registry, **attrs)
