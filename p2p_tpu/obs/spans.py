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
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional

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
