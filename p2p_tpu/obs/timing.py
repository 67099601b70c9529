"""Fenced step timing — the one img/sec/chip definition.

:class:`StepTimer` measures wall-clock over FENCED step boundaries two
ways:

- ``tick()`` per step with ``block_until_ready`` on the metrics pytree —
  the loop-style API the seed had;
- ``chain()`` around K chained dispatches fenced ONCE by a host fetch at the
  end: the host never syncs per step,
  so the device queue stays full, and the one fence's own cost (a trivial
  dispatch + fetch, :func:`measure_rtt`) is subtracted. On the attached
  chip ``block_until_ready`` fences just as well; the host fetch is kept
  because its callers read the final result anyway.

Both paths feed the same accumulator, so ``images_per_sec`` means the same
thing in the serving engine's statistics and in the metrics stream.
"""

from __future__ import annotations

import contextlib
import time
from typing import Optional

import jax


_TRIVIAL = None


def measure_rtt() -> float:
    """Round-trip cost of one trivial jitted dispatch + host fetch — what
    ``chain()``'s single fence itself costs, subtracted from its fenced
    interval. The probe program is
    cached process-wide: repeated calls (the serving engine measures per
    run) must not recompile — a fresh lambda per call would both skew the
    first measurement and trip the retrace watchdog."""
    global _TRIVIAL
    import jax.numpy as jnp

    if _TRIVIAL is None:
        _TRIVIAL = jax.jit(lambda v: v + 1)
        float(_TRIVIAL(jnp.ones(())))  # compile outside the measured fetch
    else:
        float(_TRIVIAL(jnp.ones(())))  # warm transfer path
    t0 = time.perf_counter()
    float(_TRIVIAL(jnp.ones(())))
    return time.perf_counter() - t0


class _Chain:
    """Handle yielded by :meth:`StepTimer.chain`; call :meth:`fence` on a
    device value produced by the LAST dispatch to force the whole chained
    sequence before the timer stops."""

    def __init__(self):
        self.fenced = False

    def fence(self, value) -> None:
        import numpy as np

        np.asarray(jax.device_get(value))  # host fetch == reliable fence
        self.fenced = True


class StepTimer:
    """Wall-clock over fenced steps.

    Loop style (per-step fences):

    >>> t = StepTimer(batch_size=64)
    >>> for batch in data:
    ...     state, m = step(state, batch)
    ...     t.tick(m)           # fences on the metrics pytree
    >>> t.images_per_sec

    Chained style (one fence for K steps):

    >>> t = StepTimer(batch_size=64)
    >>> with t.chain(steps=K * n_calls, rtt=measure_rtt()) as ch:
    ...     for _ in range(n_calls):
    ...         state, m = step(state, batches)   # each consumes the last
    ...     ch.fence(m["loss_g"][-1])
    """

    def __init__(self, batch_size: int, skip_first: int = 1):
        self.batch_size = batch_size
        self.skip_first = skip_first       # warmup tick intervals to discard
        self.intervals = 0                 # timed step intervals
        self.elapsed = 0.0
        self._seen = 0
        self._t0: Optional[float] = None

    def tick(self, fence_on=None) -> None:
        if fence_on is not None:
            jax.block_until_ready(fence_on)
        now = time.perf_counter()
        if self._t0 is not None:
            self._seen += 1
            if self._seen > self.skip_first:
                self.elapsed += now - self._t0
                self.intervals += 1
        self._t0 = now

    @contextlib.contextmanager
    def chain(self, steps: int, rtt: float = 0.0):
        """Time a block of ``steps`` chained steps, fenced by the caller's
        ``ch.fence(...)`` host fetch (or, failing that, at exit — unfenced
        exits still measure dispatch time, but warn via the missing fence).
        The interval, minus ``rtt``, credits ``steps`` intervals."""
        ch = _Chain()
        t0 = time.perf_counter()
        try:
            yield ch
        finally:
            dt = time.perf_counter() - t0
            if not ch.fenced:
                print("WARNING: StepTimer.chain exited without a fence — "
                      "the measured interval may exclude device time",
                      flush=True)
            self.elapsed += max(dt - rtt, 1e-9)
            self.intervals += steps

    def credit(self, steps: int, seconds: float) -> None:
        """Account an externally-fenced interval (e.g. the serving
        engine's dispatch→drain window, already RTT-corrected) into the
        shared accumulator, so its img/sec is THIS definition too."""
        self.elapsed += max(seconds, 1e-9)
        self.intervals += steps

    @property
    def images_per_sec(self) -> float:
        if self.elapsed <= 0 or self.intervals <= 0:
            return 0.0
        return self.batch_size * self.intervals / self.elapsed
