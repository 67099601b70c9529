"""Runtime watchdogs: unexpected-recompile detection and HBM sampling.

RetraceWatchdog
    A silent recompile mid-training is the classic JAX perf bug: a shape or
    dtype wobble (an odd tail batch reaching the scanned path, a python
    float flipping a weak dtype) recompiles a minute-scale XLA program and
    the step time graph grows a mystery cliff. The watchdog listens to
    ``jax.monitoring``'s backend-compile duration events (process-wide —
    every jit, pjit, and pallas call funnels through them); after ``arm()``
    (call it once warmup compiles are done, e.g. after the first epoch)
    any further compile is counted, logged as a ``kind="retrace"`` record,
    and printed.

MemoryWatchdog
    Samples ``Device.memory_stats()`` per local device into gauges — the
    HBM fill/peak numbers that tell you how close a preset is to the OOM
    cliff. CPU backends report nothing; ``sample()`` returns {} there.
"""

from __future__ import annotations

from typing import Any, Dict

import jax

# Fires once per XLA backend compile (present on the CPU and TPU runtimes
# of the installed jax).
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# Persistent-compilation-cache outcome events (jax/_src/compiler.py): one
# per backend-compile request once a cache dir is set (core/cache.py).
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


class RetraceWatchdog:
    """Count backend compiles; warn on any that happen after ``arm()``.

    Also counts persistent-compilation-cache hits/misses (``cache_hits`` /
    ``cache_misses`` attributes + ``persistent_cache_hits``/``_misses``
    registry counters) when the cache is enabled — a fleet that silently
    stopped hitting its cache is a cold-start regression the metrics
    stream should show."""

    def __init__(self, registry=None, logger=None):
        self.registry = registry
        self.logger = logger            # optional MetricsLogger for records
        self.compiles = 0               # total since construction
        self.unexpected = 0             # compiles seen while armed
        self.cache_hits = 0             # persistent-cache loads (no compile)
        self.cache_misses = 0           # persistent-cache misses (compiled)
        self.armed = False
        # jax 0.9's public monitoring API (register + unregister by
        # callback); a jax that moves it fails here, loudly
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        jax.monitoring.register_event_listener(self._on_plain_event)
        self._registered = True

    # NOTE: listener signature is (event, duration, **kwargs) in the pinned
    # jax; absorb extras so minor-version drift doesn't raise in a callback.
    def _on_event(self, event: str, duration: float, **kw) -> None:
        if event != _COMPILE_EVENT:
            return
        self.compiles += 1
        reg = self.registry
        if reg is not None:
            reg.histogram("xla_compile_secs").observe(duration)
        if self.armed:
            self.unexpected += 1
            if reg is not None:
                reg.counter("unexpected_recompiles").inc()
            rec = {"kind": "retrace", "compile_secs": round(duration, 3),
                   "n_unexpected": self.unexpected}
            if self.logger is not None:
                try:
                    self.logger.log(rec, force=True)
                except Exception:
                    pass
            print(f"WARNING: unexpected XLA recompile "
                  f"#{self.unexpected} ({duration:.2f}s) — check for "
                  "shape/dtype wobble in the input pipeline", flush=True)

    def _on_plain_event(self, event: str, **kw) -> None:
        """Counter-style monitoring events (no duration): the persistent
        compilation cache's hit/miss stream."""
        if event == _CACHE_HIT_EVENT:
            self.cache_hits += 1
            if self.registry is not None:
                self.registry.counter("persistent_cache_hits").inc()
        elif event == _CACHE_MISS_EVENT:
            self.cache_misses += 1
            if self.registry is not None:
                self.registry.counter("persistent_cache_misses").inc()

    def arm(self) -> None:
        """Call once expected warmup compiles are done; later compiles are
        flagged as unexpected."""
        self.armed = True

    def disarm(self) -> None:
        self.armed = False

    def close(self) -> None:
        """Unhook the process-global listeners (safe to call twice)."""
        if self._registered:
            jax.monitoring.unregister_event_duration_listener(self._on_event)
            jax.monitoring.unregister_event_listener(self._on_plain_event)
            self._registered = False


class MemoryWatchdog:
    """Per-device HBM statistics into gauges + a ``kind="memory"`` record."""

    def __init__(self, registry=None):
        self.registry = registry

    def sample(self, logger=None) -> Dict[str, Dict[str, Any]]:
        out: Dict[str, Dict[str, Any]] = {}
        for d in jax.local_devices():
            try:
                stats = d.memory_stats()
            except Exception:
                stats = None
            if not stats:
                continue
            keep = {
                k: int(v) for k, v in stats.items()
                if k in ("bytes_in_use", "peak_bytes_in_use",
                         "bytes_limit", "largest_alloc_size")
            }
            if not keep:
                continue
            out[str(d.id)] = keep
            if self.registry is not None:
                for k, v in keep.items():
                    self.registry.gauge(f"hbm_{k}", device=d.id).set(v)
        if out and logger is not None:
            worst = max(out.values(),
                        key=lambda s: s.get("bytes_in_use", 0))
            logger.log({"kind": "memory", "n_devices": len(out), **worst},
                       force=True)
        return out


#: tolerated |live − static| / static before the startup cross-check
#: warns — past this the static memory model (memory_budget.json) has
#: rotted relative to what the runtime actually allocates
HBM_BUDGET_DRIFT = 0.10


def budget_drift(live_bytes: int, static_bytes: int,
                 tolerance: float = HBM_BUDGET_DRIFT):
    """``(drift_fraction, out_of_band)`` for a live-vs-static byte pair —
    the pure comparison behind :func:`crosscheck_hbm_budget`, unit-tested
    without a TPU."""
    if static_bytes <= 0:
        return 0.0, False
    drift = abs(int(live_bytes) - int(static_bytes)) / float(static_bytes)
    return drift, drift > tolerance


def crosscheck_hbm_budget(cfg, mesh, registry=None, logger=None,
                          samples=None, extra_bytes: int = 0):
    """Startup cross-check (ISSUE 15): the live per-host HBM fill
    (``Device.memory_stats``) against the static ``memory_budget.json``
    state law (``analysis/memory_audit.state_budget`` over the SAME rule
    tables the trainer placed the state with). Call right after state
    placement, before the first step compiles — at that point the device
    holds essentially the TrainState, so live-vs-static is a direct test
    of the static model.

    ``extra_bytes`` covers device residents the state law does not model
    (the trainer passes its VGG feature tree — loaded before this check
    runs, so it is part of the honest baseline, not drift).

    Writes a ``kind="hbm_budget"`` record with both; WARNS (and counts
    ``hbm_budget_drift_total``) past :data:`HBM_BUDGET_DRIFT`. Returns
    the record, or None on backends that report no memory stats (CPU
    CI)."""
    if samples is None:
        samples = MemoryWatchdog(registry).sample()
    if not samples:
        return None          # CPU/test backend: nothing to cross-check
    from p2p_tpu.analysis.memory_audit import state_budget

    sizes = {str(a): int(s) for a, s in dict(mesh.shape).items()} \
        if mesh is not None else {}
    static = state_budget(cfg, sizes, tp_min_ch=cfg.parallel.tp_min_ch,
                          fsdp_params=cfg.parallel.fsdp_params)
    expected = int(static["state_total"]) + int(extra_bytes)
    live = max(int(s.get("bytes_in_use", 0)) for s in samples.values())
    drift, out_of_band = budget_drift(live, expected)
    rec = {"kind": "hbm_budget", "static_state_bytes": expected,
           "extra_bytes": int(extra_bytes),
           "live_bytes_in_use": live, "drift": round(drift, 4),
           "out_of_band": out_of_band, "mesh": sizes}
    if registry is not None and out_of_band:
        registry.counter("hbm_budget_drift_total").inc()
    if logger is not None:
        logger.log(rec, force=True)
    if out_of_band:
        print(f"WARNING: live HBM {live / (1 << 20):.1f} MiB vs static "
              f"state budget {expected / (1 << 20):.1f} MiB — "
              f"{drift * 100:.1f}% drift (> {HBM_BUDGET_DRIFT * 100:.0f}%)"
              " — the static memory model (memory_budget.json law) no "
              "longer matches the runtime; re-derive it before trusting "
              "budget rows", flush=True)
    return rec
