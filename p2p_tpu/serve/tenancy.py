"""Multi-model tenancy — N presets/checkpoints resident in ONE serving
process, each with its own engine and bucket set, sharing the persistent
XLA compilation cache; zero-downtime hot-swap of checkpoint weights.

Why one process: the AOT bucket programs and the restore path are the
expensive parts of serving; a fleet that runs one model per process pays
them per model AND wastes idle accelerator time whenever traffic is
skewed. A :class:`Tenant` packages (config, checkpoint dir, engine,
restored step) behind a stable handle; :class:`ModelRegistry` is the
name→tenant map the HTTP router dispatches on.

Hot-swap (:meth:`Tenant.reload` — ``POST /admin/reload`` or the CLI):

1. params-only ``restore_subtree`` of the new step (the ~18%-of-bytes
   restore that makes reload cheap enough to do under live traffic);
2. the restored subtree is verified against the checkpoint's integrity
   manifest (``CheckpointManager.verify_integrity``) — a torn or
   bit-rotted upload is REJECTED (:class:`HotSwapRejected`) before it
   can replace live weights, and the old engine keeps serving;
3. EMA policy re-applied exactly as at construction (the smoothed
   generator swaps into ``params_g``);
4. ``InferenceEngine.swap_state``: placed on device, warmed against the
   ALREADY-compiled buckets (zero new compiles), then atomically
   swapped — in-flight requests finish on the old weights.

Counted per tenant: ``serve_hot_swaps_total`` /
``serve_hot_swap_rejected_total``, plus a ``kind="hot_swap"`` record.
"""

from __future__ import annotations

import os
import threading
from typing import Any, Dict, Iterator, Optional, Tuple

import jax
import numpy as np

from p2p_tpu.core.config import Config
from p2p_tpu.serve.engine import (
    engine_from_checkpoint,
    serving_restore_template,
)


class HotSwapRejected(RuntimeError):
    """A reload was refused and the OLD engine keeps serving — integrity
    mismatch, missing step, or an abstract-tree mismatch."""

    def __init__(self, tenant: str, step: Optional[int], reason: str):
        self.tenant = tenant
        self.step = step
        super().__init__(
            f"hot-swap rejected for tenant {tenant!r} (step {step}): "
            f"{reason}; the previous weights keep serving")


def checkpoint_dir(cfg: Config, workdir: str) -> str:
    """The trainer's checkpoint layout for ``cfg`` — the one path rule
    shared by cli/train, cli/infer, cli/serve and the tenancy layer."""
    return os.path.join(workdir, cfg.train.checkpoint_dir,
                        cfg.data.dataset, cfg.name)


def serving_sample_batch(cfg: Config) -> Dict[str, np.ndarray]:
    """The 1-image host batch a serving restore template is built from
    (shape/dtype only — values never matter)."""
    from p2p_tpu.utils.images import dummy_batch

    return dummy_batch(cfg)


class Tenant:
    """One resident model: config + checkpoint dir + a hot-swappable
    engine. Construction restores the newest (or pinned) step and
    AOT-warms every bucket; :meth:`reload` swaps weights under traffic.

    ``engine_kw`` passes through to :class:`InferenceEngine` (buckets,
    dtype, mesh, tp_min_ch, compilation_cache_dir, io_workers) —
    tenants sharing one ``compilation_cache_dir`` share compiled
    programs across restarts AND across tenants with identical
    model geometry."""

    def __init__(self, alias: str, cfg: Config, ckpt_dir: str,
                 step: Optional[int] = None, registry=None,
                 **engine_kw):
        if cfg.data.n_frames > 1:
            raise ValueError(
                f"tenant {alias!r}: serving covers image presets; video "
                "stays on cli/infer.py's clip path")
        self.alias = alias
        self.cfg = cfg
        self.ckpt_dir = ckpt_dir
        if registry is None:
            from p2p_tpu.obs import get_registry

            registry = get_registry()
        self.registry = registry
        self._sample_batch = serving_sample_batch(cfg)
        engine_kw.setdefault("with_metrics", False)
        self.engine, self.step = engine_from_checkpoint(
            cfg, ckpt_dir, self._sample_batch, step=step, **engine_kw)
        self._reload_lock = threading.Lock()
        self._swaps = registry.counter("serve_hot_swaps_total",
                                       tenant=alias)
        self._rejected = registry.counter("serve_hot_swap_rejected_total",
                                          tenant=alias)

    def warmup(self) -> "Tenant":
        self.engine.warmup()
        return self

    @property
    def swap_count(self) -> int:
        return int(self._swaps.value)

    def reload(self, step: Optional[int] = None) -> Dict[str, Any]:
        """Hot-swap to ``step`` (default: the newest on disk). Returns a
        summary dict; raises :class:`HotSwapRejected` (old weights keep
        serving) on a missing/corrupt/incompatible checkpoint. Serialized
        against concurrent reloads; NEVER blocks the serving path — the
        engine swap itself is one atomic reference write."""
        from p2p_tpu.train.checkpoint import CheckpointManager

        with self._reload_lock:
            mgr = CheckpointManager(self.ckpt_dir,
                                    registry=self.registry)
            try:
                target = mgr.latest_step() if step is None else int(step)
                if target is None:
                    self._rejected.inc()
                    raise HotSwapRejected(
                        self.alias, None,
                        f"no checkpoint under {self.ckpt_dir}")
                try:
                    template = serving_restore_template(
                        self.cfg, self._sample_batch)
                    state = mgr.restore_subtree(template, target)
                except (FileNotFoundError, OSError, ValueError) as e:
                    self._rejected.inc()
                    raise HotSwapRejected(
                        self.alias, target, f"restore failed: {e!r}"
                    ) from e
                if mgr.integrity_manifest(target) is None:
                    # a missing/torn sidecar is the MOST likely tear (the
                    # copy job died between the data files and the
                    # manifest) — "unverifiable" must not read as
                    # "intact" on the path that replaces live weights
                    self._rejected.inc()
                    raise HotSwapRejected(
                        self.alias, target,
                        "no readable integrity manifest for this step — "
                        "refusing to swap unverifiable weights")
                bad = mgr.verify_integrity(target, state)
                if bad:
                    self._rejected.inc()
                    raise HotSwapRejected(
                        self.alias, target,
                        "integrity manifest mismatch on "
                        + ", ".join(bad[:3])
                        + ("..." if len(bad) > 3 else ""))
            finally:
                mgr.close()
            if jax.tree_util.tree_leaves(state.ema_g):
                # same EMA policy as construction: serve the SMOOTHED
                # generator (engine_from_checkpoint's swap, verbatim)
                state = state.replace(params_g=state.ema_g, ema_g=None)
            prev = self.step
            try:
                self.engine.swap_state(state)
            except ValueError as e:
                self._rejected.inc()
                raise HotSwapRejected(self.alias, target, str(e)) from e
            self.step = target
            self._swaps.inc()
            self.registry.record(
                {"kind": "hot_swap", "tenant": self.alias,
                 "from_step": int(prev), "to_step": int(target)},
                force=True)
            return {"tenant": self.alias, "from_step": int(prev),
                    "step": int(target), "swapped": True}

    def status(self) -> Dict[str, Any]:
        """The /healthz block for this tenant."""
        e = self.engine
        return {"step": int(self.step), "buckets": list(e.buckets),
                "n_compiles": int(e.n_compiles),
                "swaps": self.swap_count}


class ModelRegistry:
    """Name → :class:`Tenant` map. Insertion-ordered; lookups are plain
    dict reads (safe against concurrent request threads — tenants are
    added before serving starts, engines swap internally)."""

    def __init__(self):
        self._tenants: Dict[str, Tenant] = {}

    def add(self, tenant: Tenant) -> Tenant:
        if tenant.alias in self._tenants:
            raise ValueError(f"duplicate tenant alias {tenant.alias!r}")
        self._tenants[tenant.alias] = tenant
        return tenant

    def get(self, alias: str) -> Tenant:
        return self._tenants[alias]

    def __contains__(self, alias: str) -> bool:
        return alias in self._tenants

    def __len__(self) -> int:
        return len(self._tenants)

    def items(self) -> Iterator[Tuple[str, Tenant]]:
        return iter(tuple(self._tenants.items()))

    def aliases(self) -> Tuple[str, ...]:
        return tuple(self._tenants)
