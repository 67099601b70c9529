"""Fault injection — probabilistic or step-targeted failures at named seams.

Production training stacks treat transient faults (preemptions, flaky
storage, torn uploads) as the common case; the only way to trust the
recovery paths in :mod:`p2p_tpu.resilience` is to fire them on purpose.
This module plants *chaos points* at the seams the retry/backoff layer
wraps — checkpoint save/restore, image decode, serve output writes — and
arms them from a config string or the ``P2P_CHAOS`` environment variable,
so a test or a CI stage can make those seams fail on demand.

Spec grammar (comma-separated entries)::

    ckpt_save:0.5        fail seam 'ckpt_save' with probability 0.5
    decode@7             fail seam 'decode' exactly at "step" 7
    ckpt_save:0.5x3      as above, but at most 3 injected faults total
    nan@50x3             fail seam 'nan' at steps 50, 51 and 52
    decode:0.2x1,ckpt_save@12

``seam@N`` compares against the step the seam reports (checkpoint seams
pass the train step); seams with no step concept (decode, serve_write)
fall back to their OWN call count, so ``decode@7`` means "the 7th decode
of this process" — targeted injection works at every seam. A step-
targeted entry's ``xM`` cap widens the target to the RANGE [N, N+M):
``nan@50x3`` fires at steps 50..52 — the shape the recovery-ladder
rehearsals need (one injection per rung). Repeated calls at the same
step (a retry loop) still consume the cap one fault at a time.

Seam names in use: ``ckpt_save``, ``ckpt_restore``, ``decode``,
``serve_write``, ``nan`` (train-loop loss poisoning — the divergence
sentinel's rehearsal hook, train/loop.py), ``ckpt_corrupt`` (simulated
checksum mismatch at restore-verify, train/checkpoint.py). Unknown names
are legal (a chaos point is just a string), so new seams need no
registry changes.

Every injected fault raises :class:`FaultInjected` (classified retryable
by the default :class:`~p2p_tpu.resilience.retry.RetryPolicy`) and bumps
the ``chaos_injected_total{seam=...}`` counter on the obs registry —
injected faults are never silent.

The happy path stays free: :func:`chaos_point` is a no-op returning after
one global check when nothing is armed.
"""

from __future__ import annotations

import dataclasses
import os
import random
import threading
from typing import Dict, Optional

_ENV_VAR = "P2P_CHAOS"
_ENV_SEED_VAR = "P2P_CHAOS_SEED"


class FaultInjected(RuntimeError):
    """A fault planted by the chaos layer (always retryable)."""

    def __init__(self, seam: str, step: Optional[int] = None):
        self.seam = seam
        self.step = step
        at = f" at step {step}" if step is not None else ""
        super().__init__(f"chaos: injected fault at seam {seam!r}{at}")


@dataclasses.dataclass
class SeamSpec:
    """Arming rule for one seam."""

    prob: float = 0.0                 # per-call failure probability
    at_step: Optional[int] = None     # fire exactly when step == at_step
    max_faults: Optional[int] = None  # stop injecting after this many
    fired: int = 0                    # injected so far (mutable)
    calls: int = 0                    # chaos-point hits (the @N fallback)


_ENTRY_RE = None  # compiled lazily (module import stays re-free)

#: Seams that short-circuit a cross-host agreement protocol and therefore
#: MUST fire on every host at the same step: the ``elastic`` seam converts
#: straight into ``PreemptionGuard.request`` + an immediate stop WITHOUT
#: the allgather cadence (train/loop.py poll_preempt) — that is only safe
#: because a step-pinned ``elastic@N`` fires on every host's Nth dispatch.
#: A probabilistic ``elastic:p`` draws from each process's own RNG stream
#: (whose position depends on that host's other seam traffic), so one host
#: would stop while the rest march into the next agreement collective and
#: hang — the exact bug class the collective-consistency lint exists for
#: (p2p_tpu/analysis/collective_consistency.py).
_STEP_PINNED_SEAMS = frozenset({"elastic"})


def parse_spec(spec: str) -> Dict[str, SeamSpec]:
    """Parse the spec grammar above into ``{seam: SeamSpec}``."""
    import re

    global _ENTRY_RE
    if _ENTRY_RE is None:
        _ENTRY_RE = re.compile(
            r"^(?P<seam>[^:@]+?)"
            r"(?::(?P<prob>[0-9.eE+\-]+)|@(?P<step>\d+))?"
            r"(?:x(?P<cap>\d+))?$"
        )
    out: Dict[str, SeamSpec] = {}
    for entry in spec.split(","):
        entry = entry.strip()
        if not entry:
            continue
        m = _ENTRY_RE.match(entry)
        if m is None:
            raise ValueError(f"bad chaos entry {entry!r}")
        seam = m.group("seam").strip()
        cap = int(m.group("cap")) if m.group("cap") else None
        if seam in _STEP_PINNED_SEAMS and m.group("step") is None:
            raise ValueError(
                f"chaos seam {seam!r} must be step-pinned (use "
                f"'{seam}@N' or '{seam}@NxM'): a probabilistic spec "
                "fires on a per-host RNG draw, so one host preempts "
                "while the others hang in the next agreement collective "
                f"(bad entry: {entry!r})")
        if m.group("step") is not None:
            out[seam] = SeamSpec(at_step=int(m.group("step")),
                                 max_faults=cap if cap else 1)
        elif m.group("prob") is not None:
            p = float(m.group("prob"))
            if not 0.0 <= p <= 1.0:
                raise ValueError(f"chaos probability out of [0,1]: {entry!r}")
            out[seam] = SeamSpec(prob=p, max_faults=cap)
        else:
            # bare seam name = always fail (prob 1), once unless capped
            out[seam] = SeamSpec(prob=1.0, max_faults=cap if cap else 1)
    if not out:
        raise ValueError(f"empty chaos spec {spec!r}")
    return out


class ChaosMonkey:
    """Armed fault-injection state: seams + a seeded RNG + fired counts."""

    def __init__(self, seams: Dict[str, SeamSpec], seed: int = 0,
                 registry=None):
        self.seams = seams
        self._rng = random.Random(seed)
        self._registry = registry
        self._lock = threading.Lock()

    @classmethod
    def from_spec(cls, spec: str, seed: int = 0, registry=None) -> "ChaosMonkey":
        return cls(parse_spec(spec), seed=seed, registry=registry)

    def _reg(self):
        if self._registry is None:
            from p2p_tpu.obs import get_registry

            self._registry = get_registry()
        return self._registry

    def counts(self) -> Dict[str, int]:
        return {name: s.fired for name, s in self.seams.items()}

    def maybe_fail(self, seam: str, step: Optional[int] = None) -> None:
        s = self.seams.get(seam)
        if s is None:
            return
        with self._lock:
            s.calls += 1
            if s.max_faults is not None and s.fired >= s.max_faults:
                return
            if s.at_step is not None:
                # seams that report no step (decode, serve_write) target
                # by their own call count, so seam@N works everywhere;
                # the xM cap widens the target to the range [N, N+M) —
                # one injection per step for ladder rehearsals (same-step
                # retries still drain the cap fault by fault)
                at = step if step is not None else s.calls
                span = s.max_faults if s.max_faults is not None else 1
                if not (s.at_step <= at < s.at_step + span):
                    return
            elif not (s.prob > 0.0 and self._rng.random() < s.prob):
                return
            s.fired += 1
        self._reg().counter("chaos_injected_total", seam=seam).inc()
        raise FaultInjected(seam, step)


_active: Optional[ChaosMonkey] = None
_env_checked = False
_lock = threading.Lock()


def install(monkey: Optional[ChaosMonkey]) -> Optional[ChaosMonkey]:
    """Arm ``monkey`` process-wide (None disarms); returns the previous one.
    Also resets the env latch so a later ``P2P_CHAOS`` change can re-arm."""
    global _active, _env_checked
    with _lock:
        prev = _active
        _active = monkey
        _env_checked = monkey is not None
        return prev


def get_chaos() -> Optional[ChaosMonkey]:
    _maybe_arm_from_env()
    return _active


def _maybe_arm_from_env() -> None:
    """One-time check of ``P2P_CHAOS`` — arms the process on first use so
    subprocesses (CLI runs, CI stages) opt in purely through the env."""
    global _active, _env_checked
    if _env_checked:
        return
    with _lock:
        if _env_checked:
            return
        _env_checked = True
        spec = os.environ.get(_ENV_VAR)
        if spec:
            _active = ChaosMonkey.from_spec(
                spec, seed=int(os.environ.get(_ENV_SEED_VAR, "0")))


def chaos_point(seam: str, step: Optional[int] = None) -> None:
    """Mark a fault-injectable seam. No-op unless a :class:`ChaosMonkey`
    is armed (via :func:`install` or ``P2P_CHAOS``); armed, it may raise
    :class:`FaultInjected` per that seam's spec."""
    _maybe_arm_from_env()
    m = _active
    if m is not None:
        m.maybe_fail(seam, step)
