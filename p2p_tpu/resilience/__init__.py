"""Fault-tolerance subsystem — preemption, retry/backoff, chaos, shedding.

The production stance (docs/RESILIENCE.md): preemption and transient
faults are the COMMON case on preemptible TPU fleets, so recovery is a
first-class layer wired through train, data, serve, and obs rather than
an afterthought per call site. Four pillars:

- :mod:`.preempt` — SIGTERM/SIGINT → flag → step-boundary exact-step
  checkpoint, agreed across hosts; the distinct
  :data:`~p2p_tpu.resilience.preempt.PREEMPTED_EXIT_CODE` (75) means
  "resume me".
- :mod:`.retry` — exponential backoff + full jitter with exception
  classification and deadlines, wrapped around checkpoint I/O and image
  decode.
- :mod:`.chaos` — config/env-driven fault injection (``P2P_CHAOS``) at
  those same seams, so tests and CI exercise the recovery paths on
  purpose.
- :mod:`.queue` — serve hardening: bounded request queue with load
  shedding, per-request deadlines, poison-input quarantine.
- :mod:`.health` — self-healing training: divergence sentinel (EWMA +
  robust z-score over the step losses) → bounded recovery ladder (skip →
  LR cooldown → rollback to the last eval-validated checkpoint) →
  :data:`~p2p_tpu.resilience.health.DIVERGED_EXIT_CODE` (76) when the
  ladder is exhausted; plus checkpoint integrity verification and the
  EMA generator (train/checkpoint.py, train/step.py).
- :mod:`.reshape` — restore-time state migration: the elastic
  ``migrate`` verdict's transform chain (batch re-basing from cumulative
  samples, pipe-width trunk restructuring, closed-form TP amax
  re-calibration, opt-in dtype cast), executed by ``elastic_restore``
  from both trainers' ``maybe_resume``.

Everything counts through the PR-1 obs registry: ``preemptions_total``,
``retry_attempts_total``/``retry_exhausted_total``,
``chaos_injected_total``, ``serve_shed_total``,
``serve_deadline_expired_total``, ``serve_quarantined_total``,
``health_spikes_total``/``health_skips_total``/``health_cooldowns_total``/
``health_rollbacks_total``, ``ckpt_corrupt_total``.
"""

from p2p_tpu.resilience.chaos import (
    ChaosMonkey,
    FaultInjected,
    chaos_point,
    get_chaos,
    install as install_chaos,
    parse_spec,
)
from p2p_tpu.resilience.health import (
    DIVERGED_EXIT_CODE,
    DivergenceError,
    DivergenceSentinel,
    RecoveryLadder,
    TrainingHealth,
)
from p2p_tpu.resilience.preempt import (
    PREEMPTED_EXIT_CODE,
    Preempted,
    PreemptionGuard,
)
from p2p_tpu.resilience.queue import BoundedRequestQueue, Quarantine, Request
from p2p_tpu.resilience.retry import (
    CKPT_POLICY,
    DEFAULT_POLICY,
    RetryPolicy,
    retry_call,
    retrying,
)

__all__ = [
    "BoundedRequestQueue",
    "CKPT_POLICY",
    "ChaosMonkey",
    "DEFAULT_POLICY",
    "DIVERGED_EXIT_CODE",
    "DivergenceError",
    "DivergenceSentinel",
    "FaultInjected",
    "RecoveryLadder",
    "TrainingHealth",
    "PREEMPTED_EXIT_CODE",
    "Preempted",
    "PreemptionGuard",
    "Quarantine",
    "Request",
    "RetryPolicy",
    "chaos_point",
    "get_chaos",
    "install_chaos",
    "parse_spec",
    "retry_call",
    "retrying",
]
