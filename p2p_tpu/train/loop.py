"""The training driver — epochs, eval, checkpoints, metrics.

Replaces the reference's train.py __main__ (SURVEY §3.1/§3.2): same
capability surface (alternating-GAN training, per-epoch PSNR/SSIM eval over
the test split with mean+max reporting and sample-image dumps, periodic
checkpoints, per-epoch LR schedule) minus its bugs (no-grad eval, correct
metric space, checkpoints that restore).

TPU structure: ONE jitted step per iteration, host code only moves batches
(via the double-buffered prefetcher) and logs; metrics come back as a small
dict so the device never syncs mid-epoch unless asked.

Telemetry goes through :mod:`p2p_tpu.obs`: the JSONL/stdout ``MetricsLogger``
(formerly defined here), a per-run manifest written at startup, wall-clock
spans exported as Perfetto JSON at the end of ``fit()``, a recompile
watchdog armed after the warmup epoch, and per-device HBM sampling.

Fault tolerance goes through :mod:`p2p_tpu.resilience`: ``fit()`` installs
a :class:`~p2p_tpu.resilience.PreemptionGuard` (SIGTERM/SIGINT → flag),
the dispatch loop polls it at step boundaries (cross-host agreed), and a
preemption saves an EXACT-STEP checkpoint — TrainState plus the
data-iterator sidecar (epoch, in-epoch batch position, aug seed) — then
raises :class:`~p2p_tpu.resilience.Preempted`, which ``cli/train.py``
turns into exit code 75. ``maybe_resume`` reverses it: a mid-epoch step
resumes its epoch at the exact next batch (``make_loader(skip_batches=)``)
so no sample is replayed or skipped — pinned bitwise-equal to an
uninterrupted run by tests/test_resilience.py.

Elastic relaunch (docs/RESILIENCE.md "Elastic relaunch"): the sidecar also
records the run's TOPOLOGY (process count, mesh axis sizes, global batch,
dtype policy); ``maybe_resume`` reconciles it against the relaunch's via
:func:`~p2p_tpu.core.mesh.classify_topology_delta` — a compatible delta
(different slice size, different data-axis width) restores RESHARDED onto
the new mesh with rule-derived target shardings (parallel/rules.py) and
re-derives every host's data-shard offset from the global step, so a
preemptible fleet can resume on whatever capacity the scheduler grants.
"""

from __future__ import annotations

import functools
import gc
import os
import signal
import time
from typing import Dict, List, Optional

import jax
import numpy as np

from p2p_tpu.core.config import Config
from p2p_tpu.core.mesh import local_batch_size, batch_sharding, make_mesh
from p2p_tpu.data.pipeline import PairedImageDataset, device_prefetch, make_loader
from p2p_tpu.models.registry import (
    generator_gauges,
    generator_trace_gauges,
    input_mask_channel,
)
from p2p_tpu.models.vgg import load_vgg19_params
from p2p_tpu.obs import (
    GcPauseMeter,
    MemoryWatchdog,
    MetricsLogger,
    RetraceWatchdog,
    SpanRecorder,
    StepClock,
    add_sentinel_handler,
    crosscheck_hbm_budget,
    timed_annotation,
    write_manifest,
)
from p2p_tpu.losses.perceptual import vgg_loss_traces
from p2p_tpu.ops.conv import conv_form_sites, reflect_pad_sites
from p2p_tpu.resilience import Preempted, PreemptionGuard
from p2p_tpu.resilience.chaos import FaultInjected, chaos_point
from p2p_tpu.resilience.health import DivergenceError
from p2p_tpu.train.checkpoint import CheckpointCorrupt, CheckpointManager
from p2p_tpu.train.schedules import PlateauController
from p2p_tpu.train.state import create_train_state
from p2p_tpu.train.step import build_eval_step, build_train_step
from p2p_tpu.utils.images import ingest, save_img


def init_trainer_obs(tr) -> None:
    """Shared telemetry wiring for both trainers (p2p_tpu.obs): run manifest
    + provenance record, span recorder + trace path, recompile/HBM
    watchdogs, the step clock (when each dispatch finished) with the
    collector's pauses, and sentinel-event routing into the run's metrics
    stream. ``tr`` needs cfg/workdir/mesh/logger/obs."""
    cfg = tr.cfg
    tr.spans = SpanRecorder()
    tr._trace_path = os.path.join(tr.workdir, f"trace_{cfg.name}.json")
    if jax.process_index() == 0:
        man = write_manifest(
            os.path.join(tr.workdir, f"manifest_{cfg.name}.json"),
            cfg, mesh=tr.mesh,
        )
        # one line of provenance into the metrics stream too, so a bare
        # JSONL names the config that produced it
        tr.logger.log(
            {"kind": "manifest", "config_hash": man["config_hash"],
             "git_sha": man["git_sha"], "backend": man["backend"]},
            force=True,
        )
    tr.retrace = RetraceWatchdog(registry=tr.obs, logger=tr.logger)
    tr.memwatch = MemoryWatchdog(registry=tr.obs)
    tr.step_clock = StepClock(tr.obs)
    tr.gc_pauses = GcPauseMeter()
    tr.gc_pauses.install()
    tr._sentinel_handler = None
    if cfg.debug.nan_sentinel:
        # route in-jit sentinel events (obs/taps.py) into this run's
        # metrics stream and count them on THIS run's registry (the
        # exporters snapshot tr.obs, not the process default). Capture
        # logger/obs, not tr — the handler must not pin the TrainState.
        logger, reg = tr.logger, tr.obs

        def _handler(ev):
            reg.counter("nonfinite_events", tag=ev.get("tag", "")).inc()
            logger.log(ev, force=True)

        tr._sentinel_handler = _handler
        add_sentinel_handler(_handler)
    # startup HBM cross-check (ISSUE 15): the state is placed but no step
    # has compiled yet, so live bytes_in_use ≈ TrainState + the already-
    # loaded VGG feature tree (extra_bytes — it precedes this check) —
    # the one moment the static memory_budget.json law is directly
    # observable. No-op on backends without memory stats (CPU CI); the
    # static law models image TrainStates only, so the video trainer
    # skips it.
    if cfg.data.n_frames <= 1:
        from p2p_tpu.train.state import tree_bytes

        vgg = getattr(tr, "vgg_params", None)
        crosscheck_hbm_budget(cfg, tr.mesh, registry=tr.obs,
                              logger=tr.logger,
                              extra_bytes=tree_bytes(vgg) if vgg else 0)
    # self-healing (resilience/health.py) rides the same wiring point:
    # both trainers get the sentinel + ladder when cfg.health.enabled
    init_trainer_health(tr)


def settle_collector(tr) -> None:
    """Once a run, after the epoch that compiled the step: collect what
    set-up left behind and FREEZE what it built (the step's jaxprs, the
    loaded executables, the datasets: millions of objects that live as long
    as the run), so that no later full collection walks them. Left alone,
    the one full collection set-up leaves due lands wherever the allocation
    count puts it: 0.23 - 0.50 s inside an epoch with the device starved
    11 - 40 ms (``gc_pause_s`` of the epoch records, my chip run 3, PR 38;
    PERF.md section 6). Outside the epoch's span, whose phases tile it; its
    seconds reach the JSONL as one ``kind="gc_settle"`` line;
    ``close_trainer_obs`` thaws."""
    if getattr(tr, "_gc_settled", False):
        return
    tr._gc_settled = True
    t0 = time.perf_counter()
    gc.collect()
    gc.freeze()
    tr.logger.log({"kind": "gc_settle",
                   "sec": round(time.perf_counter() - t0, 6)}, force=True)


def close_trainer_obs(tr) -> None:
    """Tear down the process-global hooks ``init_trainer_obs`` installed —
    the compile-event listener, the collector's callback and the sentinel
    handler. Without this a SECOND trainer in the same process (sweeps,
    phase global→full, tests) would keep routing its compiles, collections
    and NaN events into the FIRST run's metrics stream. Idempotent; the
    CLI calls it after fit()."""
    from p2p_tpu.obs import remove_sentinel_handler

    tr.retrace.close()
    tr.gc_pauses.remove()
    if getattr(tr, "_gc_settled", False):
        tr._gc_settled = False
        gc.unfreeze()   # the run is over: its objects may be collected
    if getattr(tr, "_sentinel_handler", None) is not None:
        remove_sentinel_handler(tr._sentinel_handler)
        tr._sentinel_handler = None


def note_step_collectives(tr, batch) -> None:
    """What the compiled SHARDED train step moves between chips, read
    once from its text: one ``kind="collectives"`` record in the run's
    stream and the gauges ``step_collective_ops{op=}``,
    ``step_collective_bytes{op=}`` (bytes a partition, a run of the step)
    and ``step_largest_all_gather_elements`` (the largest all-gather or
    all-to-all) — the number that shows a shard silently undone (an
    activation gathered or re-sharded along H reads millions there;
    docs/OBSERVABILITY.md). Called after the step's first
    dispatch with the batch as it was fed: lowered from the same avals
    and shardings jax hands back the executable that is running, so
    nothing compiles (tests/test_spatial4.py pins that)."""
    from p2p_tpu.analysis.jaxpr_lint import (
        HLO_COLLECTIVES,
        collect_collectives,
        hlo_collective_bytes,
        hlo_collective_shapes,
    )

    step, tr._collectives_of = tr._collectives_of, None

    def aval(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding) \
            if isinstance(a, jax.Array) else a

    text = step.lower(*jax.tree_util.tree_map(
        aval, (tr.state, batch))).compile().as_text()
    counts, nbytes = collect_collectives(text), hlo_collective_bytes(text)
    # an all-to-all undoes a shard as an all-gather does (GSPMD re-sharded
    # whole activations H -> W -> H around the reflect pad that way, PR 25):
    # the record holds both, the gauge the larger
    gathered, resharded = (
        max((n for n, _ in hlo_collective_shapes(text, kind)), default=0)
        for kind in ("all-gather", "all-to-all"))
    largest = max(gathered, resharded)
    record = {"kind": "collectives",
              "mesh": {a: int(n) for a, n in tr.mesh.shape.items() if n > 1},
              "largest_all_gather_elements": int(gathered),
              "largest_all_to_all_elements": int(resharded)}
    for op in HLO_COLLECTIVES:
        tr.obs.gauge("step_collective_ops", op=op).set(counts[op])
        tr.obs.gauge("step_collective_bytes", op=op).set(nbytes[op])
        record[f"{op}.count"] = int(counts[op])
        record[f"{op}.bytes"] = int(nbytes[op])
    tr.obs.gauge("step_largest_all_gather_elements").set(largest)
    tr.logger.log(record, force=True)


def trainer_topology(tr) -> Dict:
    """The topology block recorded in the sidecar AND reconciled against
    on relaunch (core/mesh.classify_topology_delta): mesh axis sizes +
    process/device counts, plus the cross-cutting facts a reshard cannot
    paper over — the global batch (sample accounting) and the dtype
    policy (a silent Orbax cast would change numerics untraceably)."""
    from p2p_tpu.core.mesh import mesh_topology
    from p2p_tpu.data.pipeline import loader_kind

    from p2p_tpu.resilience.reshape import pp_width_of

    topo = mesh_topology(tr.mesh)
    topo.update({
        "global_batch": int(tr.cfg.data.batch_size),
        "mixed_precision": bool(tr.cfg.train.mixed_precision),
        "moment_dtype": tr.cfg.optim.moment_dtype,
        "int8_delayed": bool(tr.cfg.model.int8_delayed),
        # mid-epoch reshard is only exact under the fallback loader's
        # stride arithmetic — plan_elastic_restore gates on this
        "loader": loader_kind(),
        # the stacking the state TREE actually carries (1 = flat): the
        # pipe-width migration's restore template follows this, not the
        # mesh axis — the CLI trainer runs flat even on a pipe>1 mesh
        "pp_stages": pp_width_of(tr.state),
    })
    return topo


def save_trainer_ckpt(tr, wait: bool = False) -> int:
    """Checkpoint the trainer's TrainState AND the data-iterator sidecar
    (epoch, in-epoch batch position, aug seed) — together they name an
    exact point in the sample stream, so any checkpoint (epoch-boundary or
    mid-epoch preemption) resumes without replaying or skipping samples.
    Shared by both trainers; returns the saved step."""
    step = int(tr.state.step)
    tr.ckpt.save(step, tr.state, wait=wait)
    tr.ckpt.save_aux(step, {
        "step": step,
        "epoch": tr.epoch,
        "batches_done": step % tr.steps_per_epoch,
        "steps_per_epoch": tr.steps_per_epoch,
        # cumulative-sample accounting, written on EVERY run (not just
        # elastic ones): after a global-batch migration the step counter
        # no longer names a sample position, so these are the ground
        # truth the batch_rebase transform (resilience/reshape.py)
        # re-derives position from; pre-PR-11 sidecars fall back to the
        # step×batch derivation (counted on aux_compat_total)
        "samples_seen": int(getattr(tr, "_samples_seen", 0)),
        "epoch_samples_done": int(getattr(tr, "_epoch_samples_done", 0)),
        "aug_seed": tr.cfg.train.seed + tr.epoch
        + getattr(tr, "_seed_jitter", 0),
        # health bookkeeping a relaunch must re-derive: the rollback
        # shuffle perturbation (the resumed epoch must skip against the
        # PERTURBED permutation) and the BASE lr scale — the device
        # lr_scale may carry a transient cooldown factor that must not
        # become permanent across a preempt/resume
        "seed_jitter": int(getattr(tr, "_seed_jitter", 0)),
        "lr_base": float(getattr(tr, "_base_lr_scale", 1.0)),
        # elastic relaunch: the topology this checkpoint was written on —
        # maybe_resume reconciles it against the relaunch's and reshards
        # compatible deltas (a preemptible fleet rarely hands back the
        # same slice size it reclaimed)
        "topology": trainer_topology(tr),
    })
    return step


def finish_preempted(tr) -> None:
    """The preemption epilogue both trainers share: exact-step save (wait —
    the process exits right after; an async save racing SIGKILL at the end
    of the grace window would be torn), telemetry flush, span export, then
    raise :class:`Preempted` for the CLI to turn into exit code 75."""
    with tr.spans.span("preempt_save", epoch=tr.epoch):
        step = save_trainer_ckpt(tr, wait=True)
    guard = getattr(tr, "preempt", None)
    tr.logger.log(
        {"kind": "preempt", "epoch": tr.epoch, "step": step,
         "signum": getattr(guard, "signum", None) or 0},
        force=True,
    )
    if jax.process_index() == 0:
        tr.spans.export_perfetto(tr._trace_path)
    tr.logger.registry.flush()
    raise Preempted(step, getattr(guard, "signum", None))


_AUX_UNREAD = object()


def derive_sample_position(tr, step: int, aux, mid: int) -> int:
    """Set the trainer's cumulative-sample bookkeeping
    (``_samples_seen`` / ``_epoch_samples_done`` / ``_resume_skip_samples``)
    from a restored step's sidecar. A pre-PR-11 sidecar (or a torn one
    that degraded to None) is missing the sample fields: degrade to the
    step×batch derivation — exact whenever the run never changed batch —
    counted on ``aux_compat_total`` + a ``kind="aux_compat"`` record,
    never an exception. Returns the epoch-sample prefix."""
    topo = (aux or {}).get("topology") or {}
    b_saved = int(topo.get("global_batch") or tr.cfg.data.batch_size)
    ss = (aux or {}).get("samples_seen")
    es = (aux or {}).get("epoch_samples_done")
    if ss is None or es is None:
        tr.obs.counter("aux_compat_total").inc()
        tr.logger.log(
            {"kind": "aux_compat", "step": int(step),
             "missing": [k for k, v in (("samples_seen", ss),
                                        ("epoch_samples_done", es))
                         if v is None],
             "derived_batch": b_saved},
            force=True,
        )
        if ss is None:
            ss = int(step) * b_saved
        if es is None:
            es = int(mid) * b_saved
    tr._samples_seen = int(ss)
    tr._epoch_samples_done = int(es)
    tr._resume_skip_samples = int(es)
    return int(es)


def derive_resume_position(tr, step: int, aux=_AUX_UNREAD):
    """``(done_full_epochs, mid_batches)`` for a restored checkpoint step,
    shared by both trainers' ``maybe_resume``.

    Derived from ``step % steps_per_epoch``, then cross-checked against
    (and overridden by) the iterator sidecar when present — a sidecar
    disagreeing on steps_per_epoch means the dataset or batch size changed
    under the checkpoint, where the sidecar's recorded position is the
    ground truth. Sets ``tr._resume_skip`` and logs the ``kind="resume"``
    record for mid-epoch re-entries.

    ``aux`` lets maybe_resume pass the sidecar it already read for this
    step (None = read but missing/corrupt — a torn sidecar's
    ``aux_corrupt_total`` bump must happen once, not once per consumer);
    left unset, the sidecar is read here (rollback path)."""
    done, mid = divmod(int(step), tr.steps_per_epoch)
    if aux is _AUX_UNREAD:
        aux = tr.ckpt.restore_aux(int(step))
    if aux is not None and aux.get("seed_jitter") is not None:
        # a post-rollback run shuffles on a perturbed seed; the relaunch
        # must re-derive it or the skip below would drop batches of a
        # DIFFERENT permutation
        tr._seed_jitter = int(aux["seed_jitter"])
    if aux is not None and aux.get("batches_done") is not None:
        plan = getattr(tr, "_elastic_plan", None)
        rebasing = plan is not None and "batch_rebase" in plan.chain
        if int(aux.get("steps_per_epoch", tr.steps_per_epoch)) \
                != tr.steps_per_epoch and not rebasing:
            # a PLANNED batch migration re-bases from samples (reshape.
            # apply_batch_rebase) — this warning is for the unplanned
            # drift case (dataset changed under the checkpoint)
            print(
                f"WARNING: checkpoint step {step} was saved with "
                f"steps_per_epoch={aux.get('steps_per_epoch')} but this "
                f"run has {tr.steps_per_epoch} — exact-step resume "
                "alignment is not guaranteed (did the dataset or batch "
                "size change?)", flush=True)
        mid = int(aux["batches_done"])
        # full epochs behind the restored step, in the units the step
        # counter was WRITTEN in — the sidecar's steps_per_epoch (equal
        # to this run's except across a batch migration, where this
        # run's divisor would misplace the epoch boundary)
        done = (int(step) - mid) // int(
            aux.get("steps_per_epoch") or tr.steps_per_epoch)
        # the sidecar's aug_seed encodes train.seed + epoch at save time;
        # a different --seed on the relaunch reshuffles the epoch, so the
        # skip below would drop batches of a DIFFERENT permutation —
        # replayed/skipped samples the step counter cannot see
        want_aug = tr.cfg.train.seed + done + 1 \
            + getattr(tr, "_seed_jitter", 0)
        if mid and int(aux.get("aug_seed", want_aug)) != want_aug:
            print(
                f"WARNING: mid-epoch resume with a different --seed "
                f"(checkpoint aug_seed={aux.get('aug_seed')}, this run "
                f"would use {want_aug}): the interrupted epoch's sample "
                "order cannot be reproduced — expect replayed/skipped "
                "samples. Relaunch with the original --seed for exact "
                "resume.", flush=True)
    tr._resume_skip = mid
    derive_sample_position(tr, step, aux, mid)
    if mid:
        tr.logger.log(
            {"kind": "resume", "step": int(step), "epoch": done + 1,
             "batches_done": mid},
            force=True,
        )
    return done, mid


def plan_elastic_restore(tr, step: int, aux):
    """Reconcile the checkpoint's recorded topology with this relaunch's
    BEFORE the restore touches Orbax; shared by both trainers'
    ``maybe_resume``. Collective-bearing on >1 process (the plan it
    returns drives a cross-host Orbax load) — call sites must be
    host-uniform (collective_consistency's curated list).

    Returns None for a same-topology (or pre-elastic) checkpoint, else
    an :class:`~p2p_tpu.resilience.reshape.ElasticPlan` that
    :func:`~p2p_tpu.resilience.reshape.elastic_restore` executes — a
    plain resharded restore (``reshard``), or a restore THROUGH the
    named transform chain (``migrate``: batch_rebase / pp_restructure /
    tp_amax_recalibrate / dtype_cast). Raises
    :class:`~p2p_tpu.core.mesh.TopologyMismatch` (with the saved and
    current topologies spelled out) on a must-abort delta (dtype change
    without ``--cast_on_restore``, ``int8_delayed`` flip), on a
    mid-epoch topology change under the Grain loader (its
    contiguous-block sharding has no topology-invariant epoch
    permutation — accounting would silently drift), or on ANY delta
    under ``--no-elastic``.

    ``aux`` is the step's already-read sidecar (maybe_resume reads it
    once and threads it through — a torn sidecar must be counted once,
    not once per consumer).
    """
    from p2p_tpu.core.mesh import (
        TopologyMismatch,
        classify_topology_delta,
        describe_topology,
    )
    from p2p_tpu.resilience.reshape import ElasticPlan

    tr._elastic_plan = None
    saved = (aux or {}).get("topology")
    if not saved:
        # torn/missing sidecar for THIS step: the newest intact sidecar
        # still names the run's layout — a half-written JSON must not
        # bypass the must-abort classification (dtype, int8_delayed).
        # peek_topology RAISES SidecarCorrupt when every sidecar is torn
        # (an all-torn aux dir must not read as "pre-elastic").
        from p2p_tpu.train.checkpoint import peek_topology

        saved = peek_topology(tr.ckpt.directory)
    if not saved:
        # pre-elastic checkpoint: nothing recorded to reconcile — the
        # template's own layout rules
        return None
    current = trainer_topology(tr)
    has_quant = bool(jax.tree_util.tree_leaves(
        tuple(getattr(tr.state, f, None)
              for f in ("quant_g", "quant_d", "quant_c"))))
    delta = classify_topology_delta(
        saved, current, has_quant_state=has_quant,
        cast_on_restore=tr.cfg.train.cast_on_restore)
    if delta.kind == "same":
        return None
    detail = (f"saved: {describe_topology(saved)}; "
              f"current: {describe_topology(current)}")
    if delta.kind == "abort":
        raise TopologyMismatch(
            f"cannot resume across this topology change — {delta.reason} "
            f"({detail})")
    if not tr.cfg.train.elastic:
        raise TopologyMismatch(
            f"topology changed with elastic resume disabled — "
            f"{delta.reason} ({detail}); relaunch on the original "
            "topology, or drop --no-elastic to reshard")
    if "pp_restructure" in delta.chain and "pp_stages" not in saved \
            and int((saved.get("mesh") or {}).get("pipe", 1) or 1) > 1:
        # a pre-PR-11 sidecar cannot name the trunk stacking the
        # checkpoint tree actually carries (the CLI trainer runs flat
        # even on a pipe>1 mesh; the PP step runs stacked) — guessing
        # flat would fail deep inside Orbax with an opaque structure
        # mismatch instead of this diagnosis
        raise TopologyMismatch(
            f"cannot migrate the pipe width: the checkpoint's sidecar "
            f"predates the pp_stages record, so the saved trunk "
            f"stacking is unknown ({detail}); relaunch at the original "
            "pipe axis once (its next checkpoint records the stacking), "
            "then change the width")
    mid = int(aux["batches_done"]) if aux and \
        aux.get("batches_done") is not None \
        else int(step) % tr.steps_per_epoch
    if mid and "grain" in (saved.get("loader"), current.get("loader")):
        raise TopologyMismatch(
            "mid-epoch resume across a topology change is only exact "
            "under the fallback loader's stride sharding — the Grain "
            "loader shards contiguous record blocks per process, so the "
            "interrupted epoch's consumed prefix cannot be re-derived on "
            f"a different topology ({detail}); relaunch on the original "
            "topology, or run with P2P_TPU_NO_GRAIN=1 for elastic-exact "
            "accounting")
    tr.obs.counter("elastic_resume_total").inc()
    tr.logger.log(
        {"kind": "elastic_resume", "step": int(step),
         "decision": delta.kind, "reason": delta.reason,
         "chain": list(delta.chain),
         "saved": saved, "current": current},
        force=True,
    )
    verb = ("migrating" if delta.kind == "migrate" else "resharding")
    chain_note = (f" via {'+'.join(delta.chain)}" if delta.chain else "")
    print(f"elastic resume: {delta.reason} — {verb} the step-{step} "
          f"checkpoint onto the current topology{chain_note} ({detail})",
          flush=True)
    plan = ElasticPlan(kind=delta.kind, chain=delta.chain,
                       reason=delta.reason, saved=saved, current=current)
    tr._elastic_plan = plan
    return plan


def finish_elastic_restore(tr, step: int, plan) -> None:
    """Post-restore accounting for a resharded/migrated resume: one
    auditable record naming the count (the CI elastic smoke asserts on
    it)."""
    if plan is None or tr.mesh is None:
        return
    tr.logger.log(
        {"kind": "resharded_restore", "step": int(step),
         "decision": plan.kind, "chain": list(plan.chain),
         "resharded_restore_total":
             tr.obs.counter("resharded_restore_total").value},
        force=True,
    )


def build_trainer_mesh(cfg, workdir: str):
    """``make_mesh(cfg.parallel.mesh)`` with elastic-relaunch context: a
    resolve failure (axes don't fit the current device count — the classic
    relaunch-on-a-smaller-slice mistake) names the topology the run's
    checkpoint was saved on, when one exists, instead of a bare
    divisibility error. Shared by both trainers."""
    from p2p_tpu.core.mesh import describe_topology

    try:
        return make_mesh(cfg.parallel.mesh)
    except ValueError as e:
        from p2p_tpu.train.checkpoint import SidecarCorrupt, peek_topology

        ckpt_dir = os.path.join(
            workdir, cfg.train.checkpoint_dir, cfg.data.dataset, cfg.name)
        try:
            saved = peek_topology(ckpt_dir)
        except SidecarCorrupt:
            # enrichment only — the mesh resolve failure is the real
            # error here; the corrupt-sidecar diagnosis surfaces on the
            # resume path (plan_elastic_restore) where it is actionable
            saved = None
        if saved is not None:
            raise ValueError(
                f"{e} [relaunch context: the checkpoint under {ckpt_dir} "
                f"was saved on {describe_topology(saved)}; an elastic "
                "relaunch may change the topology, but the new mesh must "
                "fit the devices this launch actually has]") from e
        raise


def metrics_path(workdir: str, name: str) -> str:
    """Per-process metrics JSONL path. Process 0 keeps the canonical
    ``metrics_<name>.jsonl``; other processes write a ``.pN`` sibling —
    multi-host runs share one workdir (the checkpoint dir must be
    common), and two processes appending to one JSONL interleave torn
    records."""
    idx = jax.process_index()
    suffix = "" if idx == 0 else f".p{idx}"
    return os.path.join(workdir, f"metrics_{name}{suffix}.jsonl")


def poll_preempt(tr) -> bool:
    """Step-boundary preemption poll shared by both trainers, fronted by
    the ``elastic`` chaos seam: when armed (``P2P_CHAOS=elastic@N``) the
    seam converts a deterministic host step into a synthetic preemption
    request — the elastic-relaunch rehearsals (CI, tests) kill a run
    mid-epoch at an exact step with no signal-timing races, then relaunch
    it on a different topology. Returns True when the (cross-host agreed)
    stop should fire."""
    if tr.preempt is None:
        return False
    try:
        chaos_point("elastic", step=tr._host_step)
    except FaultInjected:
        # Deterministic by construction: every host runs the same
        # dispatch count, so the seam fires at the SAME step on all of
        # them — no agreement collective needed (and none would come in
        # time: the amortized cadence waits up to sync_every polls, which
        # a short rehearsal epoch may never reach). Real signals stay on
        # the agreed path below.
        tr.preempt.request(signal.SIGTERM)
        return True
    # p2p-lint: disable=collective-after-divergent-exit -- both early exits are host-uniform: the guard is acquired on every host together (acquire_preempt_guard in fit), and the elastic seam is VALIDATED step-pinned (chaos.py rejects probabilistic 'elastic' specs), so FaultInjected fires on every host's same dispatch
    return tr.preempt.should_stop()


def acquire_preempt_guard(tr):
    """fit()-scoped guard ownership, shared by both trainers: install a
    :class:`PreemptionGuard` unless the caller injected one (tests drive
    the flag programmatically). Returns the OWNED guard for
    :func:`release_preempt_guard`, or None (injected guard, or signal
    handlers unavailable off the main thread — run unguarded rather than
    crash)."""
    if tr.preempt is not None:
        return None
    try:
        guard = PreemptionGuard(registry=tr.obs).install()
    except ValueError:
        return None
    # buffered telemetry survives even if the grace window expires
    # before the step boundary saves
    guard.add_flush_hook(tr.logger.registry.flush)
    tr.preempt = guard
    return guard


def release_preempt_guard(tr, owned_guard) -> None:
    if owned_guard is not None:
        owned_guard.uninstall()
        tr.preempt = None


# --------------------------------------------------------------------------
# Self-healing (resilience/health.py): shared by Trainer and VideoTrainer.
# The sentinel reads each dispatch's metrics ONE DISPATCH LATE — by the
# time the host fetches them the producing computation has retired while
# the next dispatch runs, so the happy path never fences the device.
# --------------------------------------------------------------------------


def init_trainer_health(tr) -> None:
    """Sentinel + ladder wiring (both trainers call this after their obs
    init). ``tr._host_step`` mirrors the device step counter so the
    health path never fetches ``state.step``."""
    tr.health = None
    tr._pending_health = None
    tr._seed_jitter = 0
    tr._base_lr_scale = 1.0
    tr._applied_lr_scale = 1.0
    tr._host_step = 0
    # cumulative-sample accounting (host mirrors, like _host_step): the
    # basis the elastic batch_rebase migration re-derives position from;
    # written into every checkpoint sidecar
    tr._samples_seen = 0
    tr._epoch_samples_done = 0
    tr._resume_skip_samples = 0
    # elastic-migration transient state (resilience/reshape.py)
    tr._elastic_plan = None
    tr._quant_freeze_remaining = 0
    tr._quant_frozen = None
    if tr.cfg.health.enabled:
        from p2p_tpu.resilience.health import TrainingHealth

        tr.health = TrainingHealth(tr.cfg.health, registry=tr.obs,
                                   logger=tr.logger)


def apply_health_lr(tr) -> None:
    """Fold (plateau scale × cooldown multiplier) into the device
    ``lr_scale`` — only touching the state when the product changed, so
    the steady state costs one float compare."""
    mult = tr.health.lr_multiplier if tr.health is not None else 1.0
    want = float(tr._base_lr_scale) * float(mult)
    if want != tr._applied_lr_scale:
        import jax.numpy as jnp

        tr.state = tr.state.replace(
            lr_scale=jnp.asarray(want, jnp.float32))
        tr._applied_lr_scale = want


def queue_health_observation(tr, metrics_dev, k: int) -> None:
    """Queue this dispatch's (device) metrics for the delayed read and
    consume the PREVIOUS dispatch's. ``metrics_dev`` is the per-step
    stacked tree for a scanned dispatch (k > 1) or the single step's
    metrics (k == 1)."""
    # sample accounting rides the same host mirror: k steps consumed
    # k × global_batch samples of the epoch permutation
    tr._samples_seen += k * tr.cfg.data.batch_size
    tr._epoch_samples_done += k * tr.cfg.data.batch_size
    if tr.health is None:
        tr._host_step += k
        return
    prev, tr._pending_health = (
        tr._pending_health, (tr._host_step + 1, metrics_dev, k))
    tr._host_step += k
    if prev is not None:
        consume_health_observation(tr, prev)


def flush_health_observations(tr) -> None:
    """Drain the delayed slot (end of epoch / before eval or checkpoint:
    the last dispatch must not escape the sentinel). The step clock's
    epoch ends with it: no interval spans two epochs."""
    if tr.health is None:
        return
    pend, tr._pending_health = tr._pending_health, None
    if pend is not None:
        consume_health_observation(tr, pend)
    tr.step_clock.close_epoch()


def consume_health_observation(tr, pend) -> None:
    """Fetch one queued dispatch's metrics and walk them through the
    sentinel + ladder, one step at a time. The ``nan`` chaos seam poisons
    the OBSERVED losses here — the ladder rehearsal hook
    (``P2P_CHAOS=nan@50x3`` fails steps 50..52). The fetch is the one
    place the host learns that a dispatch has FINISHED: the step clock
    times the wait (``device_wait``) and stamps its return."""
    from p2p_tpu.resilience.health import poison_nan_observation

    first_step, dev, k = pend
    with tr.step_clock.device_wait(k):
        # p2p-lint: disable=ast-host-sync-hot-loop -- this IS the designed delayed read: the fetch lands ONE DISPATCH LATE (queue_health_observation), so the device is already past it
        host = jax.device_get(dev)
    for i in range(k):
        step = first_step + i
        m = {key: float(v[i]) if k > 1 else float(v)
             for key, v in host.items()}
        action = tr.health.observe(step, poison_nan_observation(step, m))
        if action == "rollback":
            break
    apply_health_lr(tr)


def perform_rollback(tr) -> None:
    """Recovery-ladder rung 3: restore the last eval-validated
    (``mark_good``) checkpoint — falling back to the newest intact step
    when nothing is marked yet — re-enter its epoch with a PERTURBED
    data-shuffle seed (the diverging batch order must not replay
    verbatim), and re-arm the post-rollback LR cooldown."""
    cur_step = tr._host_step
    target = tr.ckpt.last_good_step()
    if target is None:
        target = tr.ckpt.latest_step()
    if target is None:
        raise DivergenceError(cur_step, tr.health.ladder.rollbacks,
                              "no checkpoint to roll back to")
    tr.ckpt.wait()  # an async save mid-flight must finish before restore
    # fallback=True: a corrupt rollback target must walk to an older
    # intact step rather than kill the self-healing path itself
    tr.state = tr.ckpt.restore(tr.state, step=int(target), fallback=True)
    # integrity fallback may have landed on an older intact step — the
    # position/step bookkeeping must follow the weights actually restored
    if tr.ckpt.last_restored_step is not None:
        target = tr.ckpt.last_restored_step
    # a rollback can land on a PRE-drain checkpoint missing newer amax
    # leaves — same graft + warmup as the resume path (ISSUE 14)
    from p2p_tpu.resilience.reshape import arm_quant_init_warmup

    arm_quant_init_warmup(tr, int(target))
    done, mid = divmod(int(target), tr.steps_per_epoch)
    aux = tr.ckpt.restore_aux(int(target))
    if aux is not None and aux.get("batches_done") is not None:
        mid = int(aux["batches_done"])
        # divisor in the units the target's step counter was WRITTEN in
        # (its sidecar's steps_per_epoch): a rollback can land on a
        # checkpoint from BEFORE a batch migration, whose basis differs
        done = (int(target) - mid) // int(
            aux.get("steps_per_epoch") or tr.steps_per_epoch)
    tr.epoch = done + 1
    tr._resume_skip = mid
    # sample accounting must follow the weights actually restored (the
    # sidecar fields are exact; a pre-PR-11 target degrades to
    # step×batch at the SAVED batch, counted)
    derive_sample_position(tr, int(target), aux, mid)
    host_step = int(target)
    b_saved = int(((aux or {}).get("topology") or {})
                  .get("global_batch") or tr.cfg.data.batch_size)
    if b_saved != int(tr.cfg.data.batch_size):
        # the target predates a batch migration: its step counter is on
        # the OLD batch basis — re-base to samples exactly as the resume
        # path does (reshape.apply_batch_rebase's law), or the LR
        # schedule/epoch boundaries silently desync for the rest of the
        # run
        from p2p_tpu.resilience.reshape import rebase_step_counters

        b_new = int(tr.cfg.data.batch_size)
        es = int(tr._epoch_samples_done)
        host_step = done * tr.steps_per_epoch + -(-es // b_new)
        tr.state = rebase_step_counters(tr.state, host_step)
        tr._resume_skip = es // b_new
        tr.logger.log(
            {"kind": "batch_rebase", "step": int(target),
             "rebased_step": int(host_step), "batch_saved": b_saved,
             "batch_current": b_new, "samples_seen": tr._samples_seen,
             "epoch_samples_done": es,
             "steps_per_epoch": tr.steps_per_epoch, "on": "rollback"},
            force=True,
        )
    # a recalibration freeze window must not re-pin post-rollback scales
    tr._quant_freeze_remaining = 0
    tr._quant_frozen = None
    tr._seed_jitter += 1000003  # new shuffle permutation from here on
    tr._pending_health = None
    tr._host_step = host_step
    tr.health.after_rollback(cur_step, int(target))
    # the restore overwrote the device lr_scale with the checkpoint's
    # value; rather than fetching it back (a host sync, formerly waived
    # under ast-host-sync-hot-loop), mark the host cache UNKNOWN — NaN
    # compares unequal to any product, so apply_health_lr below writes
    # the host-known (plateau × cooldown) scale unconditionally. One
    # extra scalar write on a path that runs at most max_rollbacks times.
    tr._applied_lr_scale = float("nan")
    apply_health_lr(tr)  # post-rollback cooldown engages immediately
    tr.logger.log(
        {"kind": "rollback", "step": int(cur_step),
         "target_step": int(target), "epoch": tr.epoch,
         "skip_batches": mid, "rollbacks": tr.health.ladder.rollbacks},
        force=True,
    )


def log_health_summary(tr) -> None:
    if tr.health is not None:
        tr.logger.log({"kind": "health_summary", **tr.health.summary()},
                      force=True)


def scan_axis_sum(metrics, k: int):
    """A dispatch's metrics summed over its scan axis (a single step's
    as they are)."""
    if k > 1:
        metrics = jax.tree_util.tree_map(
            lambda v: jax.numpy.sum(v, axis=0), metrics)
    return metrics


def mask_skipped_metrics(metrics, k: int):
    """The epoch accumulator's view of one dispatch: every metric of a
    SKIPPED step (``health_ok == 0`` — the in-jit guard dropped its
    update) zeroed, then summed over the scan axis. A single NaN step
    would otherwise poison the whole epoch's averages and feed NaN to the
    plateau controller. Without ``health_ok`` (guard off) this is the
    plain scan-axis sum the loop always used. The loops run it traced,
    inside :func:`accumulate_metrics`."""
    import jax.numpy as jnp

    ok = metrics.get("health_ok")
    if ok is not None:
        okb = ok >= 0.5
        # where, not multiply: NaN · 0 = NaN
        metrics = {
            key: (v if key == "health_ok"
                  else jnp.where(okb, v, jnp.zeros_like(v)))
            for key, v in metrics.items()
        }
    return scan_axis_sum(metrics, k)


@functools.partial(jax.jit, static_argnums=2, donate_argnums=0)
def _accumulate(sums, metrics, k: int):
    step_metrics = mask_skipped_metrics(metrics, k)
    if sums is not None:
        step_metrics = jax.tree_util.tree_map(
            jax.numpy.add, sums, step_metrics)
    last = jax.tree_util.tree_map(lambda v: v[-1], metrics) if k > 1 else None
    return step_metrics, last


def accumulate_metrics(sums, metrics, k: int):
    """One dispatch's metrics into the epoch's running sums, as ONE
    compiled call: mask the skipped steps, sum over the scan axis, add to
    ``sums`` (None on the epoch's first dispatch; donated otherwise).
    Returns ``(sums, last)``, ``last`` the dispatch's last step's metrics.

    One call, because a runtime holds a bounded number of computations
    in flight a device (32 here) and blocks the dispatch that exceeds it:
    issued eagerly this is ~3 computations a metric key, all queued
    behind the running step, and with 11 keys the 33rd call held the host
    until the step finished, the device then idle for the next feed +
    dispatch (PERF.md section 6, PR 37). Compiled once a ``(k, keys)``,
    twice with the epoch's first dispatch."""
    sums, last = _accumulate(sums, metrics, k)
    return sums, (metrics if k == 1 else last)


def epoch_metric_means(host_sums, count: int):
    """Per-step means from the (masked) epoch sums: loss metrics average
    over the APPLIED steps (``health_ok`` sum), while ``health_ok``
    itself averages over ALL steps — the applied fraction."""
    n_ok = host_sums.get("health_ok")
    denom = max(float(n_ok) if n_ok is not None else count, 1.0)
    return {
        key: float(v) / (count if key == "health_ok" else denom)
        for key, v in host_sums.items()
    }


def eval_state_of(tr):
    """The state eval should score: EMA generator weights when carried
    (HealthConfig.ema_decay), raw weights otherwise. At ema_decay=0 the
    EMA tracks params exactly, so the two are pinned bitwise-equal."""
    st = tr.state
    ema = getattr(st, "ema_g", None)
    if ema is not None:
        st = st.replace(params_g=ema)
    return st


def local_metric_rows(vec) -> np.ndarray:
    """Process-local entries of a per-image (or per-frame) metric vector.

    On one process the global array is fully addressable; on >1 only this
    process's rows are — np.asarray would raise — so gather the
    addressable shards in row order (this process's own images, because
    the loader fed exactly those rows of the global batch).

    On a mesh with axes beyond 'data' (data×spatial, data×time) the
    vector is REPLICATED over the extra axes, so each row range appears
    once per replica among the addressable shards — concatenating them
    all would duplicate head rows and the later [:n_real] trim would drop
    real tail entries. Keep exactly one shard per distinct row range.
    Shared by Trainer.evaluate and VideoTrainer.evaluate."""
    if jax.process_count() == 1:
        return np.asarray(vec).ravel()
    by_start = {}
    for s in vec.addressable_shards:
        start = s.index[0].start or 0
        if start not in by_start:
            by_start[start] = s
    parts = [by_start[k] for k in sorted(by_start)]
    out = np.concatenate([np.asarray(p.data).ravel() for p in parts])
    # the kept shards must tile this process's rows WITHOUT overlap — a
    # future mesh layout producing overlapping slices with distinct
    # starts (e.g. [0,4) and [2,6)) would double-count rows the
    # dedup-by-start cannot see
    prev_stop = None
    for p in parts:
        start = p.index[0].start or 0
        if prev_stop is not None:
            assert start >= prev_stop, (
                "overlapping metric shards", start, prev_stop)
        prev_stop = p.index[0].stop or vec.shape[0]
    return out


def combine_process_metric_stats(psnrs, ssims):
    """Cross-process reduction of per-process metric lists into global
    (psnr_mean, psnr_max, ssim_mean, ssim_max, n_total).

    Fixed-size allgather of (sum, max, count) — the per-image vectors have
    process-dependent lengths. A process whose shard dropped to zero
    batches (tiny split) must STILL enter the collective with empty-safe
    stats, or the others hang forever. Shared by both trainers."""
    from jax.experimental import multihost_utils

    stats = np.array(
        [np.sum(psnrs), np.max(psnrs, initial=-np.inf), len(psnrs),
         np.sum(ssims), np.max(ssims, initial=-np.inf)], np.float64,
    )
    g = np.asarray(multihost_utils.process_allgather(stats))
    n_total = g[:, 2].sum()
    if n_total == 0:
        raise RuntimeError(
            "multi-host eval scored 0 images: the test split is "
            "smaller than process_count × test batch — shrink "
            "test_batch_size or add test data")
    return (float(g[:, 0].sum() / n_total), float(g[:, 1].max()),
            float(g[:, 3].sum() / n_total), float(g[:, 4].max()),
            int(n_total))


class Trainer:
    def __init__(
        self,
        cfg: Config,
        data_root: Optional[str] = None,
        workdir: str = ".",
        mesh=None,
        use_mesh: bool = True,
    ):
        self.cfg = cfg
        self.workdir = workdir
        root = data_root or os.path.join(cfg.data.root, cfg.data.dataset)
        # uint8 input pipeline (default): raw bytes host→HBM, the steps
        # normalize on device — bit-exact with the f32 pipeline, 4× less
        # memo RAM and PCIe traffic (DataConfig.uint8_pipeline)
        ds_dtype = "uint8" if cfg.data.uint8_pipeline else "float32"
        labels = cfg.model.label_classes > 0
        # an input that carries a mask is MADE by the loader from the
        # target and a mask drawn per (seed, epoch, index)
        masks = input_mask_channel(cfg.model) is not None
        self.train_ds = PairedImageDataset(
            root, "train", cfg.data.direction, cfg.data.image_size,
            cfg.data.image_width, augment=cfg.data.augment,
            dtype=ds_dtype, label_input=labels, scale=cfg.model.scale,
            mask_input=masks, mask_seed=cfg.train.seed,
        )
        self.test_ds = PairedImageDataset(
            root, "test", cfg.data.direction, cfg.data.image_size,
            cfg.data.image_width, dtype=ds_dtype, label_input=labels,
            scale=cfg.model.scale, mask_input=masks,
        )
        self.steps_per_epoch = max(1, len(self.train_ds) // cfg.data.batch_size)
        self.mesh = mesh if mesh is not None else (
            build_trainer_mesh(cfg, workdir) if use_mesh else None
        )
        self._tp = False
        self._fsdp = False
        if self.mesh is not None:
            from p2p_tpu.core.mesh import FSDP_AXIS, MODEL_AXIS, PIPE_AXIS

            # model axis: the rule tables shard the Megatron conv pairs
            # and the trainer runs genuinely tensor-parallel; fsdp axis:
            # the tables shard optimizer moments + EMA (and params under
            # --fsdp_params) ZeRO-style (parallel/rules.py)
            self._tp = self.mesh.shape.get(MODEL_AXIS, 1) > 1
            self._fsdp = self.mesh.shape.get(FSDP_AXIS, 1) > 1
            if self.mesh.shape.get(PIPE_AXIS, 1) > 1:
                # training still runs correctly (the axis is just
                # replicated) but those devices do duplicate work
                print(
                    f"WARNING: mesh axis 'pipe'="
                    f"{self.mesh.shape[PIPE_AXIS]}: the CLI trainer does "
                    "not pipeline — use train/step.build_pp_train_step + "
                    "parallel/pp.pp_split_state (docs/PARALLELISM.md) to "
                    "actually exploit it",
                    flush=True)
        self.batch_sharding = batch_sharding(self.mesh) if self.mesh else None
        # Multi-host input: each process loads 1/process_count of the
        # GLOBAL batch (Grain shards records per process; device_prefetch
        # assembles the global array). cfg.data.batch_size is always the
        # global batch.
        self.local_bs = local_batch_size(cfg.data.batch_size, self.mesh)
        self.local_test_bs = local_batch_size(
            cfg.data.test_batch_size, self.mesh)

        dtype = None
        if cfg.train.mixed_precision:
            import jax.numpy as jnp

            dtype = jnp.bfloat16

        if cfg.train.debug_nans:
            from p2p_tpu.core.debug import enable_nan_debugging

            enable_nan_debugging()

        if cfg.train.compilation_cache_dir:
            # before any step compiles: restarts/preemptions reload XLA
            # programs from disk instead of recompiling (core/cache.py);
            # hits/misses are counted by the retrace watchdog below
            from p2p_tpu.core.cache import enable_compilation_cache

            enable_compilation_cache(cfg.train.compilation_cache_dir)

        if cfg.train.eval_fid and jax.process_count() > 1:
            # FIDEvaluator accumulates host-side numpy features; a global
            # array's rows are only partially addressable per process.
            # Per-process FID over a shard would be a DIFFERENT statistic
            # (means/covariances of half the set), so disable rather than
            # silently report a wrong number. (Before the VGG load below —
            # eval_fid alone must not pull the weights onto every host.)
            print("WARNING: eval_fid disabled on multi-process runs "
                  "(host-side feature accumulation is per-process).",
                  flush=True)
            import dataclasses

            cfg = dataclasses.replace(
                cfg, train=dataclasses.replace(cfg.train, eval_fid=False))
            self.cfg = cfg
        from p2p_tpu.losses.perceptual import VGG_TAPS

        self.vgg_params = (
            load_vgg19_params(arch=VGG_TAPS[cfg.loss.vgg_taps][0])
            if (cfg.loss.lambda_vgg > 0 or cfg.loss.lambda_style > 0
                or cfg.train.eval_fid) else None
        )
        if cfg.loss.lambda_lpips > 0:
            if self.vgg_params is not None:
                raise ValueError("lambda_lpips goes with no VGG19 term "
                                 "(lambda_vgg, lambda_style, eval_fid): the "
                                 "step is handed ONE frozen tree")
            from p2p_tpu.losses.lpips import load_lpips_params

            # VGG16 + the five heads, in VGG19's place
            self.vgg_params = load_lpips_params()
        if cfg.loss.lambda_hrf > 0:
            if self.vgg_params is not None:
                raise ValueError("lambda_hrf goes with no VGG term "
                                 "(lambda_vgg, lambda_style, lambda_lpips, "
                                 "eval_fid): the step is handed ONE frozen "
                                 "tree")
            from p2p_tpu.models.resnet_dilated import (
                load_resnet50_dilated_params,
            )

            # the dilated ResNet50, in VGG19's place
            self.vgg_params = load_resnet50_dilated_params()
        self.fid_feature_fn = None
        self.vgg_source = None
        self._trace_counts_logged = {}  # kind -> counts last written
        if cfg.train.eval_fid and self.vgg_params is not None:
            from p2p_tpu.losses.fid import make_vgg_feature_fn
            from p2p_tpu.models.vgg import vgg19_params_source

            self.vgg_source = vgg19_params_source()
            if self.vgg_source != "pretrained":
                print(
                    "WARNING: VFID will use RANDOM VGG19 features (no "
                    "pretrained npz asset found) — distances are not "
                    "comparable to real VFID/FID numbers.",
                    flush=True,
                )
            # built once: jit cache survives across epochs
            self.fid_feature_fn = make_vgg_feature_fn(
                self.vgg_params, cfg.loss.vgg_imagenet_norm
            )
        sample = self._host_batch_sample()
        self.state = create_train_state(
            cfg, jax.random.key(cfg.train.seed), sample,
            self.steps_per_epoch, dtype,
        )
        self.state_sharding = None
        if self.mesh is not None:
            if self._tp or self._fsdp:
                # The ONE partitioner (parallel/rules.py): Megatron
                # channel shards on the TP conv pairs when model>1, ZeRO
                # optimizer/EMA (± param) shards when fsdp>1, everything
                # else replicated; the same tree feeds
                # make_parallel_train_step's in/out shardings so updated
                # states STAY sharded across steps — gather-on-use is
                # GSPMD's job, no hand-written collectives.
                from p2p_tpu.parallel.rules import state_target_shardings

                self.state_sharding = state_target_shardings(
                    self.state, self.mesh,
                    tp_min_ch=cfg.parallel.tp_min_ch,
                    fsdp_params=cfg.parallel.fsdp_params)
                self.state = jax.device_put(self.state, self.state_sharding)
            else:
                # Replicate the state over the mesh (as VideoTrainer does):
                # batches arrive committed to all mesh devices, and jit
                # refuses to mix them with single-device state arrays.
                # A ONE-device mesh too: the step is jitted with explicit
                # shardings on it (_build_step_fns).
                from p2p_tpu.core.mesh import replicated

                self.state = jax.device_put(self.state, replicated(self.mesh))
        self._dtype = dtype
        self._build_step_fns()
        ckpt_dir = os.path.join(
            workdir, cfg.train.checkpoint_dir, cfg.data.dataset, cfg.name
        )
        self.logger = MetricsLogger(
            metrics_path(workdir, cfg.name),
            cfg.train.log_every,
        )
        self.obs = self.logger.registry
        # ckpt after logger: checkpoint retry/chaos counters belong to
        # THIS run's registry, not the process default
        self.ckpt = CheckpointManager(ckpt_dir, registry=self.obs)
        self._init_obs()
        # what the step's generator does, from its shapes
        for name, value in generator_gauges(cfg.model, *cfg.image_hw).items():
            self.obs.gauge(name).set(value)
        # and what it chooses by itself where it is traced: nothing yet
        for name in generator_trace_gauges(cfg.model):
            self.obs.gauge(name).set(0.0)
        self.plateau = (
            PlateauController() if cfg.optim.lr_policy == "plateau" else None
        )
        self.epoch = cfg.train.epoch_count
        # Fault tolerance (p2p_tpu.resilience): fit() installs a guard
        # unless the caller injected one (tests / external schedulers);
        # _resume_skip is the mid-epoch batch offset maybe_resume derives.
        self.preempt: Optional[PreemptionGuard] = None
        self._preempted = False
        self._resume_skip = 0

    def _init_obs(self) -> None:
        init_trainer_obs(self)

    def _set_trace_gauges(self) -> None:
        """What the generator chose by itself in the trace just made
        (``generator_trace_gauges``), as gauges: called right after a
        dispatch of this Trainer's own step compiled, and at no other
        time, since the modules keep one process-wide note a call site and
        any other trace of them (the benchmark's float32 check, a control)
        overwrites it while the compiled step runs on as it was traced.
        One ``kind="generator_trace"`` record whenever a trace moved the
        gauges (off zero, the first time)."""
        gauges = generator_trace_gauges(self.cfg.model)
        for name, value in gauges.items():
            self.obs.gauge(name).set(value)
        logged = self._trace_counts_logged
        if gauges != logged.get("generator_trace", dict.fromkeys(gauges, 0.0)):
            logged["generator_trace"] = gauges
            self.logger.log({"kind": "generator_trace", **gauges}, force=True)

    def close(self) -> None:
        """Release process-global telemetry hooks (safe to call twice)."""
        close_trainer_obs(self)

    def _with_mesh(self, fn):
        # Tracing happens inside the first CALL of a jitted fn, so
        # wrapping the call in mesh_context makes the mesh visible to
        # trace-time dispatch — the sharded Pallas InstanceNorm reads
        # it to wrap itself in shard_map; without this the spatial>1
        # CLI path would all-gather activations around the custom call.
        if self.mesh is None:
            return fn

        from p2p_tpu.core.mesh import mesh_context

        def wrapped(*a, **kw):
            with mesh_context(self.mesh):
                return fn(*a, **kw)

        return wrapped

    def _build_step_fns(self) -> None:
        cfg = self.cfg
        if self.mesh is not None:
            # Every mesh run jits with EXPLICIT in/out shardings
            # (parallel/dp.py): the state round-trips in the layout it
            # came in — replicated, or the TP/FSDP rule tree — and the
            # batch arrives in batch_sharding. Left to propagation, GSPMD
            # handed back some updated params sharded over 'spatial', so
            # the SECOND step was a different program and the whole
            # train step compiled twice (found on bring-up, PR 21).
            from p2p_tpu.parallel.dp import (
                make_parallel_multi_train_step,
                make_parallel_train_step,
            )

            self.train_step = make_parallel_train_step(
                cfg, self.mesh, self.vgg_params, self.steps_per_epoch,
                self._dtype, state_sharding=self.state_sharding,
            )
            # the jitted step itself, until its collectives are noted
            # (note_step_collectives); a caller may wrap self.train_step
            self._collectives_of = (
                self.train_step if self.mesh.size > 1 else None)
            self.multi_step = None
            if cfg.train.scan_steps > 1:
                self.multi_step = make_parallel_multi_train_step(
                    cfg, self.mesh, self.vgg_params, self.steps_per_epoch,
                    self._dtype, state_sharding=self.state_sharding,
                )
        else:
            self.train_step = build_train_step(
                cfg, self.vgg_params, self.steps_per_epoch, self._dtype
            )
            self._collectives_of = None
            self.multi_step = None
            if cfg.train.scan_steps > 1:
                from p2p_tpu.train.step import build_multi_train_step

                self.multi_step = build_multi_train_step(
                    cfg, self.vgg_params, self.steps_per_epoch, self._dtype
                )
        self.eval_step = self._with_mesh(build_eval_step(cfg, self._dtype))
        # Sample-dump-only helper: the reference saves the QUANTIZED
        # compressed intermediate next to input/target/pred each epoch
        # (train.py:469-473) — the one image showing what the compression
        # net does. Separate tiny jit (not part of eval_step) so the eval
        # loop pays nothing; runs once per eval, first batch only.
        self.comp_fn = None
        if cfg.model.use_compression_net:
            from p2p_tpu.ops.quantize import quantize
            from p2p_tpu.train.state import build_models

            _, _, c = build_models(cfg, self._dtype)
            bits = cfg.model.quant_bits

            def comp_fn(state, target):
                target = ingest(target, self._dtype)
                raw = c.apply(
                    {"params": state.params_c,
                     "batch_stats": state.batch_stats_c},
                    target, False,
                )
                return quantize(raw, bits)

            self.comp_fn = self._with_mesh(jax.jit(comp_fn))

    def _host_batch_sample(self):
        item = self.train_ds[0]
        bs = self.cfg.data.batch_size
        return {
            k: np.broadcast_to(v, (bs,) + v.shape).copy() for k, v in item.items()
        }

    def maybe_resume(self) -> bool:
        step = self.ckpt.latest_step()
        if step is None:
            return False
        return self._resume_from(int(step))

    def _resume_from(self, step: int) -> bool:
        # the step's sidecar, read ONCE for every consumer below (a torn
        # one must bump aux_corrupt_total once, not once per reader)
        aux = self.ckpt.restore_aux(int(step))
        # Elastic relaunch: reconcile the sidecar's recorded topology with
        # this launch's BEFORE touching Orbax — a compatible delta restores
        # resharded onto the new mesh, a migrate delta restores THROUGH
        # the reshape transform chain (resilience/reshape.py), and an
        # incompatible one aborts with the two topologies spelled out
        # instead of a deep restore error.
        from p2p_tpu.resilience.reshape import (
            apply_batch_rebase,
            elastic_restore,
        )

        plan = plan_elastic_restore(self, int(step), aux)
        try:
            self.state = elastic_restore(self, int(step), plan)
        except CheckpointCorrupt as e:
            if self.cfg.health.ema_decay is not None:
                # the likeliest cause: --ema_decay was ADDED over a
                # checkpoint saved without the EMA tree — every step then
                # fails the template restore identically, which must not
                # read as disk corruption
                raise RuntimeError(
                    "restore failed with --ema_decay set: if these "
                    "checkpoints were saved WITHOUT the EMA generator, "
                    "resume without --ema_decay (EMA can only start on a "
                    f"fresh run); underlying: {e}") from e
            raise
        # integrity fallback may have restored an OLDER intact step than
        # latest — position bookkeeping must follow the ACTUAL weights
        # (including which step's sidecar is the ground truth)
        if self.ckpt.last_restored_step is not None \
                and int(self.ckpt.last_restored_step) != int(step):
            step = self.ckpt.last_restored_step
            aux = self.ckpt.restore_aux(int(step))
        finish_elastic_restore(self, int(step), plan)
        # forward-compat quant graft (ISSUE 14): a pre-drain checkpoint
        # missing the widened coverage's amax leaves restored with those
        # leaves initialized — arm the frozen-scale warmup over them
        from p2p_tpu.resilience.reshape import arm_quant_init_warmup

        arm_quant_init_warmup(self, int(step))
        # Exact-step resume: a mid-epoch (preemption) checkpoint re-enters
        # its epoch at batch `mid` — the loader skips exactly the batches
        # the killed run consumed (same shuffle: the epoch seed is a pure
        # function of the epoch label).
        done, mid = derive_resume_position(self, int(step), aux=aux)
        host_step = int(step)
        if plan is not None and "batch_rebase" in plan.chain:
            # global-batch migration: position/step/LR basis re-derive
            # from cumulative SAMPLES; the device step + optimizer counts
            # are rebased so `step % steps_per_epoch` keeps naming epoch
            # boundaries under the new batch
            done, host_step = apply_batch_rebase(
                self, int(step), aux, plan, done, mid)
        # --epoch_count N means "continue labeling at epoch N" (reference
        # train.py:137,253-255); without it the restored step names the
        # epoch. `1 + done` covers both boundary and mid-epoch resumes: a
        # partially-done epoch (mid > 0) re-enters ITSELF as epoch done+1,
        # with the loader skipping its consumed batches.
        self.epoch = max(self.cfg.train.epoch_count, 1 + done)
        # The restored optimizer step already encodes `done` epochs, so
        # the schedule's compiled-in offset must be the flag MINUS those:
        # keeping the full --epoch_count would count them twice — e.g.
        # --epoch_count 21 --niter 20 --niter_decay 10 after 20 epochs
        # gives mult = 1 - (20 + 21 - 20)/11 < 0 → clamped to 0, and the
        # continuation trains at LR=0 (observed on the round-3 hd_r3
        # resume: bitwise-identical evals). The subtraction also keeps a
        # warm-start labeling (a run STARTED fresh at epoch_count > 1,
        # whose step counter never encoded the offset) on its original
        # curve. Rebuilding is recompile-free — jit traces at first call,
        # which hasn't happened yet.
        eff = max(1, self.cfg.train.epoch_count - done)
        if eff != self.cfg.train.epoch_count:
            import dataclasses

            self.cfg = dataclasses.replace(
                self.cfg,
                train=dataclasses.replace(self.cfg.train, epoch_count=eff),
            )
            self._build_step_fns()
        # the restored lr_scale may carry a transient cooldown factor
        # (preempted mid-cooldown); the sidecar's lr_base names the real
        # plateau scale — reset to it so the 10x reduction isn't permanent
        base = (aux or {}).get("lr_base")
        if base is not None \
                and float(np.asarray(self.state.lr_scale)) != float(base):
            import jax.numpy as jnp

            self.state = self.state.replace(
                lr_scale=jnp.asarray(float(base), jnp.float32))
        if self.plateau is not None:
            # lr_scale only ever decreases; seed the fresh controller from
            # the restored state so resume doesn't undo prior reductions.
            self.plateau.scale = float(np.asarray(self.state.lr_scale))
        # the health LR bookkeeping must agree with the restored scale
        self._base_lr_scale = float(np.asarray(self.state.lr_scale))
        self._applied_lr_scale = self._base_lr_scale
        self._host_step = host_step
        return True

    def train_epoch(self, seed: Optional[int] = None,
                    skip_batches: int = 0,
                    skip_samples: int = 0) -> Dict[str, float]:
        """One pass over the training split; returns the epoch's metric
        means. Every second of it falls into one phase, each a
        ``TraceAnnotation`` of that name and a ``<name>_secs`` histogram
        on ``self.obs`` (docs/OBSERVABILITY.md has the table), and the
        call leaves ONE ``train_epoch`` record in the span ring and the
        metrics stream: the sum of each phase, the time to the first
        dispatch and the slowest single wait, dispatch and bookkeeping
        with the step each fell on; with the health queue on also the
        step clock's fields (``obs.StepClock``): how long the host waited
        for the device, the host's own work, the interval from one
        step's completion to the next, and which phase starved the
        device where an interval ran long."""
        with self.spans.span(
                "train_epoch", registry=self.obs, force=True,
                histogram=self.obs.histogram("train_epoch_secs"),
                epoch=self.epoch) as record:
            means = self._train_epoch(record, seed, skip_batches,
                                      skip_samples)
        settle_collector(self)
        return means

    def _train_epoch(self, record: Dict, seed: Optional[int],
                     skip_batches: int, skip_samples: int
                     ) -> Dict[str, float]:
        cfg = self.cfg
        hist = self.obs.histogram
        before = (time.process_time(), self.gc_pauses.seconds,
                  self.retrace.compiles)
        with timed_annotation("epoch_setup", hist("epoch_setup_secs")) as setup:
            # Per-epoch entropy (shuffle order + augmentation crops),
            # reproducible across same-seed runs. Defaults to the current
            # epoch so bare train_epoch() loops still see fresh crops each
            # epoch rather than a frozen augmented stream. A rollback
            # (perform_rollback) perturbs the jitter so the diverging batch
            # order is not replayed verbatim.
            seed = self.epoch if seed is None else seed
            seed = seed + getattr(self, "_seed_jitter", 0)
            self.train_ds.aug_seed = cfg.train.seed + seed
            self.train_ds.mask_shares = {}
            # Worker processes are pickled a FRESH copy of the dataset each
            # epoch, which would empty the decode memo and re-decode every
            # image — when the split is cached, in-process loading keeps
            # the memo hot (decode cost is paid exactly once, epoch 1).
            workers = 0 if self.train_ds.cache_enabled else (
                cfg.data.threads if len(self.train_ds) > 64 else 0
            )
            loader = make_loader(
                self.train_ds, self.local_bs, shuffle=True,
                seed=cfg.train.seed + seed, num_workers=workers,
                skip_batches=skip_batches, skip_samples=skip_samples,
                registry=self.obs,
            )
        # Keep a device-side running sum (no host sync mid-epoch, no buffer
        # pile-up) and transfer ONCE at epoch end, so averages cover EVERY
        # step regardless of log_every.
        sums: Optional[Dict[str, jax.Array]] = None
        count = 0
        t0 = time.perf_counter()
        K = cfg.train.scan_steps
        first_k = 0       # steps covered by the compile-bearing first dispatch
        compile_skew = 0.0  # later first-compiles excluded from throughput
        seen_kinds: set = set()
        last_logged = 0
        feed_hist = hist("feed_next_secs")
        disp_hist = hist("dispatch_secs")
        book_hist = hist("step_bookkeeping_secs")
        # device_prefetch observes these two inside feed_next
        nested = (hist("loader_next_secs"), hist("h2d_put_secs"))
        nested_before = [h.sum for h in nested]
        # this epoch's seconds in each per-step phase, and the slowest
        # single one as (seconds, steps done when it began); the first
        # feed_next holds the prefetch fill and is kept apart
        phase_s = {"feed_next": 0.0, "train_dispatch": 0.0,
                   "step_bookkeeping": 0.0}
        slowest = dict.fromkeys(phase_s, (0.0, 0))
        # for the step clock: (steps done, start, seconds) of each
        # dispatch and the seconds of each feed_next
        dispatches: List = []
        feeds: List[float] = []

        def note(phase, span, at):
            phase_s[phase] += span.secs
            if span.secs > slowest[phase][0]:
                slowest[phase] = (span.secs, at)

        def run(batch_or_stack, k) -> bool:
            """One dispatch and its bookkeeping; True = stop feeding."""
            nonlocal sums, count, t0, first_k, compile_skew, last_logged
            t_call = time.perf_counter()
            at = count
            # every dispatch takes the one light path: annotation +
            # histogram; the ring holds the epoch's record, not its steps
            compiled = self.retrace.compiles
            with timed_annotation("train_dispatch", disp_hist) as disp:
                step_fn = self.multi_step if k > 1 else self.train_step
                self.state, metrics = step_fn(self.state, batch_or_stack)
            if self.retrace.compiles != compiled:
                # this dispatch traced the step
                self._set_trace_gauges()
            if at == 0:
                record["epoch_start_s"] = round(disp.t0 - setup.t0, 6)
            note("train_dispatch", disp, at)
            dispatches.append((at, disp.t0, disp.secs))
            stop = False
            with timed_annotation("step_bookkeeping", book_hist) as book:
                if k == 1 and self._collectives_of is not None:
                    note_step_collectives(self, batch_or_stack)
                # divergence sentinel: queue THIS dispatch, read the
                # previous one (a wait on the device when the host runs
                # ahead, not idle time); scanned dispatches feed their
                # per-step stacked metrics so no step escapes
                queue_health_observation(self, metrics, k)
                if self._quant_freeze_remaining:
                    # --recalibrate_steps warmup after a TP amax migration:
                    # re-pin the migrated scales (resilience/reshape.py)
                    from p2p_tpu.resilience.reshape import hold_frozen_quant

                    hold_frozen_quant(self)
                if cfg.debug.check_finite:
                    # host-side guard (fences this dispatch): the nonfinite
                    # record lands in the metrics stream BEFORE the raise.
                    # Checked on the scan-axis SUM, not the last step's
                    # slice — summing propagates any intermediate step's
                    # NaN/Inf, so a transient blowup inside a K-step
                    # dispatch can't slip past
                    from p2p_tpu.core.debug import check_finite

                    check_finite(scan_axis_sum(metrics, k), "step_metrics",
                                 registry=self.obs)
                # a skipped step's NaN losses must not poison the epoch-sum
                # averages (or the plateau controller fed from them): mask
                # skipped steps out of the ACCUMULATOR only — the raw values
                # still reach the sentinel/check_finite/log paths above.
                # The one call on device arrays between this dispatch and
                # the next feed_next, whatever the number of metric keys
                sums, last = accumulate_metrics(sums, metrics, k)
                if count > 0 and k not in seen_kinds:
                    # first use of this dispatch shape mid-epoch (e.g. the
                    # single-step remainder after scanned dispatches): the
                    # call blocked on trace+compile — keep it out of
                    # img_per_sec
                    compile_skew += time.perf_counter() - t_call
                seen_kinds.add(k)
                first = count == 0
                count += k
                if first:
                    # the first call blocks on trace+XLA compile; exclude it
                    # from the throughput figure (first epoch only, in
                    # practice)
                    first_k = k
                    t0 = time.perf_counter()
                if count - last_logged >= cfg.train.log_every:
                    last_logged = count
                    host = {kk: float(v) for kk, v in last.items()}
                    self.logger.log(
                        {"kind": "train", "epoch": self.epoch,
                         "step": int(self.state.step),
                         # cumulative samples through this dispatch — the
                         # evidence the cross-BATCH elastic rehearsals tile
                         # for gaplessness (a host counter, no device sync)
                         "samples": int(self._samples_seen), **host},
                        force=True,
                    )
                # recovery ladder rung 3: stop feeding batches — fit() owns
                # the restore-and-reenter policy (perform_rollback)
                if self.health is not None and self.health.rollback_pending:
                    stop = True
                # Preemption poll at the step boundary (cross-host agreed —
                # every process runs the same dispatch count, so the
                # agreement collective stays aligned), fronted by the
                # `elastic` chaos seam. The flag is only SET here; fit()
                # owns the save-and-exit policy.
                # p2p-lint: disable=collective-divergent-branch -- the rollback branch above is host-uniform: the ladder consumes device-REPLICATED metrics (identical float conversions on every host), so rollback_pending flips on the same dispatch everywhere
                elif poll_preempt(self):
                    self._preempted = True
                    stop = True
            note("step_bookkeeping", book, at)
            return stop

        def dispatch_batches():
            """Yield (device_batch, n_steps): host batches K-stacked for the
            scan path (stacked on HOST, then placed with the K-extended
            sharding — stacking already-sharded device arrays would gather)."""
            if K <= 1:
                for b in device_prefetch(loader, self.batch_sharding,
                                         registry=self.obs):
                    yield b, 1
                return
            stacked_sh = None
            if self.mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec as P

                from p2p_tpu.core.mesh import BATCH_AXES, SPATIAL_AXIS

                stacked_sh = NamedSharding(
                    self.mesh, P(None, BATCH_AXES, SPATIAL_AXIS, None, None)
                )

            def gen():
                pend = []
                for b in loader:
                    pend.append(b)
                    if len(pend) == K:
                        s = {
                            kk: np.stack([p[kk] for p in pend])
                            for kk in pend[0]
                        }
                        if stacked_sh is not None:
                            s = {kk: jax.device_put(v, stacked_sh)
                                 for kk, v in s.items()}
                        yield s, K
                        pend = []
                for b in pend:  # leftover < K: single-step path
                    if self.batch_sharding is not None:
                        b = {kk: jax.device_put(v, self.batch_sharding)
                             for kk, v in b.items()}
                    yield b, 1

            yield from device_prefetch(gen(), None, with_aux=True,
                                       registry=self.obs)

        batches = dispatch_batches()
        first_feed_s = 0.0
        while True:
            # what the loop WAITED for a device batch. The terminal call
            # (the feed is exhausted) is in the epoch's sum but is no
            # step's wait: the histogram counts one wait a dispatch
            with timed_annotation("feed_next") as feed:
                item = next(batches, None)
            feeds.append(feed.secs)
            if item is None:
                phase_s["feed_next"] += feed.secs
                break
            feed_hist.observe(feed.secs)
            if count == 0:
                first_feed_s = feed.secs
                phase_s["feed_next"] += feed.secs
            else:
                note("feed_next", feed, count)
            if run(*item):
                break
        with timed_annotation("epoch_drain", hist("epoch_drain_secs")) as drain:
            # drain the delayed sentinel slot: the epoch's last dispatch
            # must not escape classification (it may be the diverging one)
            flush_health_observations(self)
            if sums is not None:
                # p2p-lint: disable=ast-host-sync-hot-loop -- epoch boundary, once per epoch: the epoch record needs the sums and the fence doubles as the img/sec stop-clock
                host_sums = jax.device_get(sums)  # fences the last step
                elapsed = time.perf_counter() - t0 - compile_skew
        record.update(
            steps=count,
            epoch_setup_s=round(setup.secs, 6),
            epoch_drain_s=round(drain.secs, 6),
            first_feed_next_s=round(first_feed_s, 6),
            loader_next_s=round(nested[0].sum - nested_before[0], 6),
            h2d_put_s=round(nested[1].sum - nested_before[1], 6),
        )
        shares = list(self.train_ds.mask_shares.values())
        if shares:
            # the loader's own counter: the mean share of a sample's
            # pixels its mask blanked, over the samples of this epoch
            record["masked_share_mean"] = round(sum(shares) / len(shares), 6)
        for phase, secs in phase_s.items():
            record[f"{phase}_s"] = round(secs, 6)
            record[f"slowest_{phase}_s"] = round(slowest[phase][0], 6)
            record[f"slowest_{phase}_step"] = slowest[phase][1]
        clock = self.step_clock.epoch_fields(dispatches, feeds, drain.t0)
        if clock:
            # the host's own work: the per-step phases less what the
            # bookkeeping spent waiting for the device
            record.update(
                clock,
                host_s=round(sum(phase_s.values()) - first_feed_s
                             - clock["device_wait_s"], 6),
                cpu_s=round(time.process_time() - before[0], 6),
                gc_pause_s=round(self.gc_pauses.seconds - before[1], 6),
                compiles=self.retrace.compiles - before[2])
        # which form the thin convolutions took, how the reflect pads'
        # backward was built and which dtype VGG19's activations were
        # stored in (ops/conv.py and losses/perceptual.py count as they are
        # traced): one record each whenever a trace added some
        logged = self._trace_counts_logged
        for kind, name, counts in (
                ("conv_forms", "conv_form_sites_total", conv_form_sites()),
                ("reflect_pad", "reflect_pad_sites_total",
                 reflect_pad_sites()),
                ("vgg_loss", "vgg_loss_traces_total", vgg_loss_traces())):
            if counts != logged.get(kind) and any(counts.values()):
                logged[kind] = counts
                self.logger.log({"kind": kind, **{
                    f"{name}.{k}": v for k, v in counts.items()}},
                    force=True)
        if sums is None:
            return {}
        out = epoch_metric_means(host_sums, count)
        if count > first_k:
            out["img_per_sec"] = (
                (count - first_k) * cfg.data.batch_size / max(elapsed, 1e-9)
            )
        return out

    def evaluate(self, save_samples: bool = False) -> Dict[str, float]:
        with self.spans.span("evaluate", epoch=self.epoch):
            return self._evaluate(save_samples)

    def _evaluate(self, save_samples: bool = False) -> Dict[str, float]:
        cfg = self.cfg
        # drop_remainder=False only on a single host: with multiple JAX
        # processes Grain's ShardByJaxProcess could hand hosts UNEQUAL
        # batch counts and the extra eval_step's collectives would hang
        # the other hosts; multi-host eval keeps the even-batch guarantee.
        full_coverage = jax.process_count() == 1
        loader = make_loader(
            self.test_ds, self.local_test_bs, shuffle=False,
            num_epochs=1, drop_remainder=not full_coverage,
        )
        psnrs: List[float] = []
        ssims: List[float] = []
        fid_eval = None
        if self.fid_feature_fn is not None:
            from p2p_tpu.losses.fid import FIDEvaluator

            fid_eval = FIDEvaluator(self.fid_feature_fn)
        # partial tail batches (drop_remainder=False: EVERY test image is
        # scored) must still split over the mesh's data axis — pad by
        # edge-repeat, then trim the per-image metric vectors.
        shards = int(self.mesh.shape["data"]) if self.mesh is not None else 1
        n_proc = jax.process_count()

        metric_local = local_metric_rows  # module-level, shared with video

        def padded(it):
            for b in it:
                n = b["input"].shape[0]
                pad = (-n) % shards
                if pad:
                    b = {
                        k: np.concatenate([v, np.repeat(v[-1:], pad, axis=0)])
                        for k, v in b.items()
                    }
                yield b, n

        # EMA generator weights when carried (HealthConfig.ema_decay) —
        # eval scores the smoothed G, bitwise == raw at ema_decay=0
        est = eval_state_of(self)
        sample_saved = False
        for batch, n_real in device_prefetch(
            padded(loader), self.batch_sharding, with_aux=True
        ):
            pred, metrics = self.eval_step(est, batch)
            if fid_eval is not None:
                # ingest: uint8-pipeline targets normalize to [-1,1] first
                fid_eval.update(ingest(batch["target"][:n_real]),
                                pred[:n_real])
            # per-image vectors → the max below is over individual images,
            # matching the reference report (train.py:498-502)
            psnrs.extend(metric_local(metrics["psnr"])[:n_real].tolist())
            ssims.extend(metric_local(metrics["ssim"])[:n_real].tolist())
            if save_samples and not sample_saved:
                # comp is an SPMD computation over a (possibly) global
                # array: EVERY process must execute it — only the file
                # writes below are process-0-only.
                comp = (self.comp_fn(est, batch["target"])
                        if self.comp_fn is not None else None)

                def first_img(arr):
                    # first locally-addressable image (global arrays are
                    # only partially addressable on >1 process); uint8
                    # batches normalize to the save_img [-1,1] contract
                    if n_proc > 1:
                        arr = arr.addressable_shards[0].data
                    return np.asarray(
                        ingest(np.asarray(arr)[0]), np.float32)

                def input_img(arr):
                    if not cfg.model.label_classes:
                        return first_img(arr)
                    from p2p_tpu.utils.images import label_preview

                    if n_proc > 1:
                        arr = arr.addressable_shards[0].data
                    return label_preview(np.asarray(arr)[0])

                if jax.process_index() == 0:
                    out_dir = os.path.join(
                        self.workdir, cfg.train.result_dir, cfg.data.dataset
                    )
                    os.makedirs(out_dir, exist_ok=True)
                    save_img(input_img(batch["input"]),
                             os.path.join(out_dir, f"e{self.epoch}_input.png"))
                    save_img(first_img(batch["target"]),
                             os.path.join(out_dir, f"e{self.epoch}_target.png"))
                    save_img(first_img(pred),
                             os.path.join(out_dir, f"e{self.epoch}_pred.png"))
                    if comp is not None:
                        save_img(first_img(comp),
                                 os.path.join(out_dir, f"e{self.epoch}_comp.png"))
                    if cfg.train.save_masks:
                        # the reference's commented masking experiment
                        # (train.py:329-334): bitwise-AND of the uint8 images
                        from p2p_tpu.utils.images import to_uint8_img

                        mask = np.bitwise_and(
                            to_uint8_img(first_img(pred)),
                            to_uint8_img(input_img(batch["input"])),
                        )
                        save_img(mask, os.path.join(
                            out_dir, f"e{self.epoch}_mask.png"))
                sample_saved = True
        if n_proc > 1:
            # each process scored its OWN shard of the test split
            pm, px, sm, sx, n_total = combine_process_metric_stats(
                psnrs, ssims)
            result = {
                "psnr_mean": pm,
                "psnr_max": px,
                "ssim_mean": sm,
                "ssim_max": sx,
                "n_images": n_total,
            }
        else:
            result = {
                "psnr_mean": float(np.mean(psnrs)),
                "psnr_max": float(np.max(psnrs)),
                "ssim_mean": float(np.mean(ssims)),
                "ssim_max": float(np.max(ssims)),
                "n_images": len(psnrs),
            }
        if fid_eval is not None and fid_eval.real.n > 1:
            result["vfid"] = fid_eval.compute()
            if self.vgg_source != "pretrained":
                result["vfid_feature_source"] = self.vgg_source
        self.logger.log({"kind": "eval", "epoch": self.epoch, **result})
        return result

    def current_lr(self) -> Optional[float]:
        """Effective generator LR: the schedule value inside the optimizer
        state (inject_hyperparams) times the host plateau scale."""
        try:
            hp = self.state.opt_g.hyperparams["learning_rate"]
            return float(np.asarray(hp)) * float(np.asarray(self.state.lr_scale))
        except (AttributeError, KeyError, TypeError):
            return None

    def fit(self, nepoch: Optional[int] = None) -> List[Dict[str, float]]:
        cfg = self.cfg
        nepoch = nepoch or cfg.train.nepoch
        history = []
        armed_retrace = False  # armed after the first COMPLETED epoch
        self._preempted = False
        # the host mirror of the device step counter needs NO fetch here:
        # it is maintained at every point the step can move — 0 at
        # construction (init_trainer_health), the restored step in
        # maybe_resume, the rollback target in perform_rollback, +k per
        # dispatch (queue_health_observation) — so fit() starts aligned.
        # (Was a jax.device_get waived under ast-host-sync-hot-loop; the
        # waiver-ceiling pin in tests/test_analysis.py holds the count.)
        owned_guard = acquire_preempt_guard(self)
        try:
            while self.epoch <= nepoch:
                t0 = time.time()
                # exact-step resume: the first epoch after a mid-epoch
                # restore skips exactly the SAMPLES the killed run
                # consumed (sample-granular, so a batch-change migration's
                # old-batch prefix still tiles exactly; = batches × batch
                # on the ordinary path)
                skip_s = self._resume_skip_samples
                self._resume_skip_samples = 0
                self._resume_skip = 0
                rollback = False
                with self.spans.span("epoch", epoch=self.epoch):
                    train_metrics = self.train_epoch(seed=self.epoch,
                                                     skip_samples=skip_s)
                    record = {"epoch": self.epoch, "sec": time.time() - t0,
                              **train_metrics}
                    lr = self.current_lr()
                    if lr is not None:  # reference prints LR per epoch (networks.py:125)
                        record["lr"] = lr
                    rollback = (self.health is not None
                                and self.health.rollback_pending)
                    if cfg.train.eval_every_epoch and not self._preempted \
                            and not rollback:
                        record.update(self.evaluate(save_samples=True))
                if self._preempted:
                    # partial epoch: no epoch record (downstream tooling
                    # reads those as COMPLETED epochs) — save the exact
                    # step + iterator sidecar and exit as "resume me"
                    finish_preempted(self)  # raises Preempted
                if rollback:
                    # recovery ladder rung 3: restore the last-good step,
                    # re-enter its epoch on a perturbed shuffle — no epoch
                    # record (the diverged partial epoch didn't complete)
                    perform_rollback(self)
                    continue
                # epoch completed: the in-epoch sample counter re-arms
                # (the cumulative _samples_seen keeps growing)
                self._epoch_samples_done = 0
                history.append(record)
                # epoch summary (incl. lr) into the metrics stream — the
                # jsonl otherwise only carries per-step and eval records, so
                # LR continuity across a resume would be unobservable
                self.logger.log({"kind": "epoch", **record}, force=True)
                self.memwatch.sample(self.logger)  # HBM fill/peak (no-op on CPU)
                if self.plateau is not None and "loss_g" in record:
                    # feed the generator loss, mode='min' (reference plateau);
                    # the returned scale multiplies every optimizer update
                    # inside the jitted step via TrainState.lr_scale
                    # (composed with the health ladder's cooldown factor).
                    self._base_lr_scale = self.plateau.update(
                        record["loss_g"])
                    apply_health_lr(self)
                if self.epoch % cfg.train.epoch_save == 0 \
                        or self.epoch == nepoch:
                    with self.spans.span("checkpoint_save", epoch=self.epoch):
                        saved_step = save_trainer_ckpt(self)
                    # last-good tracking: the eval PSNR sweep validates the
                    # step — rollback targets the newest MARKED step
                    psnr = record.get("psnr_mean")
                    if psnr is not None and np.isfinite(psnr):
                        self.ckpt.mark_good(saved_step)
                if not armed_retrace:
                    # the first COMPLETED epoch compiled every dispatch
                    # shape (scan body, remainder, eval, comp_fn) —
                    # compiles from here on are suspect. Flag-based, not
                    # epoch-label-based: a rollback rewrites self.epoch
                    # and must not leave the watchdog unarmed forever.
                    # The first async checkpoint save may still warn once;
                    # the watchdog only reports, never raises.
                    self.retrace.arm()
                    armed_retrace = True
                self.epoch += 1
        finally:
            # the epilogue runs on EVERY exit — completed, Preempted, or
            # DivergenceError (exit 76): an in-flight async save must be
            # awaited and the health summary is most valuable exactly on
            # the runs that die (the audit trail of how/why the ladder
            # fired).
            release_preempt_guard(self, owned_guard)
            self.ckpt.wait()
            # Perfetto-loadable host-span trace next to the metrics stream
            # (each fit() call rewrites it with the accumulated spans).
            if jax.process_index() == 0:
                self.spans.export_perfetto(self._trace_path)
            # one auditable line per run: how often the ladder fired
            log_health_summary(self)
            self.logger.registry.flush()
        return history
