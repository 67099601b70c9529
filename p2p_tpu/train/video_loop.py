"""Epoch driver for video (vid2vid-style) training.

Mirrors :class:`p2p_tpu.train.loop.Trainer` for NTHWC clip batches: the
video train step (spatial + temporal discriminators), per-frame PSNR/SSIM
eval, Orbax checkpointing of the VideoTrainState, JSONL metrics. Clips are
sharded ``P('data','time',...)`` over the mesh when one is configured —
sequence parallelism comes from the sharding annotation, not special code.
"""

from __future__ import annotations

import os
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from p2p_tpu.core.config import Config
from p2p_tpu.core.mesh import make_mesh, replicated, video_sharding
from p2p_tpu.data.pipeline import device_prefetch, make_loader
from p2p_tpu.data.video import VideoClipDataset
from p2p_tpu.losses import psnr, ssim
from p2p_tpu.models.vgg import load_vgg19_params
from p2p_tpu.obs import MetricsLogger
from p2p_tpu.resilience import PreemptionGuard
from p2p_tpu.train.checkpoint import CheckpointManager
from p2p_tpu.train.loop import (
    accumulate_metrics,
    acquire_preempt_guard,
    apply_health_lr,
    build_trainer_mesh,
    close_trainer_obs,
    derive_resume_position,
    epoch_metric_means,
    finish_elastic_restore,
    finish_preempted,
    flush_health_observations,
    init_trainer_obs,
    log_health_summary,
    metrics_path,
    perform_rollback,
    plan_elastic_restore,
    poll_preempt,
    queue_health_observation,
    release_preempt_guard,
    save_trainer_ckpt,
    scan_axis_sum,
)
from p2p_tpu.utils.images import ingest
from p2p_tpu.train.video_step import (
    build_video_models,
    build_video_train_step,
    create_video_train_state,
    make_parallel_video_step,
)


def build_video_eval_step(cfg: Config, train_dtype=None, jit: bool = True):
    """``eval_step(state, batch) -> (pred_clip, metrics)`` — G per frame,
    per-frame PSNR/SSIM vectors (N·T,)."""
    g, _, _ = build_video_models(cfg, train_dtype)

    def step(state, batch):
        real_a = ingest(batch["input"], train_dtype)
        real_b = ingest(batch["target"], train_dtype)
        n, t = real_a.shape[0], real_a.shape[1]
        a_f = real_a.reshape((n * t,) + real_a.shape[2:])
        b_f = real_b.reshape((n * t,) + real_b.shape[2:])
        pred = g.apply(
            {"params": state.params_g, "batch_stats": state.batch_stats_g},
            a_f, False,
        )
        metrics = {
            "psnr": psnr(b_f, pred, per_image=True),
            "ssim": ssim(b_f, pred, per_image=True),
        }
        return pred.reshape(real_b.shape), metrics

    if jit:
        step = jax.jit(step)
    return step


class VideoTrainer:
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
        kw = dict(
            direction=cfg.data.direction, image_size=cfg.data.image_size,
            image_width=cfg.data.image_width, n_frames=cfg.data.n_frames,
            dtype="uint8" if cfg.data.uint8_pipeline else "float32",
        )
        self.train_ds = VideoClipDataset(root, "train", **kw)
        self.test_ds = VideoClipDataset(root, "test", **kw)
        self.steps_per_epoch = max(1, len(self.train_ds) // cfg.data.batch_size)
        self.mesh = mesh if mesh is not None else (
            build_trainer_mesh(cfg, workdir) if use_mesh else None
        )
        self.clip_sharding = video_sharding(self.mesh) if self.mesh else None
        # global batch in cfg; per-process local batch for the loaders
        # (device_prefetch assembles the global array on >1 process)
        from p2p_tpu.core.mesh import local_batch_size
        self.local_bs = local_batch_size(cfg.data.batch_size, self.mesh)
        self.local_test_bs = local_batch_size(
            cfg.data.test_batch_size, self.mesh)

        dtype = jnp.bfloat16 if cfg.train.mixed_precision else None
        if cfg.train.compilation_cache_dir:
            from p2p_tpu.core.cache import enable_compilation_cache

            enable_compilation_cache(cfg.train.compilation_cache_dir)
        self.vgg_params = (
            load_vgg19_params() if cfg.loss.lambda_vgg > 0 else None
        )
        sample = self._host_batch_sample()
        self.state = create_video_train_state(
            cfg, jax.random.key(cfg.train.seed), sample,
            self.steps_per_epoch, dtype,
        )
        self._dtype = dtype
        self._build_step_fns()
        if self.mesh is not None:
            self.state = jax.device_put(self.state, replicated(self.mesh))
        from p2p_tpu.train.schedules import PlateauController

        self.plateau = (
            PlateauController() if cfg.optim.lr_policy == "plateau" else None
        )
        self.logger = MetricsLogger(
            metrics_path(workdir, cfg.name),
            cfg.train.log_every,
        )
        self.obs = self.logger.registry
        # ckpt after logger: retry/chaos counters on THIS run's registry
        self.ckpt = CheckpointManager(os.path.join(
            workdir, cfg.train.checkpoint_dir, cfg.data.dataset, cfg.name
        ), registry=self.obs)
        init_trainer_obs(self)  # manifest + spans + watchdogs (p2p_tpu.obs)
        self.epoch = cfg.train.epoch_count
        self.preempt: Optional[PreemptionGuard] = None
        self._preempted = False
        self._resume_skip = 0

    def close(self) -> None:
        """Release process-global telemetry hooks (safe to call twice)."""
        close_trainer_obs(self)

    def _build_step_fns(self) -> None:
        cfg = self.cfg
        if self.mesh is not None:
            self.train_step = make_parallel_video_step(
                cfg, self.mesh, self.vgg_params, self.steps_per_epoch,
                self._dtype,
            )
        else:
            self.train_step = build_video_train_step(
                cfg, self.vgg_params, self.steps_per_epoch, self._dtype
            )
        self.multi_step = None
        if cfg.train.scan_steps > 1:
            from p2p_tpu.train.video_step import build_multi_video_train_step

            self.multi_step = build_multi_video_train_step(
                cfg, self.vgg_params, self.steps_per_epoch, self._dtype
            )
        self.eval_step = build_video_eval_step(cfg, self._dtype)

    def _host_batch_sample(self):
        item = self.train_ds[0]
        bs = self.cfg.data.batch_size
        return {
            k: np.broadcast_to(v, (bs,) + v.shape).copy()
            for k, v in item.items()
        }

    def maybe_resume(self) -> bool:
        step = self.ckpt.latest_step()
        if step is None:
            return False
        return self._resume_from(int(step))

    def _resume_from(self, step: int) -> bool:
        # the step's sidecar, read ONCE for every consumer below
        aux = self.ckpt.restore_aux(int(step))
        # elastic relaunch: reconcile recorded vs current topology first
        # (cf. Trainer.maybe_resume) — reshard compatible deltas, migrate
        # transformable ones (resilience/reshape.py), abort the rest with
        # both topologies named
        from p2p_tpu.resilience.reshape import (
            apply_batch_rebase,
            elastic_restore,
        )

        plan = plan_elastic_restore(self, int(step), aux)
        self.state = elastic_restore(self, int(step), plan)
        # integrity fallback may have restored an OLDER intact step
        if self.ckpt.last_restored_step is not None \
                and int(self.ckpt.last_restored_step) != int(step):
            step = self.ckpt.last_restored_step
            aux = self.ckpt.restore_aux(int(step))
        finish_elastic_restore(self, int(step), plan)
        # (no quant graft here: VideoTrainState carries no quant
        # collections — the video trainer rejects int8_delayed outright,
        # so the forward-compat amax machinery has nothing to arm)
        # exact-step resume (shared with Trainer.maybe_resume): a
        # mid-epoch (preemption) checkpoint re-enters its epoch at
        # clip-batch `mid`
        done, mid = derive_resume_position(self, int(step), aux=aux)
        host_step = int(step)
        if plan is not None and "batch_rebase" in plan.chain:
            # global-batch migration: re-derive position from samples
            # (cf. Trainer._resume_from)
            done, host_step = apply_batch_rebase(
                self, int(step), aux, plan, done, mid)
        self.epoch = max(self.cfg.train.epoch_count, 1 + done)
        # Renormalize the schedule's epoch offset against the restored
        # step (see Trainer.maybe_resume for the double-offset analysis;
        # same bug shape here).
        eff = max(1, self.cfg.train.epoch_count - done)
        if eff != self.cfg.train.epoch_count:
            import dataclasses

            self.cfg = dataclasses.replace(
                self.cfg,
                train=dataclasses.replace(self.cfg.train, epoch_count=eff),
            )
            self._build_step_fns()
        # drop a preempt-frozen transient cooldown factor (cf. Trainer)
        base = (aux or {}).get("lr_base")
        if base is not None \
                and float(np.asarray(self.state.lr_scale)) != float(base):
            self.state = self.state.replace(
                lr_scale=jnp.asarray(float(base), jnp.float32))
        if self.plateau is not None:
            self.plateau.scale = float(np.asarray(self.state.lr_scale))
        self._base_lr_scale = float(np.asarray(self.state.lr_scale))
        self._applied_lr_scale = self._base_lr_scale
        self._host_step = host_step
        return True

    def train_epoch(self, seed: int = 0,
                    skip_batches: int = 0,
                    skip_samples: int = 0) -> Dict[str, float]:
        cfg = self.cfg
        # rollback perturbation (perform_rollback) — cf. Trainer.train_epoch
        seed = seed + getattr(self, "_seed_jitter", 0)
        loader = make_loader(
            self.train_ds, self.local_bs, shuffle=True,
            seed=cfg.train.seed + seed,
            num_workers=cfg.data.threads if len(self.train_ds) > 64 else 0,
            skip_batches=skip_batches, skip_samples=skip_samples,
            registry=self.obs,
        )
        sums = None
        count = 0
        first_k = 0
        t0 = time.perf_counter()
        K = cfg.train.scan_steps if self.multi_step is not None else 1
        last_logged = 0
        n_disp = 0
        disp_hist = self.obs.histogram("dispatch_secs")

        def run(batch, k):
            nonlocal sums, count, t0, first_k, last_logged, n_disp
            # first dispatches → span ring; all → histogram (cf. Trainer)
            if n_disp < 4:
                cm = self.spans.span("train_dispatch", steps=k,
                                     histogram=disp_hist)
            else:
                from p2p_tpu.obs import timed_annotation

                cm = timed_annotation("train_dispatch", disp_hist)
            n_disp += 1
            with cm:
                step_fn = self.multi_step if k > 1 else self.train_step
                self.state, metrics = step_fn(self.state, batch)
            # divergence sentinel: delayed read, per-step rows on the
            # scan path (cf. Trainer.train_epoch)
            queue_health_observation(self, metrics, k)
            if cfg.debug.check_finite:
                # scan-axis sum: catches an intermediate scanned step's
                # NaN/Inf, not just the last slice (cf. Trainer)
                from p2p_tpu.core.debug import check_finite

                check_finite(scan_axis_sum(metrics, k), "step_metrics",
                             registry=self.obs)
            # skipped steps out of the epoch accumulator, one compiled
            # call a dispatch (cf. Trainer)
            sums, last = accumulate_metrics(sums, metrics, k)
            first = count == 0
            count += k
            if first:
                first_k = k
                t0 = time.perf_counter()
            if count - last_logged >= cfg.train.log_every:
                last_logged = count
                self.logger.log(
                    {"kind": "train", "epoch": self.epoch,
                     "step": int(self.state.step),
                     "samples": int(self._samples_seen),
                     **{kk: float(v) for kk, v in last.items()}},
                    force=True,
                )

        def dispatch():
            if K <= 1:
                for b in device_prefetch(loader, self.clip_sharding):
                    yield b, 1
                return
            stacked_sh = None
            if self.mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec as P

                from p2p_tpu.core.mesh import (
                    BATCH_AXES, SPATIAL_AXIS, TIME_AXIS,
                )

                stacked_sh = NamedSharding(self.mesh, P(
                    None, BATCH_AXES, TIME_AXIS, SPATIAL_AXIS, None, None
                ))

            def gen():
                pend = []
                for b in loader:
                    pend.append(b)
                    if len(pend) == K:
                        s = {kk: np.stack([p[kk] for p in pend])
                             for kk in pend[0]}
                        if stacked_sh is not None:
                            s = {kk: jax.device_put(v, stacked_sh)
                                 for kk, v in s.items()}
                        yield s, K
                        pend = []
                for b in pend:
                    if self.clip_sharding is not None:
                        b = {kk: jax.device_put(v, self.clip_sharding)
                             for kk, v in b.items()}
                    yield b, 1

            yield from device_prefetch(gen(), None, with_aux=True)

        for batch, k in dispatch():
            run(batch, k)
            # recovery ladder rung 3 (cf. Trainer.train_epoch)
            if self.health is not None and self.health.rollback_pending:
                break
            # preemption poll at the step boundary, fronted by the
            # `elastic` chaos seam (cf. Trainer.train_epoch)
            # p2p-lint: disable=collective-after-divergent-exit -- the rollback break above is host-uniform: the ladder consumes device-replicated metrics (cf. Trainer.train_epoch's identical waiver)
            if poll_preempt(self):
                self._preempted = True
                break
        flush_health_observations(self)
        if sums is None:
            return {}
        # p2p-lint: disable=ast-host-sync-hot-loop -- epoch boundary, once per epoch (the image Trainer's twin)
        host = jax.device_get(sums)
        elapsed = time.perf_counter() - t0
        out = epoch_metric_means(host, count)
        if count > first_k:
            frames = cfg.data.batch_size * cfg.data.n_frames
            out["frames_per_sec"] = (
                (count - first_k) * frames / max(elapsed, 1e-9)
            )
        return out

    def evaluate(self) -> Dict[str, float]:
        with self.spans.span("evaluate", epoch=self.epoch):
            return self._evaluate()

    def _evaluate(self) -> Dict[str, float]:
        cfg = self.cfg
        loader = make_loader(
            self.test_ds, self.local_test_bs, shuffle=False,
            num_epochs=1, drop_remainder=jax.process_count() > 1,
        )
        psnrs: List[float] = []
        ssims: List[float] = []
        # partial tail clip batches must still split over the mesh's data
        # axis: edge-pad, trim per-frame metric vectors (cf. Trainer)
        shards = int(self.mesh.shape["data"]) if self.mesh is not None else 1

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

        t = cfg.data.n_frames
        # per-frame metric vectors: process-local rows with replica dedup
        # (the vector is replicated over the time axis of a data×time
        # mesh) — shared machinery with the image Trainer
        from p2p_tpu.train.loop import (
            combine_process_metric_stats,
            local_metric_rows,
        )

        for batch, n_real in device_prefetch(
            padded(loader), self.clip_sharding, with_aux=True
        ):
            _, metrics = self.eval_step(self.state, batch)
            psnrs.extend(
                local_metric_rows(metrics["psnr"])[: n_real * t].tolist()
            )
            ssims.extend(
                local_metric_rows(metrics["ssim"])[: n_real * t].tolist()
            )
        if jax.process_count() > 1:
            pm, px, sm, sx, n_total = combine_process_metric_stats(
                psnrs, ssims)
            result = {
                "psnr_mean": pm, "psnr_max": px,
                "ssim_mean": sm, "ssim_max": sx,
                "n_frames_scored": n_total,
            }
        else:
            result = {
                "psnr_mean": float(np.mean(psnrs)),
                "psnr_max": float(np.max(psnrs)),
                "ssim_mean": float(np.mean(ssims)),
                "ssim_max": float(np.max(ssims)),
                "n_frames_scored": len(psnrs),
            }
        self.logger.log({"kind": "eval", "epoch": self.epoch, **result})
        return result

    def fit(self, nepoch: Optional[int] = None) -> List[Dict[str, float]]:
        cfg = self.cfg
        nepoch = nepoch or cfg.train.nepoch
        history = []
        armed_retrace = False  # armed after the first COMPLETED epoch
        self._preempted = False
        # preemption guard (p2p_tpu.resilience) — same protocol as the
        # image Trainer: flag at the signal, exact-step save + Preempted
        # at the next step boundary, exact-step resume via maybe_resume's
        # skip_batches path. The host step mirror is maintained (cf.
        # Trainer.fit) — no device fetch needed here.
        owned_guard = acquire_preempt_guard(self)
        try:
            while self.epoch <= nepoch:
                skip_s = self._resume_skip_samples
                self._resume_skip_samples = 0
                self._resume_skip = 0
                rollback = False
                with self.spans.span("epoch", epoch=self.epoch):
                    record = {"epoch": self.epoch,
                              **self.train_epoch(seed=self.epoch,
                                                 skip_samples=skip_s)}
                    rollback = (self.health is not None
                                and self.health.rollback_pending)
                    if cfg.train.eval_every_epoch and not self._preempted \
                            and not rollback:
                        record.update(self.evaluate())
                if self._preempted:
                    finish_preempted(self)  # raises Preempted
                if rollback:
                    # ladder rung 3 (cf. Trainer.fit)
                    perform_rollback(self)
                    continue
                # epoch completed: in-epoch sample counter re-arms
                self._epoch_samples_done = 0
                history.append(record)
                self.logger.log({"kind": "epoch", **record}, force=True)
                self.memwatch.sample(self.logger)
                if self.plateau is not None and "loss_g" in record:
                    self._base_lr_scale = self.plateau.update(
                        record["loss_g"])
                    apply_health_lr(self)
                if self.epoch % cfg.train.epoch_save == 0 \
                        or self.epoch == nepoch:
                    with self.spans.span("checkpoint_save", epoch=self.epoch):
                        saved_step = save_trainer_ckpt(self)
                    psnr = record.get("psnr_mean")
                    if psnr is not None and np.isfinite(psnr):
                        self.ckpt.mark_good(saved_step)
                if not armed_retrace:
                    self.retrace.arm()  # warmup compiles done; see Trainer.fit
                    armed_retrace = True
                self.epoch += 1
        finally:
            # epilogue on every exit — incl. Preempted and exit-76
            # (cf. Trainer.fit): await async saves, keep the audit trail
            release_preempt_guard(self, owned_guard)
            self.ckpt.wait()
            if jax.process_index() == 0:
                self.spans.export_perfetto(self._trace_path)
            log_health_summary(self)
            self.logger.registry.flush()
        return history
