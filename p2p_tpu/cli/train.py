"""Training CLI — flag parity with the reference (train.py:133-157) plus
TPU-native knobs (--preset, --mesh).

Every reference flag is accepted with the same name and default. Flags the
reference parsed but never used are live here where the intent is clear
(--lamb wires the pix2pix L1 weight — SURVEY Q3) or accepted-and-ignored
with a warning where they are meaningless on TPU (--cuda).

Unset flags inherit from the chosen --preset, so
``--preset pix2pixhd --batch_size 2`` tweaks one knob of a BASELINE config.
"""

from __future__ import annotations

import argparse
import dataclasses
import sys

from p2p_tpu.core.config import Config, get_preset, list_presets


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description="p2p_tpu training")
    # --- TPU-native knobs -------------------------------------------------
    p.add_argument("--preset", type=str, default="reference",
                   help=f"named config preset: {', '.join(list_presets())}")
    p.add_argument("--data_root", type=str, default=None,
                   help="dataset root directory (default <root>/<dataset>)")
    p.add_argument("--workdir", type=str, default=".",
                   help="checkpoints/results/metrics land here")
    p.add_argument("--mesh", type=str, default=None,
                   help="mesh axes: positional "
                        "'data,spatial,time[,model[,pipe]]' (e.g. '4,2,1') "
                        "or named 'axis=size,...' over data/fsdp/spatial/"
                        "time/model/pipe (e.g. 'data=4,fsdp=2,model=2'; "
                        "data may be -1 = all remaining devices); model>1 "
                        "trains tensor-parallel, fsdp>1 shards optimizer+"
                        "EMA state ZeRO-style (docs/PARALLELISM.md)")
    p.add_argument("--tp_min_ch", type=int, default=None,
                   help="smallest channel count the TP pair rule shards "
                        "over the model axis (ParallelConfig.tp_min_ch; "
                        "default 512 — lower it only for toy models)")
    p.add_argument("--fsdp_params", action="store_true", default=None,
                   help="with mesh fsdp>1: shard the params themselves "
                        "over the fsdp axis too (ZeRO-3-ish gather-on-"
                        "use), not just optimizer moments + EMA "
                        "(ParallelConfig.fsdp_params)")
    p.add_argument("--image_width", type=int, default=None,
                   help="image width when not square (e.g. pix2pixhd "
                        "1024x512 trains height=512 width=1024)")
    p.add_argument("--image_size", type=int, default=None,
                   help="override preset image size (height; square unless "
                        "the preset sets a width)")
    p.add_argument("--n_blocks", type=int, default=None,
                   help="override generator residual block count")
    p.add_argument("--augment", action="store_true", default=None,
                   help="paired resize-286/random-crop/flip augmentation")
    p.add_argument("--int8", action="store_true", default=None,
                   help="int8 QAT MXU path for the discriminator's inner "
                        "convs (ops/int8.py; ~1.1x step on v5e); "
                        "--int8_generator extends it to the U-Net G")
    p.add_argument("--int8_generator", action="store_true", default=None,
                   help="extend --int8 to the generator convs (measured "
                        "slower on v5e at 256^2; see ModelConfig)")
    p.add_argument("--int8_stem", action="store_true", default=None,
                   help="extend the int8 path to the 3/6-channel input "
                        "stems (U-Net down0, PatchGAN stage 0, net_c's "
                        "k5 conv). Off by default: the stems are "
                        "HBM-bound — measured-rejected on v5e, kept "
                        "measurable per chip/shape")
    p.add_argument("--int8_head", action="store_true", default=None,
                   help="discriminator logits head on the int8 kn2row "
                        "tap-decomposition path (ops/int8.py "
                        "int8_kn2row_conv); the U-Net IMAGE head always "
                        "stays bf16")
    p.add_argument("--int8_compression", action="store_true", default=None,
                   help="CompressionNetwork (net_c) convs on the int8 "
                        "path; its amax state rides the 'quant' "
                        "collection as quant_c end-to-end")
    p.add_argument("--int8_fused_epilogue", action="store_true",
                   default=None,
                   help="fuse the D inner-conv epilogue [instance norm + "
                        "LeakyReLU + quantize + amax] into one streaming "
                        "Pallas pass (needs --norm_d pallas_instance and "
                        "--int8_delayed; ops/pallas/norm_act.py)")
    p.add_argument("--int8_delayed", action=argparse.BooleanOptionalAction,
                   default=None,
                   help="delayed (stored-scale) activation quantization: "
                        "per-layer amax carried in TrainState; removes "
                        "the absmax reductions from the critical path "
                        "(ops/int8.py int8_conv_ds). --no-int8_delayed "
                        "restores the dynamic-scale path (required to "
                        "RESUME pre-round-3 facades_int8 checkpoints — "
                        "the quant collection changes the TrainState "
                        "tree)")
    p.add_argument("--norm_d", type=str, default=None,
                   choices=["none", "instance", "pallas_instance"],
                   help="discriminator-side norm on the inner PatchGAN "
                        "convs (pix2pixHD-paper D layout; affine-free, so "
                        "checkpoints interchange with 'none'). "
                        "'pallas_instance' fuses norm+LeakyReLU into one "
                        "Pallas pass (ops/pallas/norm_act.py)")
    p.add_argument("--pp_overlap", action=argparse.BooleanOptionalAction,
                   default=None,
                   help="latency-hiding GPipe schedule: the stage hand-off "
                        "ppermute is double-buffered so the transfer "
                        "overlaps stage compute (parallel/pp.py; costs S-1 "
                        "extra fill/drain ticks — see docs/PARALLELISM.md)")
    p.add_argument("--legacy_layout", action="store_true", default=None,
                   help="keep the dead conv biases in front of norm "
                        "layers (round-2 checkpoint layout; see "
                        "ModelConfig.legacy_layout)")
    p.add_argument("--compilation_cache", type=str, default=None,
                   metavar="DIR",
                   help="persistent XLA compilation cache directory "
                        "(core/cache.py; default: JAX_COMPILATION_CACHE_DIR "
                        "if set, else .jax_cache in the checkout — naming "
                        "a directory that disagrees with the variable is "
                        "an error): restarted runs reload compiled "
                        "programs from disk instead of recompiling; "
                        "hits/misses are counted through the obs retrace "
                        "watchdog")
    p.add_argument("--elastic", action=argparse.BooleanOptionalAction,
                   default=None,
                   help="elastic relaunch (docs/RESILIENCE.md): on resume, "
                        "reconcile the checkpoint's recorded topology "
                        "(process count, mesh axes, global batch, dtype "
                        "policy) against this launch's and RESHARD "
                        "compatible deltas — a preemptible fleet rarely "
                        "hands back the slice size it reclaimed. On by "
                        "default; --no-elastic restores the strict "
                        "contract (any topology delta aborts)")
    p.add_argument("--cast_on_restore", action=argparse.BooleanOptionalAction,
                   default=None,
                   help="opt-in dtype-policy migration on resume: a "
                        "mixed-precision/--moment_dtype change performs "
                        "an explicit, logged cast (moments follow the "
                        "migration policy table; the integrity manifest "
                        "is regenerated post-cast) instead of exiting 2 "
                        "(resilience/reshape.py)")
    p.add_argument("--recalibrate_steps", type=int, default=None,
                   help="after a TP-width int8-amax migration, hold the "
                        "remapped scales frozen for this many dispatches "
                        "before the decaying-max update resumes "
                        "(default 0 = trust the closed-form remap)")
    # --- self-healing knobs (p2p_tpu.resilience.health) -------------------
    p.add_argument("--health", action=argparse.BooleanOptionalAction,
                   default=None,
                   help="divergence sentinel + recovery ladder (skip -> "
                        "LR cooldown -> rollback to the last-good "
                        "checkpoint; docs/RESILIENCE.md). On by default; "
                        "--no-health disables both the sentinel and the "
                        "in-step skip guard")
    p.add_argument("--ema_decay", type=float, default=None,
                   help="EMA generator decay (e.g. 0.999): TrainState "
                        "carries smoothed G weights, eval/serve use them "
                        "(0 = EMA tracks raw params exactly — the parity "
                        "mode; unset = off)")
    p.add_argument("--max_rollbacks", type=int, default=None,
                   help="rollbacks to the last-good checkpoint before the "
                        "run gives up with exit code 76 (default 3)")
    p.add_argument("--spike_zscore", type=float, default=None,
                   help="robust z-score over the loss window above which "
                        "a step classifies as a spike (default 6.0)")
    p.add_argument("--cooldown_steps", type=int, default=None,
                   help="steps the ladder's LR cooldown (rung 2) holds "
                        "the reduced LR before restoring (default 20)")
    p.add_argument("--health_window", type=int, default=None,
                   help="healthy steps in the sentinel's robust z-score "
                        "window (default 32)")
    # --- telemetry / debug knobs (p2p_tpu.obs) ----------------------------
    p.add_argument("--check_finite", action="store_true", default=None,
                   help="host-side non-finite guard on the step metrics "
                        "after every dispatch: emits a kind=nonfinite "
                        "record, then raises (fences each dispatch — "
                        "debug tool)")
    p.add_argument("--nan_sentinel", action="store_true", default=None,
                   help="in-jit NaN/Inf sentinel on the step losses via "
                        "jax.debug.callback (async, no fence on the "
                        "happy path) — events land in the metrics JSONL")
    p.add_argument("--grad_norms", action="store_true", default=None,
                   help="add grad_norm_g/d global-norm scalars to the "
                        "per-step metrics stream")
    p.add_argument("--tensorboard", action="store_true",
                   help="also write scalar records to TensorBoard event "
                        "files under <workdir>/tb/<name>")
    p.add_argument("--prom_textfile", type=str, default=None,
                   help="export registry metrics in Prometheus textfile "
                        "format to this path (atomic rewrite; point "
                        "node_exporter's textfile collector at its dir)")
    # --- reference flags (train.py:133-157), same names/defaults ---------
    p.add_argument("--dataset", type=str, default=None, help="facades")
    p.add_argument("--name", type=str, default=None, help="training name")
    p.add_argument("--epoch_count", type=int, default=None)
    p.add_argument("--nepoch", type=int, default=None)
    p.add_argument("--niter", type=int, default=None)
    p.add_argument("--niter_decay", type=int, default=None)
    p.add_argument("--cuda", action="store_true",
                   help="accepted for parity; ignored (always TPU/XLA)")
    p.add_argument("--epochsave", type=int, default=None)
    p.add_argument("--batch_size", type=int, default=None)
    p.add_argument("--test_batch_size", type=int, default=None)
    p.add_argument("--direction", type=str, default=None, help="a2b or b2a")
    p.add_argument("--input_nc", type=int, default=None)
    p.add_argument("--output_nc", type=int, default=None)
    p.add_argument("--label_classes", type=int, default=None,
                   help="label-map presets: the number of class ids of "
                        "the conditioning map (sets input_nc = classes + "
                        "the edge channel)")
    from p2p_tpu.cli import add_vq_flags

    add_vq_flags(p)
    p.add_argument("--ngf", type=int, default=None)
    p.add_argument("--ndf", type=int, default=None)
    p.add_argument("--lr", type=float, default=None)
    p.add_argument("--lr_policy", type=str, default=None,
                   help="lambda|step|plateau|cosine")
    p.add_argument("--lr_decay_iters", type=int, default=None)
    p.add_argument("--beta1", type=float, default=None)
    p.add_argument("--moment_dtype", type=str, default=None,
                   help="Adam moment STORAGE dtype (e.g. bfloat16): halves "
                        "optimizer-state HBM traffic, update math stays f32 "
                        "(train/state.py scale_by_adam_lp)")
    p.add_argument("--threads", type=int, default=None)
    p.add_argument("--seed", type=int, default=None)
    p.add_argument("--lamb", type=float, default=None,
                   help="L1 weight (dead in the reference — Q3; live here)")
    p.add_argument("--lambda_vgg", type=float, default=None,
                   help="VGG perceptual weight (reference 10.0; set 0 when "
                        "no pretrained VGG asset exists — the random-feature "
                        "fallback at x10 can destabilize training)")
    p.add_argument("--lambda_feat", type=float, default=None,
                   help="feature-matching weight (reference 10.0)")
    p.add_argument("--lambda_tv", type=float, default=None,
                   help="total-variation weight (reference 1.0)")
    p.add_argument("--lambda_sobel", type=float, default=None,
                   help="Sobel edge-L1 weight (the reference's commented "
                        "edge experiment, train.py:362-363; 0 = off)")
    p.add_argument("--sobel_warmup_epochs", type=int, default=None,
                   help="ramp the sobel weight linearly over this many "
                        "epochs (reference train.py:445-448; 0 = constant)")
    p.add_argument("--lambda_angular", type=float, default=None,
                   help="mean-angular-error weight (the reference's "
                        "commented experiment, train.py:355-360; 0 = off)")
    p.add_argument("--grad_clip", type=float, default=None,
                   help="global-norm gradient clipping (0 = off; guards "
                        "per-sample-norm backward blowups on degenerate "
                        "images — see train/state.py)")
    p.add_argument("--pool_size", type=int, default=None,
                   help="historical-fake pool fed to D (reference "
                        "ImagePool(0) = passthrough); >0 enables a "
                        "device-side ring buffer. Image presets only — "
                        "the video step has no pool")
    p.add_argument("--save_masks", action="store_true", default=None,
                   help="dump mask.png = bitwise_and(uint8(fake_b), "
                        "uint8(real_a)) with the eval samples (the "
                        "reference's commented masking experiment, "
                        "train.py:324-334; visualization only)")
    p.add_argument("--eval_fid", action="store_true", default=None,
                   help="compute FID (VFID for video presets) per eval epoch "
                        "from VGG19 features; the feature source "
                        "(pretrained npz vs random init) is reported")
    p.add_argument("--scan_steps", type=int, default=None,
                   help="train steps fused into one lax.scan dispatch "
                        "(amortizes host dispatch latency; metrics are still "
                        "logged per step)")
    p.add_argument("--log_every", type=int, default=None,
                   help="per-step metrics record + stdout heartbeat cadence "
                        "(TrainConfig.log_every; epoch/eval records are "
                        "always written)")
    p.add_argument("--phase", choices=["global", "full"], default=None,
                   help="pix2pixHD coarse-to-fine schedule: 'global' trains "
                        "G1 alone at half resolution (checkpoints under "
                        "<name>_g1); 'full' trains the enhancer-wrapped "
                        "generator with the phase-1 G1 weights grafted in")
    p.add_argument("--init_g1_from", type=str, default=None,
                   help="explicit phase-1 checkpoint dir for --phase full "
                        "(default: checkpoint/<dataset>/<name>_g1)")
    return p


def config_from_flags(args: argparse.Namespace) -> Config:
    """Build a Config: preset defaults overridden by explicitly-set flags."""
    cfg = get_preset(args.preset)
    model, loss, optim, data, train, par = (
        cfg.model, cfg.loss, cfg.optim, cfg.data, cfg.train, cfg.parallel
    )
    from p2p_tpu.cli import apply_overrides as over
    from p2p_tpu.cli import with_label_classes

    model = over(model, input_nc=args.input_nc, output_nc=args.output_nc,
                 ngf=args.ngf, ndf=args.ndf, n_blocks=args.n_blocks,
                 int8=args.int8,
                 int8_generator=args.int8_generator,
                 int8_delayed=args.int8_delayed,
                 int8_stem=args.int8_stem, int8_head=args.int8_head,
                 int8_compression=args.int8_compression,
                 int8_fused_epilogue=args.int8_fused_epilogue,
                 legacy_layout=args.legacy_layout, norm_d=args.norm_d)
    from p2p_tpu.cli import with_vq_sizes

    model = with_vq_sizes(with_label_classes(model, args.label_classes),
                          args)
    loss = over(loss, lambda_l1=args.lamb, lambda_vgg=args.lambda_vgg,
                lambda_feat=args.lambda_feat, lambda_tv=args.lambda_tv,
                lambda_sobel=args.lambda_sobel,
                sobel_warmup_epochs=args.sobel_warmup_epochs,
                lambda_angular=args.lambda_angular)
    optim = over(optim, lr=args.lr, lr_policy=args.lr_policy,
                 lr_decay_iters=args.lr_decay_iters, beta1=args.beta1,
                 niter=args.niter, niter_decay=args.niter_decay,
                 grad_clip=args.grad_clip, moment_dtype=args.moment_dtype)
    data = over(data, dataset=args.dataset, direction=args.direction,
                batch_size=args.batch_size, image_size=args.image_size,
                image_width=args.image_width,
                test_batch_size=args.test_batch_size, threads=args.threads,
                augment=args.augment)
    if args.image_size is not None and args.image_width is None and \
            data.image_width is not None:
        # an explicit square --image_size overrides a rectangular preset
        # wholesale (halving only one dim silently breaks aspect handling)
        data = dataclasses.replace(data, image_width=None)
    train = over(train, nepoch=args.nepoch, epoch_count=args.epoch_count,
                 epoch_save=args.epochsave, seed=args.seed,
                 eval_fid=args.eval_fid, scan_steps=args.scan_steps,
                 pool_size=args.pool_size, save_masks=args.save_masks,
                 log_every=args.log_every,
                 compilation_cache_dir=args.compilation_cache,
                 elastic=args.elastic,
                 cast_on_restore=args.cast_on_restore,
                 recalibrate_steps=args.recalibrate_steps)
    debug = over(cfg.debug, check_finite=args.check_finite,
                 nan_sentinel=args.nan_sentinel, grad_norms=args.grad_norms)
    health = over(cfg.health, enabled=args.health,
                  ema_decay=args.ema_decay,
                  max_rollbacks=args.max_rollbacks,
                  spike_zscore=args.spike_zscore,
                  cooldown_steps=args.cooldown_steps,
                  window=args.health_window)
    par = over(par, tp_min_ch=args.tp_min_ch, pp_overlap=args.pp_overlap,
               fsdp_params=args.fsdp_params)
    if args.mesh is not None:
        from p2p_tpu.core.mesh import parse_mesh_arg

        try:
            spec = parse_mesh_arg(args.mesh)
        except ValueError as e:
            raise SystemExit(
                f"--mesh must be 'data,spatial,time[,model[,pipe]]' "
                f"comma-separated ints or named 'axis=size,...' (got "
                f"{args.mesh!r}: {e})"
            )
        par = dataclasses.replace(par, mesh=spec)
    name = args.name or cfg.name
    cfg = dataclasses.replace(
        cfg, name=name, model=model, loss=loss, optim=optim, data=data,
        train=train, parallel=par, debug=debug, health=health,
    )
    if getattr(args, "phase", None) == "global":
        # coarse-to-fine phase 1 — applied AFTER flag overrides so an
        # explicit --image_size/--name is halved/suffixed consistently,
        # and with the same helper phase 2 uses to locate the checkpoint.
        from p2p_tpu.train.graft import g1_phase_config

        cfg = g1_phase_config(cfg)
    return cfg


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    if args.cuda:
        print("note: --cuda accepted for parity but ignored (TPU/XLA build)",
              file=sys.stderr)
    cfg = config_from_flags(args)
    from p2p_tpu.core.cache import enable_compilation_cache

    # every entry point compiles through the persistent cache; WHERE is
    # core/cache.py's one rule (env > flag > fixed in-checkout dir)
    enable_compilation_cache(args.compilation_cache)

    if cfg.data.n_frames > 1:
        from p2p_tpu.train.video_loop import VideoTrainer as Trainer
    else:
        from p2p_tpu.train.loop import Trainer

    trainer = Trainer(cfg, data_root=args.data_root, workdir=args.workdir)
    if args.tensorboard:
        import os

        from p2p_tpu.obs import TensorBoardSink

        try:
            trainer.logger.registry.add_sink(
                TensorBoardSink(os.path.join(args.workdir, "tb", cfg.name)))
        except ImportError as e:
            print(f"note: --tensorboard unavailable ({e}); continuing "
                  "with JSONL/stdout only", file=sys.stderr)
    if args.prom_textfile:
        from p2p_tpu.obs import PrometheusTextfileSink

        trainer.logger.registry.add_sink(PrometheusTextfileSink(
            args.prom_textfile, trainer.logger.registry))
    from p2p_tpu.core.mesh import TopologyMismatch

    try:
        resumed = trainer.maybe_resume()
    except TopologyMismatch as tm:
        # an elastic relaunch hit a delta the resharded-resume path cannot
        # reconcile (or --no-elastic forbade reconciling it). This is a
        # flags problem, not a transient: exit 2, NOT 75 — "re-run these
        # flags" would hit the same wall.
        print(f"topology mismatch: {tm}", file=sys.stderr, flush=True)
        return 2
    if resumed:
        print(f"resumed at epoch {trainer.epoch}")
    elif getattr(args, "phase", None) == "full":
        # coarse-to-fine phase 2: graft the phase-1 G1 checkpoint
        # (<name>_g1) into the full generator before training starts.
        from p2p_tpu.train.graft import load_and_graft_g1

        trainer.state = load_and_graft_g1(
            trainer.state, cfg, workdir=args.workdir,
            g1_dir=args.init_g1_from, mesh=getattr(trainer, "mesh", None),
        )
    from p2p_tpu.resilience import (
        DIVERGED_EXIT_CODE,
        PREEMPTED_EXIT_CODE,
        DivergenceError,
        Preempted,
    )

    try:
        trainer.fit()
    except Preempted as p:
        # graceful preemption (SIGTERM/SIGINT): the exact step is on disk —
        # exit 75 (EX_TEMPFAIL) tells the supervisor "re-run these flags";
        # the relaunch lands in maybe_resume's exact-step path above.
        print(f"preempted: checkpoint saved at step {p.step} — "
              f"relaunch with identical flags to resume "
              f"(exit {PREEMPTED_EXIT_CODE})", flush=True)
        return PREEMPTED_EXIT_CODE
    except DivergenceError as d:
        # the recovery ladder is exhausted: rolled back max_rollbacks
        # times and diverged again. Exit 76 — DISTINCT from preemption's
        # 75, because "relaunch with identical flags" would just diverge
        # again; this needs a human (or a config change).
        print(f"diverged: {d} (exit {DIVERGED_EXIT_CODE})", flush=True)
        trainer.logger.registry.flush()
        return DIVERGED_EXIT_CODE
    finally:
        trainer.close()  # unhook compile listener + sentinel handler
    return 0


if __name__ == "__main__":
    sys.exit(main())
