"""Inference CLI — batched generator inference from a training checkpoint.

Replaces the reference's test.py (test.py:1-46), which loads a pickled
module file train.py never writes (SURVEY Q5). Inference restores from the
SAME Orbax checkpoint the trainer saves — but through the serving engine
(p2p_tpu.serve): a params-only subtree restore (never materializing the
discriminator or optimizer state), a small set of AOT-compiled batch
buckets (the final partial batch pads up to a bucket instead of
recompiling), and thread-pooled PNG encoding that overlaps device compute.

Flag parity with test.py (--dataset/--direction/--cuda) plus checkpoint
addressing by step (--step, default latest). ``--ndf``/``--pool_size`` are
accepted-but-ignored (like --cuda): the params-only restore no longer needs
discriminator/pool hyperparameters to rebuild a checkpoint template.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description="p2p_tpu inference")
    p.add_argument("--preset", type=str, default="reference")
    p.add_argument("--name", type=str, default=None,
                   help="training name (checkpoint subdir; default preset name)")
    p.add_argument("--dataset", type=str, default=None, help="facades")
    p.add_argument("--direction", type=str, default=None, help="a2b or b2a")
    p.add_argument("--cuda", action="store_true",
                   help="accepted for parity; ignored (always TPU/XLA)")
    p.add_argument("--step", type=int, default=None,
                   help="checkpoint step to load (default: latest)")
    p.add_argument("--data_root", type=str, default=None)
    p.add_argument("--workdir", type=str, default=".")
    p.add_argument("--out", type=str, default=None,
                   help="output dir (default <workdir>/result/<dataset>)")
    p.add_argument("--batch_size", type=int, default=None)
    p.add_argument("--image_size", type=int, default=None)
    p.add_argument("--ngf", type=int, default=None)
    p.add_argument("--ndf", type=int, default=None,
                   help="image presets: accepted-but-ignored (params-only "
                        "restore never rebuilds the discriminator); video "
                        "presets still restore the FULL state and need "
                        "the trained value")
    p.add_argument("--n_blocks", type=int, default=None)
    p.add_argument("--image_width", type=int, default=None)
    p.add_argument("--label_classes", type=int, default=None,
                   help="label-map presets: the number of class ids the "
                        "checkpoint was trained with")
    from p2p_tpu.cli import add_vq_flags

    add_vq_flags(p)
    p.add_argument("--mask_dir", type=str, default=None,
                   help="inpainting presets: where the masks lie, one a "
                        "test image under the image's file name, nonzero = "
                        "a pixel to fill (default <data_root>/test/mask)")
    p.add_argument("--metrics", action="store_true",
                   help="also print mean/max PSNR+SSIM vs the targets")
    p.add_argument("--ema_decay", type=float, default=None,
                   help="the checkpoint was trained with --ema_decay: "
                        "restore the EMA generator weights too and serve "
                        "the SMOOTHED G (bitwise == raw at decay 0)")
    p.add_argument("--pool_size", type=int, default=None,
                   help="image presets: accepted-but-ignored (params-only "
                        "restore never rebuilds the fake pool); video "
                        "presets still restore the FULL state and need "
                        "the trained value")
    # --- serving-engine knobs (p2p_tpu.serve; docs/SERVING.md) -----------
    p.add_argument("--buckets", type=str, default=None,
                   help="comma-separated batch buckets AOT-compiled at "
                        "startup (default: the test batch size; the tail "
                        "batch pads up to the smallest covering bucket)")
    p.add_argument("--dtype", type=str, default="bf16",
                   choices=["bf16", "f32"],
                   help="inference compute dtype policy (params stay f32; "
                        "delayed-int8 checkpoints additionally serve with "
                        "frozen activation scales)")
    p.add_argument("--mesh", type=str, default=None,
                   help="serving mesh: positional 'data,spatial,time"
                        "[,model]' or named 'axis=size,...'; model>1 "
                        "shards the generator tensor-parallel "
                        "(parallel/rules.py)")
    p.add_argument("--tp_min_ch", type=int, default=None,
                   help="smallest channel count the TP rule shards")
    p.add_argument("--io_threads", type=int, default=4,
                   help="PNG encode worker threads (overlap device compute)")
    p.add_argument("--compilation_cache", type=str, default=None,
                   metavar="DIR",
                   help="persistent XLA compilation cache dir: cold starts "
                        "load compiled bucket programs from disk")
    p.add_argument("--stats", action="store_true",
                   help="print the engine's fenced timing breakdown as a "
                        "JSON line (img/s, infer/encode/wall sec, compiles)")
    return p


def _parse_mesh(arg):
    if arg is None:
        return None
    from p2p_tpu.core.mesh import make_mesh, parse_mesh_arg

    try:
        spec = parse_mesh_arg(arg)
    except ValueError as e:
        raise SystemExit(
            f"--mesh must be 'data,spatial,time[,model[,pipe]]' "
            f"comma-separated ints or named 'axis=size,...' (got "
            f"{arg!r}: {e})")
    return make_mesh(spec)


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    if args.cuda:
        print("note: --cuda accepted for parity but ignored (TPU/XLA build)",
              file=sys.stderr)

    import dataclasses

    from p2p_tpu.core.cache import enable_compilation_cache
    from p2p_tpu.core.config import get_preset
    from p2p_tpu.data.pipeline import PairedImageDataset, make_loader

    enable_compilation_cache(args.compilation_cache)
    from p2p_tpu.serve import engine_from_checkpoint

    from p2p_tpu.cli import apply_overrides as over

    cfg = get_preset(args.preset)
    data = over(cfg.data, dataset=args.dataset, direction=args.direction,
                test_batch_size=args.batch_size, image_size=args.image_size,
                image_width=args.image_width)
    from p2p_tpu.cli import with_label_classes, with_vq_sizes

    model = with_vq_sizes(with_label_classes(
        over(cfg.model, ngf=args.ngf, n_blocks=args.n_blocks),
        args.label_classes), args)
    health = over(cfg.health, ema_decay=args.ema_decay)
    cfg = dataclasses.replace(cfg, data=data, model=model, health=health,
                              name=args.name or cfg.name)
    if cfg.data.n_frames > 1:
        # the video path restores the FULL TrainState (its own pytree), so
        # the template-rebuild knobs stay live there
        model = over(cfg.model, ndf=args.ndf)
        train = over(cfg.train, pool_size=args.pool_size)
        return _video_main(args, dataclasses.replace(cfg, model=model,
                                                     train=train))
    for flag in ("ndf", "pool_size"):
        if getattr(args, flag) is not None:
            print(f"note: --{flag} accepted for parity but ignored — "
                  "params-only restore needs no checkpoint template "
                  "beyond the generator", file=sys.stderr)

    if cfg.model.scale > 1:
        return _upscale_main(args, cfg)
    from p2p_tpu.models.registry import input_mask_channel

    if input_mask_channel(cfg.model) is not None:
        return _inpaint_main(args, cfg)

    root = args.data_root or os.path.join(cfg.data.root, cfg.data.dataset)
    ds_dtype = "uint8" if cfg.data.uint8_pipeline else "float32"
    try:
        ds = PairedImageDataset(
            root, "test", cfg.data.direction, cfg.data.image_size,
            cfg.data.image_width, dtype=ds_dtype,
            label_input=cfg.model.label_classes > 0,
        )
    except (RuntimeError, FileNotFoundError) as e:
        print(f"no test images under {root}: {e}", file=sys.stderr)
        return 1

    ckpt_dir = os.path.join(
        args.workdir, cfg.train.checkpoint_dir, cfg.data.dataset, cfg.name
    )
    bs = cfg.data.test_batch_size
    sample = ds[0]
    sample_batch = {
        k: np.broadcast_to(v, (bs,) + v.shape).copy() for k, v in sample.items()
    }
    buckets = ([int(b) for b in args.buckets.split(",")] if args.buckets
               else None)
    try:
        engine, step = engine_from_checkpoint(
            cfg, ckpt_dir, sample_batch, step=args.step,
            buckets=buckets or (bs,), dtype=args.dtype,
            mesh=_parse_mesh(args.mesh), tp_min_ch=args.tp_min_ch,
            # only compile the PSNR/SSIM tail into the bucket programs
            # when asked — metrics-off serving must not pay for them
            with_metrics=args.metrics,
            compilation_cache_dir=args.compilation_cache,
            io_workers=args.io_threads,
        )
    except FileNotFoundError as e:
        print(str(e), file=sys.stderr)
        return 1

    out_dir = args.out or os.path.join(
        args.workdir, cfg.train.result_dir, cfg.data.dataset
    )
    os.makedirs(out_dir, exist_ok=True)

    # drop_remainder=False: EVERY test image gets a prediction — the final
    # partial batch pads up to a compiled bucket (no tail recompile) and
    # its padding rows are masked out of files and metrics
    loader = make_loader(ds, bs, shuffle=False, num_epochs=1,
                         drop_remainder=False)
    stats, metrics = engine.run(
        loader, names=ds.names, out_dir=out_dir,
        collect_metrics=args.metrics,
    )
    print(f"wrote {stats.n_images} predictions (checkpoint step {step}) "
          f"to {out_dir}")
    if args.metrics and metrics.get("psnr"):
        psnrs, ssims = metrics["psnr"], metrics["ssim"]
        print(f"psnr_mean={np.mean(psnrs):.4f} psnr_max={np.max(psnrs):.4f} "
              f"ssim_mean={np.mean(ssims):.4f} ssim_max={np.max(ssims):.4f}")
    if args.stats:
        print(json.dumps({"kind": "serve_stats", **stats.as_dict()}))
    return 0


def _upscale_main(args, cfg) -> int:
    """A preset whose output is ``model.scale`` times its input
    (super-resolution): every image of the test split's input side, AT ITS
    OWN EXTENT, to its upscaled image. An input is padded below and to the
    right by mirroring (the SwinIR authors' test script) up to the multiple
    its generator needs (``models/registry.input_extent_multiple``), run
    through the serving engine of that padded extent (one engine, one
    restore and one compile an extent), and the result cropped back."""
    import dataclasses

    from PIL import Image

    from p2p_tpu.data.generate import is_image_file
    from p2p_tpu.models.registry import input_extent_multiple
    from p2p_tpu.serve import engine_from_checkpoint
    from p2p_tpu.utils.images import save_img

    root = args.data_root or os.path.join(cfg.data.root, cfg.data.dataset)
    in_dir = os.path.join(
        root, "test", "a" if cfg.data.direction == "a2b" else "b")
    try:
        names = sorted(f for f in os.listdir(in_dir) if is_image_file(f))
    except FileNotFoundError:
        names = []
    if not names:
        print(f"no test images under {in_dir}", file=sys.stderr)
        return 1
    if args.metrics:
        print("note: --metrics needs targets of one extent; ignored for an "
              "upscaling preset", file=sys.stderr)
    ckpt_dir = os.path.join(
        args.workdir, cfg.train.checkpoint_dir, cfg.data.dataset, cfg.name)
    out_dir = args.out or os.path.join(
        args.workdir, cfg.train.result_dir, cfg.data.dataset)
    os.makedirs(out_dir, exist_ok=True)
    s, m = cfg.model.scale, input_extent_multiple(cfg.model)
    engines, step = {}, None
    for name in names:
        img = np.asarray(Image.open(os.path.join(in_dir, name))
                         .convert("RGB"), np.uint8)
        h, w = img.shape[:2]
        ph, pw = -(-h // m) * m, -(-w // m) * m
        lq = np.pad(img, ((0, ph - h), (0, pw - w), (0, 0)),
                    mode="symmetric")
        if not cfg.data.uint8_pipeline:
            lq = ((lq.astype(np.float32) - np.float32(127.5))
                  * np.float32(1.0 / 127.5))
        if (ph, pw) not in engines:
            ext = dataclasses.replace(
                cfg, data=dataclasses.replace(
                    cfg.data, image_size=ph * s, image_width=pw * s))
            try:
                engines[ph, pw], step = engine_from_checkpoint(
                    ext, ckpt_dir, {"input": lq[None]}, step=args.step,
                    buckets=(1,), dtype=args.dtype,
                    mesh=_parse_mesh(args.mesh), tp_min_ch=args.tp_min_ch,
                    with_metrics=False,
                    compilation_cache_dir=args.compilation_cache,
                    io_workers=args.io_threads)
            except FileNotFoundError as e:
                print(str(e), file=sys.stderr)
                return 1
        pred, _, _ = engines[ph, pw].infer_batch({"input": lq[None]})
        save_img(np.asarray(pred[0], np.float32)[:h * s, :w * s],
                 os.path.join(out_dir, name))
    print(f"wrote {len(names)} x{s} predictions (checkpoint step {step}, "
          f"{len(engines)} extents) to {out_dir}")
    return 0


def _inpaint_main(args, cfg) -> int:
    """A preset whose input carries a mask (inpainting): every image of
    the test split's target side with the mask of the same file name
    (``--mask_dir``, nonzero = a pixel to fill), AT ITS OWN EXTENT, to the
    composite: the generator's prediction where the mask says so, the
    image's own pixels elsewhere. Image and mask are padded below and to
    the right by mirroring (the LaMa authors' ``pad_img_to_modulo``) up to
    the multiple the generator needs (``models/registry.
    input_extent_multiple``), run through the serving engine of that
    padded extent (one engine, one restore and one compile an extent), and
    the result cropped back. The model is fully convolutional: the extent
    it was trained at binds nothing."""
    import dataclasses

    from PIL import Image

    from p2p_tpu.data.generate import is_image_file
    from p2p_tpu.data.masks import masked_input
    from p2p_tpu.models.registry import input_extent_multiple
    from p2p_tpu.serve import engine_from_checkpoint
    from p2p_tpu.utils.images import save_img

    root = args.data_root or os.path.join(cfg.data.root, cfg.data.dataset)
    img_dir = os.path.join(
        root, "test", "b" if cfg.data.direction == "a2b" else "a")
    mask_dir = args.mask_dir or os.path.join(root, "test", "mask")
    try:
        names = sorted(f for f in os.listdir(img_dir) if is_image_file(f))
    except FileNotFoundError:
        names = []
    if not names:
        print(f"no test images under {img_dir}", file=sys.stderr)
        return 1
    if args.metrics:
        print("note: --metrics needs targets of one extent; ignored for an "
              "inpainting preset", file=sys.stderr)
    ckpt_dir = os.path.join(
        args.workdir, cfg.train.checkpoint_dir, cfg.data.dataset, cfg.name)
    out_dir = args.out or os.path.join(
        args.workdir, cfg.train.result_dir, cfg.data.dataset)
    os.makedirs(out_dir, exist_ok=True)
    m = input_extent_multiple(cfg.model)
    engines, step = {}, None
    for name in names:
        img = np.asarray(Image.open(os.path.join(img_dir, name))
                         .convert("RGB"), np.uint8)
        try:
            mask = np.asarray(Image.open(os.path.join(mask_dir, name))
                              .convert("L"), np.uint8) > 0
        except FileNotFoundError:
            print(f"no mask {os.path.join(mask_dir, name)} for {name}",
                  file=sys.stderr)
            return 1
        h, w = img.shape[:2]
        if mask.shape != (h, w):
            print(f"mask of {name} is {mask.shape[1]}x{mask.shape[0]}, the "
                  f"image {w}x{h}", file=sys.stderr)
            return 1
        ph, pw = -(-h // m) * m, -(-w // m) * m
        grow = lambda a: np.pad(  # noqa: E731
            a, ((0, ph - h), (0, pw - w)) + ((0, 0),) * (a.ndim - 2),
            mode="symmetric")
        img_p = grow(img)
        if not cfg.data.uint8_pipeline:
            img_p = ((img_p.astype(np.float32) - np.float32(127.5))
                     * np.float32(1.0 / 127.5))
        inp = masked_input(img_p, grow(mask))
        if (ph, pw) not in engines:
            ext = dataclasses.replace(
                cfg, data=dataclasses.replace(
                    cfg.data, image_size=ph, image_width=pw))
            try:
                engines[ph, pw], step = engine_from_checkpoint(
                    ext, ckpt_dir, {"input": inp[None]}, step=args.step,
                    buckets=(1,), dtype=args.dtype,
                    mesh=_parse_mesh(args.mesh), tp_min_ch=args.tp_min_ch,
                    with_metrics=False,
                    compilation_cache_dir=args.compilation_cache,
                    io_workers=args.io_threads)
            except FileNotFoundError as e:
                print(str(e), file=sys.stderr)
                return 1
        pred, _, _ = engines[ph, pw].infer_batch({"input": inp[None]})
        save_img(np.asarray(pred[0], np.float32)[:h, :w],
                 os.path.join(out_dir, name))
    print(f"wrote {len(names)} inpainted images (checkpoint step {step}, "
          f"{len(engines)} extents) to {out_dir}")
    return 0


def _video_main(args, cfg) -> int:
    """Clip inference: per-frame predictions written as
    <out>/<video>_<frame>.png (video configs, n_frames>1). Stays on the
    full-state restore path — the video TrainState has its own structure;
    engine coverage is image presets (docs/SERVING.md)."""
    import jax

    from p2p_tpu.data.pipeline import make_loader
    from p2p_tpu.data.video import VideoClipDataset
    from p2p_tpu.train.checkpoint import CheckpointManager
    from p2p_tpu.train.video_loop import build_video_eval_step
    from p2p_tpu.train.video_step import create_video_train_state
    from p2p_tpu.utils.images import save_img

    root = args.data_root or os.path.join(cfg.data.root, cfg.data.dataset)
    try:
        ds = VideoClipDataset(
            root, "test", cfg.data.direction, cfg.data.image_size,
            cfg.data.image_width, n_frames=cfg.data.n_frames,
        )
    except (RuntimeError, FileNotFoundError) as e:
        print(f"no test clips under {root}: {e}", file=sys.stderr)
        return 1

    ckpt_dir = os.path.join(
        args.workdir, cfg.train.checkpoint_dir, cfg.data.dataset, cfg.name
    )
    ckpt = CheckpointManager(ckpt_dir)
    step = args.step if args.step is not None else ckpt.latest_step()
    if step is None:
        print(f"no checkpoint found under {ckpt_dir}", file=sys.stderr)
        return 1

    bs = cfg.data.test_batch_size
    sample = ds[0]
    sample_batch = {
        k: np.broadcast_to(v, (bs,) + v.shape).copy() for k, v in sample.items()
    }
    state = create_video_train_state(cfg, jax.random.key(0), sample_batch)
    state = ckpt.restore(state, step)
    eval_step = build_video_eval_step(cfg)

    out_dir = args.out or os.path.join(
        args.workdir, cfg.train.result_dir, cfg.data.dataset
    )
    os.makedirs(out_dir, exist_ok=True)

    n_clip = 0
    n_frames = 0
    psnrs, ssims = [], []
    for batch in make_loader(ds, bs, shuffle=False, num_epochs=1,
                             drop_remainder=False):
        pred, metrics = eval_step(state, batch)
        pred = np.asarray(pred, np.float32)
        if args.metrics:
            psnrs.extend(np.asarray(metrics["psnr"]).ravel().tolist())
            ssims.extend(np.asarray(metrics["ssim"]).ravel().tolist())
        for i in range(pred.shape[0]):
            if n_clip >= len(ds):
                break
            vid, frames = ds.windows[n_clip]
            for t, fname in enumerate(frames):
                stem = os.path.splitext(fname)[0]
                save_img(pred[i, t], os.path.join(out_dir, f"{vid}_{stem}.png"))
                n_frames += 1
            n_clip += 1
    print(f"wrote {n_frames} frames / {n_clip} clips "
          f"(checkpoint step {step}) to {out_dir}")
    if args.metrics and psnrs:
        print(f"psnr_mean={np.mean(psnrs):.4f} psnr_max={np.max(psnrs):.4f} "
              f"ssim_mean={np.mean(ssims):.4f} ssim_max={np.max(ssims):.4f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
