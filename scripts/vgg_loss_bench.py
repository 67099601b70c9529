"""Time the perceptual loss alone on the attached chip: ``vgg_loss`` forward
+ its image gradient (what a train step asks of it) at the per-chip shapes
of the three benchmark cells, for float32 images (the trunk as ``nn.Conv``
promotes it), for bf16 images (activations stored in bf16) and for bf16
images through the promoted trunk (the program bf16 images got before the
stored path: ``VGG19Features()`` with no ``store_dtype``).

    chiprun -- python scripts/vgg_loss_bench.py [--only ref256] [--profile]

The twin of ``scripts/thin_conv_bench.py`` (whose clock and profile reader
it uses). Every (shape, path) runs in a process of its own, one after the
other, because ``peak_bytes_in_use`` is a high-water mark of the process;
this parent stays off jax so that each child finds the chip free. Prints
one JSON line a (shape, path): milliseconds a call (host clock over
``--iters`` calls, fenced once), the runtime's peaks (in use, and
reserved: a program's temporaries count there) and the compiled program's
temporaries; appends them to ``chiprun_out/vgg_loss_bench.jsonl``.
The whole step's trace (``scripts/conv_layer_trace.py``, scope
``loss_vgg``) has the last word.
"""

import argparse
import json
import os
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: name -> the images a chip's ``vgg_loss`` sees in a cell (N, H, W, 3)
SHAPES = {
    "ref256": (32, 256, 256, 3),      # reference_256.train
    "hd1024": (2, 512, 1024, 3),      # pix2pixhd_1024x512.train
    "hd2048": (1, 512, 2048, 3),      # a shard of ...train_spatial4
}
PATHS = ("f32_images", "bf16_images", "bf16_images_promoted")


def promoted_loss(params, x, y):
    """``vgg_loss`` over the float32 trunk, whatever the images."""
    from p2p_tpu.losses.perceptual import tap_distance
    from p2p_tpu.models.vgg import VGG19Features

    model = VGG19Features()
    return tap_distance(model.apply({"params": params}, x),
                        model.apply({"params": params}, y))


def one(args) -> int:
    import jax
    import jax.numpy as jnp

    from p2p_tpu.losses import vgg_loss
    from p2p_tpu.models.vgg import load_vgg19_params
    from thin_conv_bench import device_ops, time_ms

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.allow_cpu:
        print(f"no TPU: {dev}", file=sys.stderr)
        return 2
    n, h, w, c = SHAPES[args.shape]
    shape = (n, h // args.scale, w // args.scale, c)
    dtype = jnp.float32 if args.path == "f32_images" else jnp.bfloat16
    loss = promoted_loss if args.path.endswith("promoted") else vgg_loss
    params = load_vgg19_params()
    kx, ky = jax.random.split(jax.random.key(0))
    x = jnp.tanh(jax.random.normal(kx, shape)).astype(dtype)
    y = jnp.tanh(jax.random.normal(ky, shape)).astype(dtype)
    fn = jax.jit(jax.value_and_grad(lambda a, b: loss(params, a, b)))
    row = {"shape": args.shape, "images": list(shape), "path": args.path,
           "device": dev.device_kind}
    try:
        mem = fn.lower(x, y).compile().memory_analysis()
        row["temp_bytes"] = mem.temp_size_in_bytes
        row["fwd_grad_ms"] = time_ms(fn, (x, y), args.iters)
        # the runtime's in-use peak leaves a program's temporaries out;
        # its reserved peak holds them (benchmark/harness.py)
        stats = dev.memory_stats() or {}
        row["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
        row["peak_bytes_reserved"] = stats.get("peak_bytes_reserved")
        row["loss"] = float(fn(x, y)[0])
        if args.profile:
            row["fwd_grad_device"] = device_ops(fn, (x, y), 5)
    except Exception as e:  # a program the compiler refuses is a reading
        row["error"] = f"{type(e).__name__}: {str(e)[:200]}"
    print(json.dumps(row), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/vgg_loss_bench.jsonl", "a") as f:
        f.write(json.dumps(row) + "\n")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--only", default="", help="comma list of shapes")
    ap.add_argument("--paths", default="", help="comma list of paths")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--profile", action="store_true",
                    help="also the costliest device ops of a call")
    ap.add_argument("--allow_cpu", action="store_true")
    ap.add_argument("--scale", type=int, default=1,
                    help="divide every extent by this (CPU rehearsal)")
    ap.add_argument("--shape", choices=sorted(SHAPES), help=argparse.SUPPRESS)
    ap.add_argument("--path", choices=PATHS, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.shape:
        return one(args)
    argv = list(sys.argv[1:] if argv is None else argv)
    only = [s for s in args.only.split(",") if s] or list(SHAPES)
    paths = [p for p in args.paths.split(",") if p] or list(PATHS)
    worst = 0
    for shape in only:
        for path in paths:
            worst = max(worst, subprocess.run(
                [sys.executable, os.path.abspath(__file__), *argv,
                 "--shape", shape, "--path", path]).returncode)
    return worst


if __name__ == "__main__":
    sys.exit(main())
