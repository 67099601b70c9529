"""Time the two forms an ``UpsampleConvLayer(k3, upsample=2)`` site can
take, one layer at a time on the attached chip: the plain chain (nearest
x2 -> reflect or zero pad -> k3 conv) against the subpixel form of
``ops/conv.py`` (``nearest_up2_conv``: one k3 conv ``ci -> 4*co`` on the
LOW-RES input padded by the pad mode's ring, edge or zeros, then
``depth_to_space_2x``).

    chiprun -- python scripts/up2_conv_bench.py [--only ref_up1,hd_enh] [--profile]

Each case is a k3-up2 site of a preset at the extent and batch a
benchmark cell runs it (a shard's view for the four-chip cell; one image
of ``reference`` too): forward, and forward + input gradient + weight
gradient, bf16 operands as the presets compute. Prints one JSON line a (case, form) with the
milliseconds of each program (``thin_conv_bench.time_ms``) and, on the
chip at the cases' own extents, writes them all to
``chiprun_out/up2_conv_bench.jsonl``; a form that fails ends the run with
its error. This is the reading the rule in ``ops/conv.nearest_up2_engages``
is set from (PERF.md section 6, PR 28 and PR 43); the whole step's trace
(``scripts/conv_layer_trace.py``) has the last word.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: name -> (batch, H, W, C_in, C_out[, pad mode]) of the layer's LOW-RES
#: input; the pad mode is UpsampleConvLayer's, "reflect" where not given
CASES = {
    # preset reference at 256x256, bs32 (cell reference_256.train)
    "ref_up1": (32, 128, 128, 64, 32),     # UpsampleConvLayer_1
    "ref_up0": (32, 64, 64, 128, 64),      # UpsampleConvLayer_0
    # one image of it, as cli.infer feeds the preset
    "ref_up1_bs1": (1, 128, 128, 64, 32),
    "ref_up0_bs1": (1, 64, 64, 128, 64),
    # preset pix2pixhd at 1024x512, bs2 (cell pix2pixhd_1024x512.train)
    "hd_enh": (2, 256, 512, 64, 32),       # the enhancer's upsample
    "hd_g1_last": (2, 128, 256, 128, 64),  # G1's fourth upsample
    "hd_g1_third": (2, 64, 128, 256, 128),
    "hd_g1_second": (2, 32, 64, 512, 256),  # at the pixel floor
    # one shard of pix2pixhd at 2048x1024 on data=2,spatial=2, bs1
    "hd4_enh": (1, 256, 1024, 64, 32),
    "hd4_g1_last": (1, 128, 512, 128, 64),
    "hd4_g1_third": (1, 64, 256, 256, 128),
    # preset swinir_realsr_x4 on 64x64 inputs, bs4 (cell
    # swinir_m_realsr_x4_gan.train): both sites pad with zeros
    "sr_up1": (4, 64, 64, 64, 64, "zero"),      # conv_up1
    "sr_up2": (4, 128, 128, 64, 64, "zero"),    # conv_up2
    # preset vqgan_imagenet_f16 at 256x256, bs12 (cell
    # vqgan_imagenet_f16_16384.train): the decoder's four, zero-padded
    "vq_up4": (12, 16, 16, 512, 512, "zero"),   # under the pixel floor
    "vq_up3": (12, 32, 32, 256, 256, "zero"),
    "vq_up2": (12, 64, 64, 256, 256, "zero"),
    "vq_up1": (12, 128, 128, 128, 128, "zero"),
}


def forms(pad_mode="reflect"):
    """name -> f(x, w): the layer without its bias, x (N,H,W,ci) bf16, w
    (3,3,ci,co) float32, as UpsampleConvLayer builds each form for
    ``pad_mode``."""
    import jax
    import jax.numpy as jnp

    from p2p_tpu.ops import conv as C

    def plain(x, w):
        up = C.upsample_nearest(x, 2)
        up = (C.reflect_pad_2d(up, 1) if pad_mode == "reflect"
              else jnp.pad(up, ((0, 0), (1, 1), (1, 1), (0, 0))))
        return jax.lax.conv_general_dilated(
            up, w.astype(x.dtype), (1, 1), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))

    def subpixel(x, w):
        return C.nearest_up2_conv(x, w, x.dtype, pad_mode)

    return {"plain": plain, "subpixel": subpixel}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--only", default="")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--profile", action="store_true",
                    help="also the costliest device ops of forward+backward")
    ap.add_argument("--allow_cpu", action="store_true",
                    help="a rehearsal: nothing is written")
    ap.add_argument("--scale", type=int, default=1,
                    help="divide every extent by this (a rehearsal too)")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from scripts.thin_conv_bench import device_ops, time_ms

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.allow_cpu:
        print(f"no TPU: {dev}", file=sys.stderr)
        return 2
    only = [c for c in args.only.split(",") if c]
    rows = []
    for name, (n, h, w, cin, cout, *pad_mode) in CASES.items():
        if only and name not in only:
            continue
        (pad_mode,) = pad_mode or ("reflect",)
        h, w = h // args.scale, w // args.scale
        kx, kw_, kg = jax.random.split(jax.random.key(0), 3)
        x = jax.random.normal(kx, (n, h, w, cin), jnp.bfloat16)
        wt = 0.02 * jax.random.normal(kw_, (3, 3, cin, cout), jnp.float32)
        g = jax.random.normal(kg, (n, 2 * h, 2 * w, cout), jnp.bfloat16)
        ref = None
        for form, fwd in forms(pad_mode).items():
            def fwd_bwd(x, wt, g, fwd=fwd):
                y, vjp = jax.vjp(fwd, x, wt)
                dx, dw = vjp(g)
                return y, dw, dx

            row = {"case": name, "form": form, "shape": [n, h, w, cin, cout],
                   "pad_mode": pad_mode, "device": dev.device_kind}
            row["fwd_ms"] = time_ms(jax.jit(fwd), (x, wt), args.iters)
            both = jax.jit(fwd_bwd)
            row["fwd_bwd_ms"] = time_ms(both, (x, wt, g), args.iters)
            # the forms are one function: the widest gap of (y, dw, dx)
            # to the first form's, over that tensor's largest entry
            outs = [jnp.asarray(o, jnp.float32) for o in both(x, wt, g)]
            if ref is None:
                ref = outs
            row["gap_to_first"] = [
                float(jnp.abs(a - b).max() / jnp.abs(b).max())
                for a, b in zip(outs, ref)]
            if args.profile:
                row["fwd_bwd_device"] = device_ops(both, (x, wt, g), 5)
            rows.append(row)
            print(json.dumps(row), flush=True)
    if dev.platform == "tpu" and args.scale == 1:
        os.makedirs("chiprun_out", exist_ok=True)
        with open("chiprun_out/up2_conv_bench.jsonl", "a") as f:
            for row in rows:
                f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
