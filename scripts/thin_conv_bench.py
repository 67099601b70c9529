"""Time the forms a thin image-side convolution can take, one layer at a
time on the attached chip: XLA's plain conv and the blocked form on blocks
of 2-16 pixels along W (``ops/conv.py``). (The im2col + matmul and kn2row
+ custom-VJP forms PR 24 read beside them lost and went in PR 27; their
readings are in PERF.md section 6, PR 24.)

    chiprun -- python scripts/thin_conv_bench.py [--only ref_head,hd_stem]

Each case is a layer of a preset at the extent and batch a benchmark cell
(or a preset with no cell) runs it: reflect pad + conv forward, and
forward + the gradients that layer's place in the step needs (a stem fed
by the image needs no input gradient). bf16 operands as the presets
compute. Prints one JSON line a (case, form) with the milliseconds of
each program (host clock over ``--iters`` calls, fenced once) and writes
them all to ``chiprun_out/thin_conv_bench.jsonl``. This is the reading
the gate constants in ``ops/conv.py`` are set from; the whole step's
trace (``scripts/conv_layer_trace.py``) has the last word.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: name -> (batch, H, W, C_in, C_out, k, needs the input gradient)
CASES = {
    # preset reference at 256x256, bs32 (cell reference_256.train)
    "ref_stem": (32, 256, 256, 12, 32, 9, True),      # C branch needs dx
    "ref_stem_nodx": (32, 256, 256, 12, 32, 9, False),  # G side
    "ref_head": (32, 256, 256, 32, 3, 9, True),
    "ref_cstem": (32, 256, 256, 3, 64, 5, False),     # compression net
    # preset pix2pixhd at 1024x512, bs2 (cell pix2pixhd_1024x512.train)
    "hd_stem": (2, 512, 1024, 3, 32, 7, False),
    "hd_head": (2, 512, 1024, 32, 3, 7, True),
    "g1_stem": (2, 256, 512, 3, 64, 7, False),
    # preset cityscapes at 512x256 (ResnetGenerator's head; no cell)
    "city_head": (2, 256, 512, 64, 3, 7, True),
}


def forms_for(cin, cout, w):
    import jax

    from p2p_tpu.ops import conv as C

    def plain(xp, wt):
        return jax.lax.conv_general_dilated(
            xp, wt.astype(xp.dtype), (1, 1), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))

    out = {"plain": plain}
    for s in (2, 4, 8, 16):
        if w % s == 0 and min(cin, cout) * s <= 96:
            out[f"blocked_s{s}"] = (
                lambda xp, wt, s=s: C.blocked_conv(xp, wt, s))
    return out


def time_ms(fn, args, iters):
    import jax

    for _ in range(2):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    out = None
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1000.0 * (time.perf_counter() - t0) / iters


def device_ops(fn, args, iters, top=8):
    """The costliest device ops of ``iters`` calls of ``fn``, as
    ``[name and shape, milliseconds a call]`` (one profiler capture)."""
    import shutil
    import tempfile

    import jax

    from benchmark import trace_reduce

    tmp = tempfile.mkdtemp(prefix="thin_conv_bench_")
    try:
        jax.profiler.start_trace(tmp)
        out = None
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        red = trace_reduce.reduce_trace(trace_reduce.find_xplane(tmp),
                                        top=top)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return {"busy_ms": 1000.0 * red["busy_s"] / iters,
            "ops": [[label, 1000.0 * sec / iters]
                    for label, sec in red["device_ops"]]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--only", default="")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--forms", default="",
                    help="comma list; default: every form of a case")
    ap.add_argument("--profile", action="store_true",
                    help="also the costliest device ops of forward+backward")
    ap.add_argument("--allow_cpu", action="store_true")
    ap.add_argument("--scale", type=int, default=1,
                    help="divide every extent by this (CPU rehearsal)")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from p2p_tpu.ops.conv import reflect_pad_2d

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.allow_cpu:
        print(f"no TPU: {dev}", file=sys.stderr)
        return 2
    only = [c for c in args.only.split(",") if c]
    forms = [f for f in args.forms.split(",") if f]
    os.makedirs("chiprun_out", exist_ok=True)
    rows = []
    for name, (n, h, w, cin, cout, k, need_dx) in CASES.items():
        if only and name not in only:
            continue
        h, w = h // args.scale, w // args.scale
        kx, kw_, kg = jax.random.split(jax.random.key(0), 3)
        x = jax.random.normal(kx, (n, h, w, cin), jnp.bfloat16)
        wt = 0.02 * jax.random.normal(kw_, (k, k, cin, cout), jnp.float32)
        g = jax.random.normal(kg, (n, h, w, cout), jnp.bfloat16)
        for form, conv in forms_for(cin, cout, w).items():
            if forms and form not in forms:
                continue

            def fwd(x, wt, conv=conv):
                return conv(reflect_pad_2d(x, k // 2), wt)

            def fwd_bwd(x, wt, g, fwd=fwd):
                y, vjp = jax.vjp(fwd, x, wt)
                dx, dw = vjp(g)
                return (y, dw, dx) if need_dx else (y, dw)

            row = {"case": name, "form": form, "shape": [n, h, w, cin, cout],
                   "k": k, "needs_dx": need_dx, "device": dev.device_kind}
            try:
                row["fwd_ms"] = time_ms(jax.jit(fwd), (x, wt), args.iters)
                both = jax.jit(fwd_bwd)
                row["fwd_bwd_ms"] = time_ms(both, (x, wt, g), args.iters)
                if args.profile:
                    row["fwd_bwd_device"] = device_ops(both, (x, wt, g), 5)
            except Exception as e:  # a form the compiler refuses is a reading
                row["error"] = f"{type(e).__name__}: {str(e)[:200]}"
            rows.append(row)
            print(json.dumps(row), flush=True)
    with open("chiprun_out/thin_conv_bench.jsonl", "a") as f:
        for row in rows:
            f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
