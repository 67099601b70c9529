"""One Fourier unit on the attached chip, both forms of its two transforms:
XLA's ``fft`` (``jnp.fft.rfft2`` / ``irfft2``, what ``models/ffc.py`` called
until PR 42) against the real DFT matrix products it calls now
(``ffc.rfft2`` / ``ffc.irfft2``), at the shape the cell
``big_lama_places256.train`` runs a unit (``[16, 32, 32, 192]``, bf16
activations, float32 transforms).

    chiprun -- python scripts/fourier_unit_bench.py [--profile]

Prints one JSON line a (what, form): the milliseconds of ``FourierUnit``
whole (rfft2, 1x1 convolution, BatchNorm, ReLU, irfft2: the layout copies
XLA puts round the transforms count) and of the transform pair alone (a
ReLU between them), forward + backward, as the mean of ``--units`` of them
chained in ONE program (a call a unit is bound by the host's dispatch, and
a program that never reads the forward's output loses the forward); with
``--profile`` the costliest device ops of a unit too. Then the precision
reading: each form's ``rfft2`` of a float32 tensor and ``irfft2`` of a
NON-Hermitian spectrum (the unit's is one: it comes out of a convolution,
BatchNorm and a ReLU) against numpy's float64 transforms of the same
numbers on the host, as the largest error over the largest value. The
lines also go to ``chiprun_out/fourier_unit_bench.jsonl``. Without a TPU it
exits 2 (``--allow_cpu --scale 4`` rehearses the control flow at a toy
size; no time of that means anything). A reading of one unit alone: the
chained tensors fit the chip's VMEM, the step's come from HBM, and the
whole step's trace has the last word (PERF.md section 6, PR 42).
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

BATCH, EXTENT, CHANNELS = 16, 32, 192


def fft_pair():
    """``(rfft2, irfft2)`` on XLA's ``fft`` with ``models/ffc.py``'s
    signatures: the lines the module held until PR 42."""
    import jax
    import jax.numpy as jnp

    def rfft2(x):
        z = jnp.fft.rfft2(x, axes=(1, 2), norm="ortho")
        return jnp.stack([z.real, z.imag], axis=-1)

    def irfft2(z, w):
        return jnp.fft.irfft2(jax.lax.complex(z[..., 0], z[..., 1]),
                              s=(z.shape[1], w), axes=(1, 2), norm="ortho")

    return rfft2, irfft2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--units", type=int, default=12)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--profile", action="store_true",
                    help="also a unit's costliest device ops, each form")
    ap.add_argument("--allow_cpu", action="store_true")
    ap.add_argument("--scale", type=int, default=1,
                    help="divide batch, extent and channels by this")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from p2p_tpu.models import ffc
    from scripts.thin_conv_bench import device_ops, time_ms

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.allow_cpu:
        print(f"no TPU: {dev}", file=sys.stderr)
        return 2
    n, hw, c = (BATCH // args.scale, EXTENT // args.scale,
                CHANNELS // args.scale)
    rng = np.random.default_rng(args.seed)
    x32 = rng.standard_normal((n, hw, hw, c)).astype(np.float32)
    ct = jnp.asarray(rng.standard_normal(x32.shape).astype(np.float32))
    x = jnp.asarray(x32).astype(jnp.bfloat16)
    forms = {"fft": fft_pair(), "dft": (ffc.rfft2, ffc.irfft2)}
    lines = []

    def say(**line):
        line["device"] = dev.device_kind
        print(json.dumps(line), flush=True)
        lines.append(line)

    module = ffc.FourierUnit(dtype=jnp.bfloat16)
    variables = module.init(jax.random.key(args.seed), x, True)
    params, stats = variables["params"], variables["batch_stats"]

    def unit(p, v):
        return module.apply({"params": p, "batch_stats": stats}, v, True,
                            mutable=["batch_stats"])[0]

    def pair(p, v):
        del p
        z = ffc.rfft2(v.astype(jnp.float32)).astype(v.dtype)
        return ffc.irfft2(jax.nn.relu(z).astype(jnp.float32),
                          v.shape[2]).astype(v.dtype)

    def chained(fn):
        def units(p, x0):
            def one(_, carry):
                xx, acc, _ = carry
                y, vjp = jax.vjp(fn, p, xx)
                dp, dx = vjp(y + ct.astype(y.dtype))
                return (xx + 0.01 * jnp.tanh(dx), jax.tree_util.tree_map(
                    jnp.add, acc, dp), dx)
            return jax.lax.fori_loop(
                0, args.units, one,
                (x0, jax.tree_util.tree_map(jnp.zeros_like, p),
                 jnp.zeros_like(x0)))
        return jax.jit(units)

    module_pair = ffc.rfft2, ffc.irfft2
    want = {}
    try:
        for what, fn in (("unit", unit), ("pair", pair)):
            for form, transforms in forms.items():
                # the module looks its two transforms up when it is traced
                ffc.rfft2, ffc.irfft2 = transforms
                run = chained(fn)
                # the last unit's gradient to its input
                got = np.asarray(run(params, x)[2], np.float32)
                want.setdefault(what, got)
                say(what=what, form=form,
                    forward_backward_ms=time_ms(
                        run, (params, x), args.iters) / args.units,
                    gap_to_fft=float(np.abs(got - want[what]).max()
                                     / np.abs(want[what]).max()))
                if args.profile:
                    ops = device_ops(run, (params, x), 2, top=14)
                    say(what=what, form=form,
                        busy_ms_a_unit=ops["busy_ms"] / args.units,
                        ops_ms_a_unit=[[label, ms / args.units]
                                       for label, ms in ops["ops"]])
    finally:
        ffc.rfft2, ffc.irfft2 = module_pair

    # the precision reading: each form against numpy's float64 on the host
    z32 = rng.standard_normal((n, hw, hw // 2 + 1, c, 2)).astype(np.float32)
    z64 = z32[..., 0].astype(np.float64) + 1j * z32[..., 1]
    spectrum = np.fft.rfft2(x32.astype(np.float64), axes=(1, 2),
                            norm="ortho")
    spectrum = np.stack([spectrum.real, spectrum.imag], -1)
    image = np.fft.irfft2(z64, s=(hw, hw), axes=(1, 2), norm="ortho")
    for form, (rfft2, irfft2) in forms.items():
        got = np.asarray(jax.jit(rfft2)(jnp.asarray(x32)), np.float64)
        back = np.asarray(jax.jit(lambda z: irfft2(z, hw))(jnp.asarray(z32)),
                          np.float64)
        say(precision=form,
            rfft2_max_error=float(np.abs(got - spectrum).max()
                                  / np.abs(spectrum).max()),
            irfft2_max_error=float(np.abs(back - image).max()
                                   / np.abs(image).max()))
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/fourier_unit_bench.jsonl", "w") as f:
        f.writelines(json.dumps(ln) + "\n" for ln in lines)
    return 0


if __name__ == "__main__":
    sys.exit(main())
