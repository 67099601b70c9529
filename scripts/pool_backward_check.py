"""Does the chip compute the backward of D's input pyramid right?

    chiprun --timeout 240 -- python scripts/pool_backward_check.py

Each op's backward-to-input on the default device against the same jnp
function on the host CPU of the same process (float32, white-noise
cotangent): the 3x3 stride-2 average pool of the discriminators' pyramid
as ``benchmark/reference/nn.avg_pool_3s2`` has it (``plain``; the
program's ``models/patchgan.avg_pool_downsample`` is the same
``lax.reduce_window``, whose transpose is ONE base-dilated
``reduce-window``) and as ``benchmark/reference/pix2pixhd_2048x1024.py``
gives it a backward of its own (``fixed``: nine shifted copies on the
stride-2 grid), at the extents the references' row blocks and the
program's batches have; then three 4x4 stride-2 stems with the pools
between, the pyramid as ``train_step.discriminator`` builds it.

Read on one "TPU v5 lite" in PR 25 (my chip runs 7 and 8; PERF.md
section 6): at ``[1,1024,2048,6]`` the plain pool's backward came back
with norm 296.6 against the CPU's 591.8, relative difference 1.119
(uncorrelated with the truth), the pyramid 0.187 off, while at the three
smaller extents it agreed to 3e-8; the fixed pool agreed to the last bit
at all four and its pyramid to 4e-4; 165 s in all.
"""
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, ".")
from benchmark.reference import nn  # noqa: E402

T0 = time.time()


def norm(v):
    return float(np.linalg.norm(np.asarray(v, np.float64).ravel()))


def both(name, fn, *args):
    """``fn(*args)`` on the default device and on the host CPU."""
    dev, cpu = jax.devices()[0], jax.devices("cpu")[0]
    got, want = (np.asarray(jax.device_get(
        jax.jit(fn)(*jax.device_put(args, d)))) for d in (dev, cpu))
    print(json.dumps({
        "op": name, "seconds": round(time.time() - T0, 1),
        "device": dev.platform, "device_norm": norm(got),
        "cpu_norm": norm(want),
        "reldiff": norm(got - want) / max(norm(want), 1e-300)}), flush=True)


def pool_dx(pool):
    return lambda x, ct: jax.vjp(pool, x)[1](ct)[0]


def pyramid_dx(pool):
    def dx(x, kernels, cts):
        def loss(v):
            total = 0.0
            for k, c in zip(kernels, cts):
                total += jnp.vdot(nn.leaky_relu(
                    nn.zero_conv(v, k, None, stride=2, pad=2)), c)
                v = pool(v)
            return total
        return jax.grad(loss)(x)
    return dx


def main():
    from benchmark import harness

    fixed = harness.load_by_path("reference",
                                 "pix2pixhd_2048x1024").avg_pool_3s2
    pools = {"plain": nn._plain_avg_pool_3s2, "fixed": fixed}
    rng = np.random.default_rng(0)
    draw = lambda *s: rng.normal(size=s).astype(np.float32)  # noqa: E731
    # reference_256's row block, pix2pixhd_1024x512's row, the program's
    # batch there, this configuration's row
    for n, h, w in ((8, 256, 256), (1, 512, 1024), (2, 512, 1024),
                    (1, 1024, 2048)):
        x, ct = draw(n, h, w, 6), draw(n, h // 2, w // 2, 6)
        for name, pool in pools.items():
            both(f"{name} pool_dx[{n},{h},{w},6]", pool_dx(pool), x, ct)
    h, w = 1024, 2048
    x = draw(1, h, w, 6)
    kernels = [0.1 * draw(4, 4, 6, 64) for _ in range(3)]
    cts = [draw(1, h // 2 ** (i + 1) + 1, w // 2 ** (i + 1) + 1, 64)
           for i in range(3)]
    for name, pool in pools.items():
        both(f"{name} pyramid_dx[1,{h},{w},6]", pyramid_dx(pool), x,
             kernels, cts)


if __name__ == "__main__":
    main()
