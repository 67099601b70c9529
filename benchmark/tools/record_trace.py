"""Record a SMALL device trace for ``benchmark/tests/data``: a few jitted
convolutions under a host TraceAnnotation, with idle gaps between them.
Run on the chip; writes ``chiprun_out/small_trace.xplane.pb`` and prints
the planes, lines and a few events so the reduction can be read against
the raw layout.

    python benchmark/tools/record_trace.py
"""

import glob
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main() -> int:
    import jax
    import jax.numpy as jnp

    out = os.path.join("chiprun_out", "small_trace")
    shutil.rmtree(out, ignore_errors=True)
    x = jnp.ones((4, 128, 128, 64), jnp.bfloat16)
    w = jnp.ones((3, 3, 64, 64), jnp.bfloat16)

    @jax.jit
    def step(x, w):
        with jax.named_scope("net_a"):
            y = jax.lax.conv_general_dilated(
                x, w, (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
        with jax.named_scope("net_b"):
            return jnp.tanh(y).astype(jnp.bfloat16)

    step(x, w).block_until_ready()
    jax.profiler.start_trace(out)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("train_dispatch"):
            y = step(x, w)
        with jax.profiler.TraceAnnotation("bench_fence"):
            y.block_until_ready()
            time.sleep(0.02)
    jax.profiler.stop_trace()
    pb = sorted(glob.glob(os.path.join(
        out, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    dest = os.path.join("chiprun_out", "small_trace.xplane.pb")
    shutil.copy(pb, dest)
    shutil.rmtree(out, ignore_errors=True)
    print("bytes", os.path.getsize(dest))
    prof = jax.profiler.ProfileData.from_file(dest)
    for plane in prof.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            print("  LINE", repr(line.name), len(evs))
            for ev in evs[:4]:
                print("    EV", repr(ev.name), ev.start_ns, ev.duration_ns,
                      [(k, str(v)[:80]) for k, v in ev.stats][:8])
    from benchmark import trace_reduce

    print(trace_reduce.reduce_trace(dest, ("train_dispatch", "bench_fence")))
    return 0


if __name__ == "__main__":
    sys.exit(main())
