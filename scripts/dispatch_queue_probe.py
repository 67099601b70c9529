"""How many computations may queue behind a running one before a dispatch
call blocks the host?

One long jitted function (~0.3 s on a v5e), then 80 tiny computations,
jitted or eager, dependent on the long one's output or not; prints each
dispatch call's microseconds and the first call that took over 5 ms.

    chiprun -- python scripts/dispatch_queue_probe.py

On "TPU v5 lite" with jax 0.9.0 (and on the CPU backend) call 31 after
the long one blocks until the long one is done: 32 computations in
flight a device, the 33rd call waits for the oldest (PERF.md section 6,
PR 37). A training loop that issues more than that between two steps
runs at the device's pace plus its own feed and dispatch.
"""
import json
import time

import jax
import jax.numpy as jnp


def main():
    dev = jax.devices()[0]
    print(json.dumps({"probe": "queue", "platform": dev.platform,
                      "kind": dev.device_kind, "jax": jax.__version__}))
    x = jnp.ones((4096, 4096), jnp.bfloat16) * 0.001

    @jax.jit
    def long(x):
        def body(i, x):
            return jnp.tanh(x @ x) * 0.01
        y = jax.lax.fori_loop(0, 400, body, x)
        return y, jnp.sum(y.astype(jnp.float32))

    tiny = jax.jit(lambda s: s + 1.0)
    one = jnp.float32(1.0)
    # warm every shape
    y, s = long(x)
    s2 = tiny(s)
    s3 = jnp.add(s, one)
    s4 = jnp.where(s >= 0.5, s, jnp.zeros_like(s))
    jax.block_until_ready((y, s2, s3, s4))
    t0 = time.perf_counter()
    y, s = long(x)
    jax.block_until_ready(s)
    long_s = time.perf_counter() - t0
    print(json.dumps({"long_s": long_s}))
    for kind in ("jit_dependent", "eager_add_dependent",
                 "eager_add_independent", "eager_where_dependent"):
        for rep in range(3):
            free = jnp.float32(2.0)
            jax.block_until_ready(free)
            t_start = time.perf_counter()
            y, s = long(x)
            t_long = time.perf_counter() - t_start
            secs = []
            for i in range(80):
                t0 = time.perf_counter()
                if kind == "jit_dependent":
                    s = tiny(s)
                elif kind == "eager_add_dependent":
                    s = jnp.add(s, one)
                elif kind == "eager_where_dependent":
                    s = jnp.where(s >= 0.5, s, jnp.zeros_like(s))
                else:
                    free = jnp.add(free, one)
                secs.append(time.perf_counter() - t0)
            t_issued = time.perf_counter() - t_start
            jax.block_until_ready((s, free))
            t_done = time.perf_counter() - t_start
            first_slow = next((i for i, v in enumerate(secs) if v > 0.005), None)
            print(json.dumps({
                "kind": kind, "rep": rep, "long_dispatch_s": round(t_long, 6),
                "issued_after_s": round(t_issued, 4),
                "all_done_after_s": round(t_done, 4),
                "first_call_over_5ms": first_slow,
                "that_call_s": None if first_slow is None
                else round(secs[first_slow], 4),
                "median_call_us": round(sorted(secs)[len(secs) // 2] * 1e6, 1),
                "calls_us": [round(v * 1e6) for v in secs]}))


if __name__ == "__main__":
    main()
