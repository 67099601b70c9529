"""One layer's window attention on the attached chip, both forms of
``ops/pallas/window_attention.py``: XLA's chain (``window_attention``)
against the kernel (``window_attention_fused``) at the shapes the cell
``swinir_m_realsr_x4_gan.train`` runs a layer (256 windows = 4 images x 64,
T = 64, C = 180, six heads, bf16 operands), shifted and not.

    chiprun -- python scripts/window_attention_bench.py [--blocks 8,16,32]

Prints one JSON line a (layer, form): the milliseconds a layer of
``models/swinir.WindowAttention`` whole (qkv, the attention, proj: the
layout copies XLA puts between them count, and they are most of what
differs), forward + backward, as the mean of ``--layers`` of them chained
in ONE program (a call a layer is bound by the host's dispatch, and a
program that never reads the forward's output loses the forward), and how
far the kernel's gradients lie from XLA's (``--profile``: the costliest
device ops of a layer too). Then the precision reading
``tests/test_window_attention.py`` makes interpreted, compiled here: the
output of each form, and of the kernel body with its softmax's
intermediates kept in bfloat16, against the float32 statement of the
function at HIGHEST precision on the same bf16-rounded operands (mean
absolute error; operands spread as ``benchmark/drivers/train_sr.WIDEN``
spreads them). The lines also go to
``chiprun_out/window_attention_bench.jsonl``. Without a TPU it exits 2 and
times nothing (the interpreted kernel is the tests' to read, and no time
of it means anything). A reading of one layer alone; the whole step's
trace has the last word (PERF.md section 6, PR 39).
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

WINDOW, HEADS, EMBED, IMAGES, EXTENT = 8, 6, 180, 4, 64


def operands(seed: int, dtype):
    """qkv, the bias table, the index, the shift mask and a cotangent:
    q and k of std 1.6 (logits of std ~2.6) and a table of std 1, as the
    cell's check widens a seeded start."""
    import jax.numpy as jnp
    import numpy as np

    from p2p_tpu.models.swinir import relative_position_index, shift_mask

    rng = np.random.default_rng(seed)
    b, t = IMAGES * (EXTENT // WINDOW) ** 2, WINDOW * WINDOW
    qkv = rng.standard_normal((b, t, 3 * EMBED)).astype(np.float32)
    qkv[..., :2 * EMBED] *= 1.6
    table = rng.standard_normal(((2 * WINDOW - 1) ** 2, HEADS)).astype(
        np.float32)
    ct = rng.standard_normal((b, t, EMBED)).astype(np.float32)
    return (jnp.asarray(qkv).astype(dtype), jnp.asarray(table),
            relative_position_index(WINDOW),
            shift_mask(EXTENT, EXTENT, WINDOW), jnp.asarray(ct))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--blocks", default="8,16,32",
                    help="windows a grid step, comma separated")
    ap.add_argument("--layers", type=int, default=12)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--profile", action="store_true",
                    help="also a layer's costliest device ops, each form")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from p2p_tpu.models.swinir import WindowAttention
    from p2p_tpu.ops.pallas import window_attention as wa
    from scripts.thin_conv_bench import device_ops, time_ms

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: {dev}", file=sys.stderr)
        return 2
    qkv, table, index, mask, ct = operands(args.seed, jnp.bfloat16)
    lines = []

    def say(**line):
        line["device"] = dev.device_kind
        print(json.dumps(line), flush=True)
        lines.append(line)

    # ---- a layer whole, both forms (the plan is the bench's to set) ------
    module = WindowAttention(heads=HEADS, window=WINDOW, dtype=jnp.bfloat16)
    x = qkv[..., :EMBED]
    plan = wa.kernel_plan
    wa.kernel_plan = lambda *a, **k: (0, False)
    params = module.init(jax.random.key(args.seed), x, mask)["params"]

    def chained(m):
        def layers(p, x0):
            def layer(_, carry):
                xx, acc = carry
                y, vjp = jax.vjp(lambda pp, v: module.apply(
                    {"params": pp}, v, m), p, xx)
                dp, dx = vjp(y + ct.astype(y.dtype))
                return (xx + 0.01 * jnp.tanh(dx), jax.tree_util.tree_map(
                    jnp.add, acc, dp))
            return jax.lax.fori_loop(
                0, args.layers, layer,
                (x0, jax.tree_util.tree_map(jnp.zeros_like, p)))
        return jax.jit(layers)

    for layer, m in (("unshifted", None), ("shifted", mask)):
        plans = {"xla": 0}
        for wb in (int(w) for w in args.blocks.split(",")):
            if (mask.shape[0] if m is not None else x.shape[0]) % wb == 0:
                plans[f"kernel_wb{wb}"] = wb
        want = None
        for name, wb in plans.items():
            wa.kernel_plan = lambda *a, answer=(wb, False), **k: answer
            run = chained(m)
            got = [np.asarray(g, np.float32) for g in
                   jax.tree_util.tree_leaves(run(params, x))]
            want = want or got
            say(layer=layer, form=name,
                layer_forward_backward_ms=time_ms(
                    run, (params, x), args.iters) / args.layers,
                widest_gap=max(float(np.abs(a - b).max() / np.abs(b).max())
                               for a, b in zip(got, want)))
            if args.profile:
                ops = device_ops(run, (params, x), 2, top=14)
                say(layer=layer, form=name, busy_ms_a_layer=ops["busy_ms"]
                    / args.layers, ops_ms_a_layer=[
                        [label, ms / args.layers] for label, ms in ops["ops"]])
    wa.kernel_plan = plan

    # the precision reading: each form against float32 on the same operands
    d = EMBED // HEADS
    wb = wa.block_windows(*qkv.shape[:2], HEADS, d, qkv.dtype,
                          mask.shape[0])
    padded = wa.pad_heads(qkv, 3, HEADS)
    merged = lambda o: np.asarray(o, np.float32).reshape(  # noqa: E731
        o.shape[:2] + (-1, wa.head_stride(d)))[..., :HEADS, :d].reshape(
        o.shape[:2] + (EMBED,))
    with jax.default_matmul_precision("highest"):
        truth = np.asarray(jax.jit(lambda q, tb: wa.window_attention(
            q.astype(jnp.float32), tb, index, mask, HEADS))(qkv, table))
    for name, form, operand in (
            ("xla", lambda q, tb: wa.window_attention(q, tb, index, mask,
                                                      HEADS), qkv),
            ("kernel", lambda q, tb: wa.window_attention_fused(
                q, tb, index, mask, HEADS, d, wb), padded),
            ("kernel_bf16_softmax", lambda q, tb: wa.window_attention_fused(
                q, tb, index, mask, HEADS, d, wb, False, jnp.bfloat16),
             padded)):
        got = jax.jit(form)(operand, table)
        got = np.asarray(got, np.float32) if name == "xla" else merged(got)
        say(precision=name, windows_per_block=wb,
            mean_abs_error=float(np.abs(got - truth).mean()),
            max_abs_error=float(np.abs(got - truth).max()),
            mean_abs=float(np.abs(truth).mean()))
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/window_attention_bench.jsonl", "w") as f:
        f.writelines(json.dumps(x) + "\n" for x in lines)
    return 0


if __name__ == "__main__":
    sys.exit(main())
