"""Hash of the train step's lowered program for one benchmark cell, for a
DESCRIBED v5e (no chip, nothing compiled, nothing run).

A PR that says "the step's program is the same" shows it with this: run it
on the parent (``git archive <parent> | tar -x -C <dir>``) and on the
change, and compare the two lines.

    python scripts/step_program_hash.py --cell reference_256.train
    python scripts/step_program_hash.py --cell reference_256.train \
        --root /root/scratch/parent --text /root/scratch/parent_ref.txt

``--xla_path`` hashes the program the CPU backend traces instead (no
kernel taken anywhere): what a PR that adds a kernel shows to say that
the path beside it is still the parent's, letter for letter.

The configuration is built as ``benchmark/drivers/train.py`` builds it
(the CLI's parser and ``config_from_flags`` on the cell's flags); the state
is abstract (``jax.eval_shape``), the batch two uint8 images per example
at the cell's extent (the input at that over the configuration's ``scale``
where it states one, with the mask as a fourth channel where the
configuration's generator reads one), the mesh the cell's own over the described chips
with the Pallas branch taken as on the chip. ``steps_per_epoch`` is the
cell's ``dataset_pairs // batch_size``. VGG19's seeded weights are
closed-over constants of the step: they are part of the text.

The StableHLO text carries no source locations, but the Mosaic kernels
inside it do: each ``tpu_custom_call`` holds its kernel as serialized MLIR
whose debug locations name files and LINES of the call stack (moving
``ops/norm.py`` by eleven lines changed all sixteen payloads of the
pix2pixhd step and nothing else, PR 27). ``sha256`` is therefore taken
with every payload replaced by the hash of its location-free text;
``sha256_raw`` is the text as lowered.

``--compile`` also COMPILES the lowered step for the described chips
(~5 min for the four-chip cell) and prints what the compiled text
says of it: the compiler's own ``estimated_cycles`` summed over the
module and by opcode (a ranking of whole steps, not a time: PERF.md
section 6), the count and the bytes of every kind of collective
(``analysis/jaxpr_lint``), the all-reduced bytes by dtype, and the
temporaries. A PR on ``pix2pixhd_2048x1024.train_spatial4`` reads
whether a form engaged, and whether a shard was undone (an all-gather or
an all-to-all), off these before it asks for four chips:

    python scripts/step_program_hash.py --compile \
        --cell pix2pixhd_2048x1024.train_spatial4
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import re
import sys

_BODY = re.compile(r'(body\\22: \\22)([A-Za-z0-9+/=]+)(\\22)')


def without_kernel_locations(text: str) -> str:
    """``text`` with each Mosaic kernel payload (base64 MLIR bytecode in a
    ``tpu_custom_call``'s ``backend_config``) replaced by the sha256 of
    the kernel printed without debug locations."""
    import base64

    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib.mlir import ir

    ctx = jax_mlir.make_ir_context()
    ctx.allow_unregistered_dialects = True

    def digest(m):
        with ctx:
            asm = ir.Module.parse(base64.b64decode(m.group(2))
                                  ).operation.get_asm(enable_debug_info=False)
        return m.group(1) + hashlib.sha256(asm.encode()).hexdigest() \
            + m.group(3)

    return _BODY.sub(digest, text)


_CYCLES = re.compile(r'"estimated_cycles":"(\d+)"')
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_FUSION_KIND = re.compile(r"\bkind=(k[A-Za-z]+)")


def compiled_census(compiled) -> dict:
    """What the compiled step's text says of itself (module docstring)."""
    # its private patterns too: this also runs on a parent's tree (--root)
    from p2p_tpu.analysis.jaxpr_lint import (
        _HLO_ARRAY_RE,
        _HLO_DTYPE_BYTES,
        _HLO_OP_RE,
        collect_collectives,
        hlo_collective_bytes,
    )

    text = compiled.as_text()
    cycles, ops, reduced = {}, {}, {}
    for ln in text.splitlines():
        body = ln.partition(" = ")[2]
        m = _CYCLES.search(body)
        if m:
            op = _OPCODE.search(" " + body)
            name = op.group(1) if op else "?"
            if name == "fusion":
                kind = _FUSION_KIND.search(body)
                name = f"fusion:{kind.group(1)}" if kind else name
            cycles[name] = cycles.get(name, 0) + int(m.group(1))
            ops[name] = ops.get(name, 0) + 1
        m = _HLO_OP_RE.search(ln)
        if m and m.group(1) == "all-reduce":
            for dtype, dims in _HLO_ARRAY_RE.findall(ln[:m.start(1)]):
                n = _HLO_DTYPE_BYTES.get(dtype, 0)
                for d in dims.split(","):
                    n *= int(d) if d else 1
                reduced[dtype] = reduced.get(dtype, 0) + n
    top = sorted(cycles, key=cycles.get, reverse=True)[:8]
    mem = compiled.memory_analysis()
    return {
        "estimated_cycles": sum(cycles.values()),
        "ops_with_cycles": sum(ops.values()),
        "cycles_by_opcode": {k: [cycles[k], ops[k]] for k in top},
        "collectives": dict(collect_collectives(text)),
        "collective_bytes": dict(hlo_collective_bytes(text)),
        "all_reduce_bytes_by_dtype": reduced,
        "temp_gib": round(mem.temp_size_in_bytes / 2 ** 30, 3),
        "argument_gib": round(mem.argument_size_in_bytes / 2 ** 30, 3),
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", required=True,
                    help="a workload of BENCHMARK.json, e.g. "
                         "pix2pixhd_1024x512.train")
    ap.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), help="the tree to import from")
    ap.add_argument("--text", default=None,
                    help="also write the lowered text to this file")
    ap.add_argument("--compile", action="store_true",
                    help="also compile the step for the described chips "
                         "and print estimated cycles, collectives, memory")
    ap.add_argument("--compiled_text", default=None,
                    help="with --compile: write the compiled text here")
    ap.add_argument("--xla_path", action="store_true",
                    help="leave the kernel dispatcher alone: the program "
                         "a CPU backend traces (every Pallas site's XLA "
                         "form), lowered for the described chips")
    args = ap.parse_args()
    root = os.path.abspath(args.root)
    sys.path.insert(0, root)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies

    import p2p_tpu
    from p2p_tpu.cli import train as cli_train
    from p2p_tpu.core.mesh import (
        batch_sharding,
        make_mesh,
        parse_mesh_arg,
        replicated,
    )
    from p2p_tpu.models.vgg import load_vgg19_params
    from p2p_tpu.ops import pallas
    from p2p_tpu.ops.pallas import instance_norm
    from p2p_tpu.parallel.dp import make_parallel_train_step
    from p2p_tpu.train.state import create_train_state

    assert os.path.abspath(p2p_tpu.__file__).startswith(root + os.sep), (
        p2p_tpu.__file__, root)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        workload = next(w for w in json.load(f)["workloads"]
                        if w["name"] == args.cell)
    with open(os.path.join(root, "benchmark", "configs",
                           f"{workload['config']}.json")) as f:
        cfgf = json.load(f)
    argv = ["--preset", cfgf["preset"], "--batch_size",
            str(cfgf["batch_size"]), "--seed", "0"]
    for flag, value in cfgf.get("flags", {}).items():
        argv += [f"--{flag}", str(value)]
    cfg = cli_train.config_from_flags(
        cli_train.build_parser().parse_args(argv))

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    spec = parse_mesh_arg(cfgf.get("flags", {}).get("mesh", "data=1"))
    chips = int(workload["chips"])
    mesh = make_mesh(spec, devices=topo.devices[:chips])
    # the backend is the CPU here: take the chip's branch of the dispatcher
    for mod in () if args.xla_path else (pallas, instance_norm):
        mod.kernel_dispatch = lambda force=False, interpret=False: (True, False)
    # a program traced outside a mesh context asks this whether it may span
    # devices (.claude/skills/verify/SKILL.md): answer for the topology
    jax.device_count = lambda *a, **k: chips

    bs = int(cfgf["batch_size"])
    h, w = int(cfgf["image_height"]), int(cfgf["image_width"])
    steps_per_epoch = max(1, int(cfgf["dataset_pairs"]) // bs)
    dtype = jnp.bfloat16 if cfg.train.mixed_precision else None
    image = jax.ShapeDtypeStruct((bs, h, w, 3), jnp.uint8)
    # a super-resolution cell's input has the target's extent over its scale
    scale = int(cfgf.get("scale", 1))
    # an inpainting cell's carries its mask as a fourth channel (a label
    # map stands in as three channels here, as it always has)
    in_c = 3 if cfg.model.label_classes else cfg.model.input_nc
    lq = jax.ShapeDtypeStruct((bs, h // scale, w // scale, in_c), jnp.uint8)
    state = jax.eval_shape(
        lambda: create_train_state(
            cfg, jax.random.key(0),
            {"input": jnp.zeros(lq.shape, lq.dtype),
             "target": jnp.zeros(image.shape, image.dtype)},
            steps_per_epoch, dtype))
    vgg = None
    if cfg.loss.lambda_vgg > 0:
        # (a parent's tree may lack the field: its taps are the first table)
        taps = getattr(cfg.loss, "vgg_taps", "relu")
        if taps == "relu":
            vgg = load_vgg19_params()
        else:
            from p2p_tpu.losses.perceptual import VGG_TAPS

            vgg = load_vgg19_params(arch=VGG_TAPS[taps][0])
    if getattr(cfg.loss, "lambda_hrf", 0) > 0:
        from p2p_tpu.models.resnet_dilated import load_resnet50_dilated_params

        # the dilated ResNet50, in VGG19's place
        vgg = load_resnet50_dilated_params()
    step = make_parallel_train_step(cfg, mesh, vgg, steps_per_epoch, dtype)
    rep, bsh = replicated(mesh), batch_sharding(mesh)

    def on(tree, sharding):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=sharding), tree)

    lowered = step.lower(on(state, rep),
                         on({"input": lq, "target": image}, bsh))
    text = lowered.as_text()
    plain = without_kernel_locations(text)
    if args.text:
        with open(args.text, "w") as f:
            f.write(plain)
    line = {
        "cell": args.cell, "root": root, "mesh": dict(mesh.shape),
        "batch": bs, "extent": [h, w], "text_bytes": len(text),
        "tpu_custom_calls": text.count("@tpu_custom_call"),
        "sha256": hashlib.sha256(plain.encode()).hexdigest(),
        "sha256_raw": hashlib.sha256(text.encode()).hexdigest()}
    print(json.dumps(line), flush=True)
    if args.compile:
        compiled = lowered.compile()
        if args.compiled_text:
            with open(args.compiled_text, "w") as f:
                f.write(compiled.as_text())
        print(json.dumps({"cell": args.cell, "sha256": line["sha256"][:8],
                          **compiled_census(compiled)}))


if __name__ == "__main__":
    main()
