"""Device time of a train cell's step by LAYER: one ``trace_phases.py``
run (the cell's own ``--trace 1`` run, read by the program's names) whose
join of the trace with the compiled step's text is also read per flax
module path, per net and per direction.

    chiprun -- python scripts/conv_layer_trace.py --workload reference_256.train --seed 2147483659

An instruction's ``op_name`` holds the module path under the step's scope
(``jit(step)/transpose(jvp(C_branch))/G/UpsampleConvLayer_2/Conv_0/...``),
so for every layer whose path matches ``--layers`` this prints, after
``trace_phases.py``'s own lines, one ``{"layer_ms": ...}`` line: per
(net scope, layer, forward | backward) the milliseconds a step and the
costliest ops with their result shapes (an input gradient and a weight
gradient are both "backward"; their shapes tell them apart), and
``blocked_conv_ms`` and ``nearest_up2_ms``, the device milliseconds a
step under the ``blocked_conv`` and ``nearest_up2`` scopes of
``ops/conv.py`` (0 on a program without the scope), and
``reflect_pad_ms``: the same under the scope ``reflect_pad``
(``reflect_pad_2d``, the pad and its backward), in all and by net and
direction, whichever layers ``--layers`` names.
A fusion is counted where its root instruction's ``op_name`` points, so a
norm's backward fused into a convolution's gradient counts as that layer.
"""

import bisect
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

#: the named scopes of ``ops/conv.py``'s non-plain forms
FORM_SCOPES = ("blocked_conv", "nearest_up2")


def layer_keys(hlo_text, scopes, layers):
    """Instruction name -> (net scope, layer path, "fwd" | "bwd", the
    form scope it is under or None) for the instructions whose ``op_name``
    holds a component matching ``layers``; the layer path runs from the
    component after the scope to the matching one."""
    # the join's own two patterns: one text, one way to read it
    from benchmark.scope_time import _INSTRUCTION, _OP_NAME, first_scope

    layer_re = re.compile(layers)
    out = {}
    for line in hlo_text.splitlines():
        m, op = _INSTRUCTION.match(line), _OP_NAME.search(line)
        if not (m and op):
            continue
        parts = op.group(1).split("/")
        words = [(re.findall(r"[\w.\-]+", p) or [""])[-1] for p in parts]
        hit = next((i for i, w in enumerate(words) if layer_re.fullmatch(w)),
                   None)
        if hit is None:
            continue
        net = first_scope(op.group(1), scopes)
        start = words.index(net) + 1 if net in words else 1
        out[m.group(1)] = (net or "unscoped", "/".join(words[start:hit + 1]),
                           "bwd" if "transpose(" in op.group(1) else "fwd",
                           next((f for f in FORM_SCOPES if f in words), None))
    return out


def by_layer(xplane_path, hlo_text, layers, top=4):
    """Milliseconds a step by (net, layer, direction) over the executions
    of the module ``hlo_text`` describes, with each key's costliest ops."""
    from jax.profiler import ProfileData

    from benchmark import scope_time, trace_reduce

    scopes = scope_time.program_scopes()
    keys = layer_keys(hlo_text, scopes, layers)
    pads = {name: f"{net}|{direction}" for name, (net, _, direction, _)
            in layer_keys(hlo_text, scopes, "reflect_pad").items()}
    module = scope_time.module_name(hlo_text)
    total, ops_s, pad_ns, executions, chips = {}, {}, {}, 0, 0
    form_ns = dict.fromkeys(FORM_SCOPES, 0.0)
    for plane in ProfileData.from_file(xplane_path).planes:
        if not trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        runs, events = [], []
        for line in plane.lines:
            if line.name == scope_time.MODULE_LINE:
                runs += [(int(e.start_ns), int(e.start_ns + e.duration_ns))
                         for e in line.events
                         if e.name.split("(", 1)[0] == module]
            elif line.name == trace_reduce.OP_LINE:
                events += list(line.events)
        if not events:
            continue
        chips += 1
        runs.sort()
        executions += len(runs)
        starts = [s for s, _ in runs]
        for ev in events:
            i = bisect.bisect_right(starts, int(ev.start_ns)) - 1
            if i < 0 or ev.start_ns >= runs[i][1]:
                continue
            name, _, opcode = trace_reduce.parse_op(ev.name)
            if opcode in trace_reduce.CONTAINER_OPCODES:
                continue
            if name in pads:
                pad_ns[pads[name]] = pad_ns.get(pads[name], 0.0) \
                    + ev.duration_ns
            if name not in keys:
                continue
            net, layer, direction, form = keys[name]
            key = f"{net}|{layer}|{direction}"
            total[key] = total.get(key, 0.0) + ev.duration_ns
            per_op = ops_s.setdefault(key, {})
            label = trace_reduce.op_label(ev.name)
            per_op[label] = per_op.get(label, 0.0) + ev.duration_ns
            if form:
                form_ns[form] += ev.duration_ns
    if not executions:
        raise ValueError(f"{xplane_path}: no execution of {module}")
    per_step = 1e-6 / executions    # ns over all chips -> ms a step a chip
    return {
        "steps": executions // chips,
        "blocked_conv_ms": form_ns["blocked_conv"] * per_step,
        "nearest_up2_ms": form_ns["nearest_up2"] * per_step,
        "reflect_pad_ms": {
            "all": sum(pad_ns.values()) * per_step,
            **{key: ns * per_step for key, ns in sorted(pad_ns.items())}},
        "layer_ms": {
            key: {"ms": ns * per_step,
                  "ops": [[label, v * per_step] for label, v in sorted(
                      ops_s[key].items(), key=lambda kv: -kv[1])[:top]]}
            for key, ns in sorted(total.items(), key=lambda kv: -kv[1])},
    }


def thawed(live_trainer):
    """``epoch_records.live_trainer`` behind a ``gc.unfreeze()``: the
    Trainer freezes the collector after the epoch that compiled its step
    (``train.loop.settle_collector``, PR 38) and ``gc.get_objects()`` lists
    no frozen object, so the plain search finds no Trainer from then on
    and ``trace_phases.py``'s join fails on every cell. The join runs
    after the window, where a thaw costs the measurement nothing."""
    import gc

    def live_trainer_thawed():
        gc.unfreeze()
        return live_trainer()

    return live_trainer_thawed


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    layers = r"(Upsample)?ConvLayer_\d+"
    if "--layers" in argv:
        i = argv.index("--layers")
        layers = argv[i + 1]
        del argv[i:i + 2]

    from benchmark import epoch_records, harness, scope_time
    from benchmark.tools import trace_phases

    by_scope = scope_time.by_scope

    def by_scope_and_layer(xplane, text, *a, **kw):
        harness.say(**by_layer(xplane, text, layers))
        return by_scope(xplane, text, *a, **kw)

    live_trainer = epoch_records.live_trainer
    scope_time.by_scope = by_scope_and_layer
    epoch_records.live_trainer = thawed(live_trainer)
    try:
        return trace_phases.main(argv)
    finally:
        scope_time.by_scope = by_scope
        epoch_records.live_trainer = live_trainer


if __name__ == "__main__":
    sys.exit(main())
