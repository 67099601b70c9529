"""Device time of the train step by whose work it is: the join of a
profiler trace with the compiled step's text.

A device event carries its HLO instruction's text and nothing of the
framework (``jax.profiler.ProfileData`` has no op path on this jax), but
the text begins with the instruction's NAME (``%fusion.3 = ...``), which
is unique in its module, and the COMPILED module's text carries for every
instruction ``metadata={op_name="jit(step)/jvp(G)/ExpandNetwork/..."}``:
the program's ``jax.named_scope`` names, wrapped by the transforms that
made the op (``jvp(G)`` forward, ``transpose(jvp(G))`` backward). So
instruction name -> ``op_name`` -> the first component that is one of the
program's scopes (``p2p_tpu.train.step.STEP_SCOPES``) is a join, not a
guess. An op whose ``op_name`` holds none of them (a parameter's layout
copy is named after the parameter; some compiler-made ops carry nothing)
is counted as unscoped, so the sums say how far they can be trusted.

Checked against the recorded trace in ``benchmark/tests/data`` and the
text its step compiles to (``small_trace.hlo.txt``).
"""

from __future__ import annotations

import bisect
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from benchmark import trace_reduce

#: the line of a device plane that holds one event per executed program
MODULE_LINE = "XLA Modules"
UNSCOPED = "unscoped"

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)")


def program_scopes() -> Tuple[str, ...]:
    """The scope names the program's step is written under; none on a
    program from before it named them."""
    from p2p_tpu.train import step

    return tuple(getattr(step, "STEP_SCOPES", ()))


def first_scope(op_name: str, scopes: Iterable[str]) -> Optional[str]:
    """The first ``/`` component of ``op_name`` that is one of ``scopes``
    once the transforms around it are taken off (``transpose(jvp(G))`` ->
    ``G``)."""
    for component in op_name.split("/"):
        words = re.findall(r"[\w.\-]+", component)
        if words and words[-1] in scopes:
            return words[-1]
    return None


def module_name(hlo_text: str) -> str:
    m = _MODULE.match(hlo_text)
    if not m:
        raise ValueError("not a compiled module's text: no 'HloModule' "
                         f"line at its head ({hlo_text[:60]!r})")
    return m.group(1)


def instruction_scopes(hlo_text: str, scopes: Sequence[str]
                       ) -> Dict[str, Optional[str]]:
    """Instruction name -> its first scope (None = unscoped) for every
    instruction of every computation in the module's text."""
    out: Dict[str, Optional[str]] = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        op = _OP_NAME.search(line)
        out[m.group(1)] = first_scope(op.group(1), scopes) if op else None
    return out


def by_scope(xplane_path: str, hlo_text: str,
             scopes: Optional[Sequence[str]] = None, top: int = 10) -> dict:
    """Device seconds of the module ``hlo_text`` describes, by first
    scope, over the trace: the ops that began inside one of that module's
    executions (the ``XLA Modules`` line), container ops left out as
    ``trace_reduce`` leaves them out, averaged over chips.

    Keys: ``module``, ``executions`` (per chip), ``scope_s`` (scope ->
    seconds; ``unscoped`` among them), ``op_s`` (their sum: all the
    module's op seconds), ``unmatched_s`` (the part of ``unscoped`` whose
    instruction the text does not hold: 0 when text and trace are of one
    program), ``unscoped_ops`` (the costliest ops no scope claims,
    ``[name and shape, seconds]``), ``n_op_events``.
    """
    from jax.profiler import ProfileData

    scopes = program_scopes() if scopes is None else tuple(scopes)
    module = module_name(hlo_text)
    owner = instruction_scopes(hlo_text, scopes)
    scope_s: Dict[str, float] = {}
    unscoped_ops: Dict[str, float] = {}
    unmatched = 0.0
    executions = n_ops = n_chips = 0
    for plane in ProfileData.from_file(xplane_path).planes:
        if not trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        runs: List[Tuple[int, int]] = []
        ops = []
        for line in plane.lines:
            if line.name == MODULE_LINE:
                runs += [(int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
                         for ev in line.events
                         if ev.name.split("(", 1)[0] == module]
            elif line.name == trace_reduce.OP_LINE:
                ops += list(line.events)
        if not ops:
            continue
        n_chips += 1
        runs.sort()
        executions += len(runs)
        starts = [s for s, _ in runs]
        for ev in ops:
            i = bisect.bisect_right(starts, int(ev.start_ns)) - 1
            if i < 0 or ev.start_ns >= runs[i][1]:
                continue
            name, _, opcode = trace_reduce.parse_op(ev.name)
            if opcode in trace_reduce.CONTAINER_OPCODES:
                continue
            n_ops += 1
            seconds = ev.duration_ns / 1e9
            scope = owner.get(name) or UNSCOPED
            scope_s[scope] = scope_s.get(scope, 0.0) + seconds
            if scope is UNSCOPED:
                label = trace_reduce.op_label(ev.name)
                unscoped_ops[label] = unscoped_ops.get(label, 0.0) + seconds
            if name not in owner:
                unmatched += seconds
    if not n_chips:
        raise ValueError(f"{xplane_path}: no device op in the trace")
    scope_s = {k: v / n_chips for k, v in scope_s.items()}
    return {"module": module, "executions": executions // n_chips,
            "scope_s": scope_s, "op_s": sum(scope_s.values()),
            "unmatched_s": unmatched / n_chips,
            "unscoped_ops": [[k, v / n_chips] for k, v in sorted(
                unscoped_ops.items(), key=lambda kv: -kv[1])[:top]],
            "n_op_events": n_ops}


#: metric -> the scopes whose device time it sums (PERF.md section 3)
NET_METRICS = {
    "model.g_ms_per_step": ("G",),
    "model.d_ms_per_step": ("D_fake", "D_real", "loss_gan", "loss_fm"),
    "model.c_ms_per_step": ("compress", "C_branch"),
    "loss.vgg_ms_per_step": ("loss_vgg",),
    "step.optimizer_ms_per_step": ("opt_g", "opt_d", "opt_c"),
}


def per_step_numbers(scoped: dict) -> Dict[str, float]:
    """The per-net readings of one ``by_scope`` result: milliseconds a
    step under each group of ``NET_METRICS`` (left out where no op ran
    under the group, as on a program with no scopes) and
    ``step.unscoped_share``, the percentage of the module's op time no
    scope claims."""
    n, total = scoped["executions"], scoped["op_s"]
    if not n or not total:
        return {}
    out = {"step.unscoped_share":
           100.0 * scoped["scope_s"].get(UNSCOPED, 0.0) / total}
    for metric, group in NET_METRICS.items():
        seconds = sum(scoped["scope_s"].get(s, 0.0) for s in group)
        if seconds:
            out[metric] = 1000.0 * seconds / n
    return out
