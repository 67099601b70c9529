"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read: device busy time as the UNION of the intervals in which an
operation ran, the idle share of the traced window, time per operation
group, time in Pallas kernels (``tpu_custom_call``), and the longest idle
gaps, each named by what the host was doing in it.

Reads the trace through ``jax.profiler.ProfileData`` and nothing else.
Checked against a small recorded trace in ``benchmark/tests``.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[int, int]          # start_ns, end_ns

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
#: the line of a device plane that holds one event per executed HLO op
OP_LINE = "XLA Ops"
#: ops that only wrap other ops (a ``while`` spans its body's ops);
#: counting them would count the body's time twice in the tables. The busy
#: UNION is unaffected.
CONTAINER_OPCODES = ("while", "conditional", "call")


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def union_ns(intervals: Iterable[Interval]) -> Tuple[int, List[Interval]]:
    """Total covered nanoseconds and the merged intervals."""
    merged: List[List[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), [(s, e) for s, e in merged]


def gaps_ns(merged: Sequence[Interval], window: Interval) -> List[Interval]:
    """The idle intervals of ``window`` that ``merged`` leaves."""
    out, cur = [], window[0]
    for s, e in merged:
        if e <= window[0] or s >= window[1]:
            continue
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < window[1]:
        out.append((cur, window[1]))
    return out


def parse_op(text: str) -> Tuple[str, str, str]:
    """``(name, result type, opcode)`` of a device op event, whose name is
    the HLO instruction's text: ``%fusion.7 = bf16[8,64]{1,0} fusion(...),
    kind=kLoop``. A tuple result type is kept whole. Text that is not an
    instruction comes back as ``(text, "", text)``."""
    head, sep, rest = text.partition(" = ")
    if not sep:
        return text, "", text
    rest = rest.lstrip()
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        rtype, tail = rest[:i + 1], rest[i + 1:].lstrip()
    else:
        rtype, _, tail = rest.partition(" ")
    opcode = tail.split("(", 1)[0].strip()
    return head.lstrip("%"), rtype, opcode


def op_group(text: str) -> str:
    """The kind of work an op is: its HLO opcode, for a fusion with the
    fusion kind (``kOutput`` holds the convolutions and matmuls, ``kLoop``
    elementwise chains, ``kInput`` reductions), for a custom call with its
    target."""
    _, _, opcode = parse_op(text)
    if opcode == "fusion":
        m = re.search(r"kind=(k\w+)", text)
        return f"fusion:{m.group(1)}" if m else "fusion"
    if opcode == "custom-call":
        m = re.search(r'custom_call_target="([^"]+)"', text)
        return f"custom-call:{m.group(1)}" if m else "custom-call"
    return opcode


def op_label(text: str) -> str:
    """A short name of ONE op for the table of the costliest: its name and
    the shape it produces, without layouts."""
    name, rtype, _ = parse_op(text)
    shape = re.sub(r"\{[^}]*\}", "", rtype)
    return f"{name} {shape}"[:96]


def is_kernel(text: str) -> bool:
    """A Pallas kernel: a custom call into Mosaic (``tpu_custom_call``)."""
    _, _, opcode = parse_op(text)
    return opcode == "custom-call" and "tpu_custom_call" in text


def host_annotations(profile, names: Sequence[str]) -> Dict[str, List[Interval]]:
    """Intervals of the named TraceAnnotations on the host's threads."""
    out: Dict[str, List[Interval]] = {n: [] for n in names}
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in out:
                    s = int(ev.start_ns)
                    out[ev.name].append((s, s + int(ev.duration_ns)))
    return out


def name_gap(gap: Interval, annotations: Dict[str, List[Interval]],
             priority: Sequence[str]) -> str:
    """The annotation (first in ``priority``) that covers most of the gap,
    if any covers at least half of it; else ``host_other``."""
    length = max(gap[1] - gap[0], 1)
    best, best_cov = "host_other", 0
    for name in priority:
        cov = 0
        for s, e in annotations.get(name, ()):
            cov += max(0, min(e, gap[1]) - max(s, gap[0]))
        if cov * 2 >= length and cov > best_cov:
            best, best_cov = name, cov
            break
    return best


def reduce_trace(xplane_path: str, annotation_priority: Sequence[str] = (),
                 window: Optional[Interval] = None, top: int = 10,
                 window_from: Optional[Tuple[str, str]] = None) -> dict:
    """The reduced trace. ``window`` (ns, the profiler's clock) bounds the
    reduction; ``window_from`` names two host annotations instead: the
    window then runs from the first start of the one to the last end of
    the other (the driver's first timed call and its closing fence), so
    idle time before the first device op and after the last counts; the
    host's and the device's clocks lie about a millisecond apart in a
    trace, so such edges are good to that. With neither it runs from the
    first to the last device op.

    Keys: ``busy_s`` (union of op intervals, averaged over chips),
    ``window_s``, ``idle_share``, ``n_chips``, ``kernel_s`` (summed device
    time of Pallas / custom-call kernels, per chip), ``n_kernel_events``,
    ``group_s`` (kind of op -> seconds, per chip), ``device_ops`` (the
    costliest single ops, ``[name and shape, seconds]``), ``idle_gaps``
    (idle seconds by what the host was doing, ``[name, seconds]``),
    ``longest_gaps`` (the longest single gaps), ``n_op_events``.
    """
    from jax.profiler import ProfileData

    profile = ProfileData.from_file(xplane_path)
    names = tuple(annotation_priority) + tuple(window_from or ())
    ann = host_annotations(profile, names)
    if window is None and window_from:
        first, last = ann[window_from[0]], ann[window_from[1]]
        if not first or not last:
            raise ValueError(f"{xplane_path}: no host annotation "
                             f"{window_from} to bound the window")
        window = (min(s for s, _ in first), max(e for _, e in last))
    per_chip = []
    for plane in profile.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = [ln for ln in plane.lines if ln.name == OP_LINE]
        events = [ev for ln in lines for ev in ln.events]
        if events:
            per_chip.append(events)
    if not per_chip:
        raise ValueError(
            f"{xplane_path}: no device op ran in the traced window (planes: "
            f"{[p.name for p in profile.planes]})")
    busy, kernel, n_kernel, n_ops = [], [], 0, 0
    groups: Dict[str, float] = {}
    ops: Dict[str, float] = {}
    merged_first: List[Interval] = []
    lo, hi = None, None
    for i, events in enumerate(per_chip):
        iv = []
        for ev in events:
            s = int(ev.start_ns)
            e = s + int(ev.duration_ns)
            if window and (e <= window[0] or s >= window[1]):
                continue
            iv.append((s, e))
            n_ops += 1
            if parse_op(ev.name)[2] in CONTAINER_OPCODES:
                continue
            dur = (e - s) / 1e9
            g = op_group(ev.name)
            groups[g] = groups.get(g, 0.0) + dur
            label = op_label(ev.name)
            ops[label] = ops.get(label, 0.0) + dur
            if is_kernel(ev.name):
                kernel.append(dur)
                n_kernel += 1
        total, merged = union_ns(iv)
        busy.append(total / 1e9)
        if i == 0:
            merged_first = merged
        if merged:
            lo = merged[0][0] if lo is None else min(lo, merged[0][0])
            hi = merged[-1][1] if hi is None else max(hi, merged[-1][1])
    if lo is None:
        raise ValueError(f"{xplane_path}: no device op inside the window")
    win = window or (lo, hi)
    n = len(per_chip)
    window_s = (win[1] - win[0]) / 1e9
    busy_s = sum(busy) / n
    named: Dict[str, float] = {}
    for gap in gaps_ns(merged_first, win):
        who = name_gap(gap, ann, annotation_priority)
        named[who] = named.get(who, 0.0) + (gap[1] - gap[0]) / 1e9
    longest = sorted(((g[1] - g[0]) / 1e9,
                      name_gap(g, ann, annotation_priority))
                     for g in gaps_ns(merged_first, win))[::-1][:top]
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
        "n_chips": n,
        "n_op_events": n_ops,
        "kernel_s": sum(kernel) / n,
        "n_kernel_events": n_kernel // n,
        "group_s": {k: v / n for k, v in groups.items()},
        "device_ops": [[k, v / n] for k, v in sorted(
            ops.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v] for k, v in sorted(
            named.items(), key=lambda kv: -kv[1])[:top]],
        "longest_gaps": [[who, s] for s, who in longest],
    }
