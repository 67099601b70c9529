"""Device time in collective operations, from the reduced trace.

``trace_reduce.reduce_trace`` sums the device time of every executed op
by its HLO opcode into ``run["trace"]["group_s"]``, as a mean over the
device planes it found. A collective shows there under its own opcode or
under the two halves of its asynchronous form (``all-reduce-start`` /
``all-reduce-done``). On the op line of a TPU plane a transfer that is
hidden behind compute takes no time of its own and a ``-done`` that has
to wait does, so the sum is what the step PAYS for its collectives, not
how long the wires were busy.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

#: ``all-to-all`` is here beyond the four the issue named: it is what GSPMD
#: answered the k7 layers' reflect pad with when it undid the H shard
#: (PERF.md section 4), so a step that pays for one must show it
COLLECTIVES = ("all-reduce", "collective-permute", "all-gather",
               "reduce-scatter", "all-to-all")


def collective_seconds(run: Dict[str, Any],
                       kinds: Sequence[str] = COLLECTIVES) -> Optional[float]:
    """Seconds a chip spent in the collectives ``kinds`` over the traced
    window; None where there is no trace or no such op ran (one chip)."""
    groups = (run.get("trace") or {}).get("group_s")
    if not groups:
        return None
    names = {k + half for k in kinds for half in ("", "-start", "-done")}
    found = [s for g, s in groups.items() if g in names]
    return sum(found) if found else None


def ms_per_step(run: Dict[str, Any],
                kinds: Sequence[str] = COLLECTIVES) -> Optional[float]:
    secs, steps = collective_seconds(run, kinds), run.get("steps")
    return 1000.0 * secs / steps if secs is not None and steps else None
