"""A scope's device time where the compiler fused its work into ops named
after something else.

``scope_time.by_scope`` joins a device op to the scope on its OWN
``op_name``, and XLA names a fusion by one instruction of it (its root,
or its convolution). Work of a scope that was fused under another
instruction's name is then counted outside it: the backward of a
normalisation's statistics rides on the backward convolution of the
layer that follows it, a bias gradient on the optimizer's update. This
module rewrites the compiled module's TEXT, which is all ``by_scope``
reads of it, so that such a fusion's ``op_name`` ends in one of two tags:

- ``<scope>_fused_passes``: a fusion of elementwise and reduction passes
  (no convolution in it) that holds at least one instruction under the
  scope;
- ``<scope>_fused_in_conv``: a fusion around a convolution that is NOT
  under the scope, with at least one instruction under the scope fused
  into it. Its time is the convolution's and the scope's passes'
  together; a join cannot part them.

A fusion whose own name lacks the scope but whose convolution carries it
is the scope's own and gets the scope's name. Fusions nested in a fusion
are followed. ``by_scope(xplane, tagged(text, "spade"), tags("spade"))``
then gives the three sums side by side: the first is what the plain join
reads (a lower bound of the scope's time), all three together every op
that does any of the scope's work (an upper bound).
"""

from __future__ import annotations

import re
from typing import Dict, List, Tuple

from benchmark.scope_time import first_scope

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_FUSION = re.compile(r"\sfusion\(")
_CONVOLUTION = re.compile(r"\sconvolution\(")


def tags(scope: str) -> Tuple[str, str, str]:
    """The scope itself, its passes fused under another name, and its
    passes fused into a convolution outside it."""
    return scope, f"{scope}_fused_passes", f"{scope}_fused_in_conv"


def _under(line: str, scope: str) -> bool:
    op = _OP_NAME.search(line)
    return bool(op and first_scope(op.group(1), (scope,)))


def _computations(lines: List[str]) -> Dict[str, List[str]]:
    out: Dict[str, List[str]] = {}
    body = None
    for line in lines:
        if body is None:
            m = _COMPUTATION.match(line)
            if m:
                body = out[m.group(1)] = []
        elif line.startswith("}"):
            body = None
        else:
            body.append(line)
    return out


def tagged(hlo_text: str, scope: str) -> str:
    """``hlo_text`` with the ``op_name`` of every fusion that does work
    of ``scope`` under another name ending in one of :func:`tags`."""
    own, passes, in_conv = tags(scope)
    lines = hlo_text.split("\n")
    bodies = _computations(lines)
    seen: Dict[str, Tuple[bool, bool, bool]] = {}

    def holds(name: str) -> Tuple[bool, bool, bool]:
        """(an instruction under the scope, a convolution under it, a
        convolution outside it) in the computation and the fusions it
        calls."""
        if name not in seen:
            any_in = conv_in = conv_out = False
            for line in bodies.get(name, ()):
                inside = _under(line, scope)
                any_in |= inside
                if _CONVOLUTION.search(line):
                    conv_in |= inside
                    conv_out |= not inside
                callee = _FUSION.search(line) and _CALLS.search(line)
                if callee:
                    a, b, c = holds(callee.group(1))
                    any_in, conv_in, conv_out = (any_in | a, conv_in | b,
                                                 conv_out | c)
            seen[name] = (any_in, conv_in, conv_out)
        return seen[name]

    for i, line in enumerate(lines):
        callee = _FUSION.search(line) and _CALLS.search(line)
        if not callee or _under(line, scope):
            continue
        any_in, conv_in, conv_out = holds(callee.group(1))
        if not any_in:
            continue
        tag = own if conv_in else in_conv if conv_out else passes
        if _OP_NAME.search(line):
            lines[i] = _OP_NAME.sub(
                lambda m: f'op_name="{m.group(1)}/{tag}"', line, count=1)
        else:
            lines[i] = f'{line}, metadata={{op_name="{tag}"}}'
    return "\n".join(lines)
