"""Bytes the Pallas kernels of one train step must move at the least:
every ``tpu_custom_call``'s operands and results, read or written once
each, counted from the LOWERED step's StableHLO text.

The kernels sit in private functions that the step calls many times, so
each call site is weighted by how often its function is reached from
``@main``. (A kernel inside a ``while`` body would be counted once per
site, not per trip; the steps measured here have none.)
"""

from __future__ import annotations

import re
from typing import Dict, List, Tuple

MARKER = "@tpu_custom_call"
_FUNC = re.compile(r"^\s*func\.func\s+(?:public\s+|private\s+)?@([\w.$-]+)\(")
_CALL = re.compile(r"\bcall\s+@([\w.$-]+)\(")
_TENSOR = re.compile(r"tensor<([^>]*)>")
_DTYPE_BYTES = {"f64": 8, "i64": 8, "ui64": 8, "f32": 4, "i32": 4, "ui32": 4,
                "bf16": 2, "f16": 2, "i16": 2, "ui16": 2, "i8": 1, "ui8": 1,
                "i1": 1, "f8E4M3FN": 1, "f8E5M2": 1}


def tensor_bytes(spec: str) -> int:
    """``2x256x512x64xbf16`` -> bytes."""
    *dims, dtype = spec.split("x")
    if dtype not in _DTYPE_BYTES:
        raise ValueError(f"unknown element type in tensor<{spec}>")
    n = 1
    for d in dims:
        n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def signature_bytes(line: str) -> int:
    """Operand plus result bytes of one custom-call line: the function type
    after the attribute dictionary, ``} : (operands) -> results``."""
    at = line.rfind("} : (")
    if at < 0:
        raise ValueError("custom call without a type signature")
    return sum(tensor_bytes(m) for m in _TENSOR.findall(line[at:]))


def custom_call_bytes(text: str) -> Tuple[int, int]:
    """``(executed call sites, bytes)`` of one run of ``@main``."""
    current = None
    calls: Dict[str, List[str]] = {}
    kernel_bytes: Dict[str, List[int]] = {}
    for line in text.splitlines():
        if "func.func" in line:
            m = _FUNC.match(line)
            if m:
                current = m.group(1)
                calls.setdefault(current, [])
                kernel_bytes.setdefault(current, [])
                continue
        if current is None:
            continue
        if MARKER in line:
            kernel_bytes[current].append(signature_bytes(line))
        elif "call @" in line and len(line) < 4096:
            calls[current].extend(_CALL.findall(line))
    memo: Dict[str, Tuple[int, int]] = {}

    def visit(fn: str, stack=()) -> Tuple[int, int]:
        if fn in memo:
            return memo[fn]
        if fn in stack or fn not in calls:
            return 0, 0
        sites, nbytes = len(kernel_bytes[fn]), sum(kernel_bytes[fn])
        for callee in calls[fn]:
            s, b = visit(callee, stack + (fn,))
            sites, nbytes = sites + s, nbytes + b
        memo[fn] = (sites, nbytes)
        return memo[fn]

    return visit("main")
