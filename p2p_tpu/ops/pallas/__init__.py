"""Pallas TPU kernels and the ONE rule for when a call site runs them.

``kernel_dispatch`` is that rule — every dispatch seam
(ops/pallas/instance_norm.py, ops/conv.py's Pallas head) asks it instead
of probing the backend itself, so "which program ran" has one answer:

- TPU backend: the compiled kernel. Interpret mode there is an error,
  never a default — a smoke or bench that silently interpreted would
  time the wrong program.
- CPU backend: the XLA/lax reference, unless the caller (``force=True``)
  or the environment (``P2P_TPU_FORCE_PALLAS=1``: the lint CLI and the
  tests) asks for the kernel program, which then runs INTERPRETED.
- any other backend: the reference; forcing the kernel there raises —
  Mosaic compiles for TPU only and interpret mode is a CPU test device.

A backend that fails to initialise raises out of ``default_backend()``;
nothing here turns that into "use the XLA path".
"""

from __future__ import annotations

import os
from typing import Tuple

import jax


def kernel_dispatch(force: bool = False,
                    interpret: bool = False) -> Tuple[bool, bool]:
    """``(use_kernel, interpret)`` for the current default backend."""
    backend = jax.default_backend()
    if backend == "tpu":
        if interpret:
            raise ValueError(
                "Pallas interpret mode requested on a TPU backend — the "
                "chip path always runs the compiled kernel")
        return True, False
    if not (force or os.environ.get("P2P_TPU_FORCE_PALLAS") == "1"):
        return False, False
    if backend != "cpu":
        raise RuntimeError(
            f"Pallas kernel forced on backend {backend!r}: the kernels "
            "compile for TPU only, and interpret mode is for CPU tests")
    return True, True
