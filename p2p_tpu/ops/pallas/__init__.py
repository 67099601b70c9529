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

``spans_devices`` is the second question every such seam asks: a Mosaic
call is not GSPMD's to partition, so in a program over several devices the
compiled kernel runs inside a ``shard_map`` or not at all.
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


def spans_devices(interpret: bool = False) -> bool:
    """Whether the program being traced may span several devices: the
    visible mesh (``core.mesh.mesh_context``) has more than one. With NO
    mesh visible in a process that has several devices nothing at trace
    time says what the program spans (a jit on a mesh Trainer's replicated
    state from outside its ``mesh_context``, the benchmark's generator
    check, is a multi-device program), so the answer is yes there too; the
    step, the evaluation and the server all trace inside ``mesh_context``.
    (The interpreted kernel is plain XLA ops and needs no such care.)"""
    from p2p_tpu.core.mesh import current_mesh

    mesh = current_mesh()
    if mesh is None:
        return not interpret and jax.device_count() > 1
    return mesh.size > 1
