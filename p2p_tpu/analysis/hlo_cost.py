"""Static roofline cost model over traced programs (ISSUE 13 tentpole).

The paper's pipeline is a fixed-shape, kernel-dominated GAN step, so its
cost is statically computable: every ``conv_general_dilated`` /
``dot_general`` eqn's FLOPs follow from its shapes, every operand's HBM
bytes from its dtype, and the ratio — arithmetic intensity — says which
side of the chip's roofline a program sits on *before it ever runs*.
This module walks a traced jaxpr (``jax.make_jaxpr`` over
``ShapeDtypeStruct`` args — zero device compute, the CI contract shared
with every other analyzer here) and produces:

- :func:`eqn_cost` — per-eqn ``(kind class, flops, bytes, dtype key)``;
  MXU ops (conv/dot) get exact contraction FLOPs, elementwise/reduce ops
  count one VPU flop per element, movement ops (pad/slice/concat/...)
  count bytes only, collectives count ICI bytes. ``pallas_call`` is
  atomic: operands + results once — the hand-fused kernels' streaming
  contract is exactly "one read + one write per tensor" and their
  interior ref ops must not be double-counted.
- :func:`program_cost` — the per-program aggregate: total/per-class
  FLOPs and bytes, arithmetic intensity, MXU dtype split (the int8
  lever's denominator), per-source-line hotspots. ``lax.scan`` bodies
  multiply by trip count (the PP tick loop and ``scan_steps`` are real
  cost, not one iteration's).
- :func:`roofline_summary` — time bounds against a chip model
  (:data:`CHIP_MODEL`, v5e-class planning numbers): ``t_compute`` =
  Σ flops/peak-at-dtype, ``t_memory`` = bytes/BW, and the bound class
  (``compute-bound`` / ``memory-bound``). A *static* bound — XLA fuses
  below the byte count — but one that moves with the model, so
  regressions (an f32 leak doubling operand traffic, a lost int8 conv
  halving MXU rate) show as table diffs.
- :func:`perf_budget_rows` — the ``perf_budget.json`` artifact
  (``memory_budget.json``'s twin): one row per traced program of the
  lint CLI's set, with declared bounds (:data:`PERF_BOUNDS`) asserted on
  canonical rows — ``perf-roofline-out-of-bounds`` (warning) when a row
  leaves its band, info summary rows otherwise.

The numbers are a COST MODEL, not a measurement: bands are pinned on the
fixed tiny-config trace shapes (deterministic — jaxpr-based, immune to
XLA version drift), and their job is to catch structural regressions,
not to predict img/sec. The measurement of record is
``benchmark/run.py`` (PERF.md, ``PERF_LEDGER.jsonl``);
:func:`roofline_row_for` names the row that models a preset.
"""

from __future__ import annotations

from collections import defaultdict
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from p2p_tpu.analysis.findings import INFO, WARNING, Finding

RULE_ROOFLINE_BOUNDS = "perf-roofline-out-of-bounds"
#: the per-row info summary rides its OWN rule id so a grep (or waiver)
#: for the violation rule never matches a clean run's summary lines
RULE_ROOFLINE_ROW = "perf-roofline-row"

#: v5e-class planning numbers (SNIPPETS retrieval brief / ops/int8.py
#: header): peak MXU rate per operand dtype and HBM bandwidth. Planning
#: constants for the static bound, not a measurement — override the HBM
#: figure with ``P2P_HBM_GBPS`` for other parts.
CHIP_MODEL: Dict[str, Any] = {
    "name": "v5e-class",
    "peak_flops": {
        "int8": 394e12,        # s8×s8→s32 MXU rate (2× bf16)
        "bfloat16": 197e12,
        "float32": 49e12,      # f32 runs at the slow full-precision path
    },
    "hbm_gbps": 819.0,
}

#: eqn kind classes the aggregate reports
MXU, VPU, MEM, ICI = "mxu", "vpu", "mem", "ici"

#: movement primitives: bytes in + bytes out, zero flops
_MOVEMENT = frozenset({
    "broadcast_in_dim", "concatenate", "pad", "slice", "dynamic_slice",
    "dynamic_update_slice", "gather", "scatter", "rev", "transpose",
    "convert_element_type", "select_n", "iota", "copy",
    "device_put", "squeeze", "expand_dims",
})

#: metadata-only primitives: free at run time (bitcasts / aliasing views)
_FREE = frozenset({
    "reshape", "stop_gradient", "bitcast_convert_type",
    "sharding_constraint", "split", "pvary",
})

_COLLECTIVES = frozenset({
    "psum", "pmax", "pmin", "pmean", "ppermute", "pshuffle", "all_gather",
    "all_to_all", "reduce_scatter", "psum_scatter", "pbroadcast",
})

_REDUCTIONS = frozenset({
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod", "reduce_and",
    "reduce_or", "argmax", "argmin", "reduce_window_sum",
    "reduce_window_max", "reduce_window_min", "cumsum", "cummax", "cummin",
    "cumprod", "reduce", "reduce_precision",
})


def _aval_nbytes(v) -> int:
    aval = getattr(v, "aval", None)
    if aval is None or not hasattr(aval, "shape"):
        return 0
    try:
        item = np.dtype(aval.dtype).itemsize
    except TypeError:
        item = 4                     # extended dtypes (PRNG keys)
    n = int(np.prod(aval.shape, dtype=np.int64)) if len(aval.shape) else 1
    return n * item


def _aval_numel(v) -> int:
    aval = getattr(v, "aval", None)
    shape = getattr(aval, "shape", None)
    if shape is None:
        return 0
    return int(np.prod(shape, dtype=np.int64)) if len(shape) else 1


def _io_bytes(eqn) -> int:
    return (sum(_aval_nbytes(v) for v in eqn.invars)
            + sum(_aval_nbytes(v) for v in eqn.outvars))


def _mxu_dtype_key(eqn) -> str:
    """The roofline rate bucket an MXU eqn runs at: int8 when BOTH
    contraction operands are int8 (the s8×s8→s32 path), else the widest
    float operand (an f32 operand forces the full-precision path —
    the same law ``jaxpr-f32-leak`` enforces as a finding)."""
    dts = [str(getattr(getattr(v, "aval", None), "dtype", "?"))
           for v in eqn.invars[:2]]
    if all(d == "int8" for d in dts):
        return "int8"
    if any(d == "float32" for d in dts):
        return "float32"
    return "bfloat16"


def conv_flops(eqn) -> int:
    """Exact MACs×2 of a ``conv_general_dilated`` eqn from its shapes:
    ``2 · out_numel · KH·KW · C_in_per_group`` — the closed form every
    conv roofline uses (independent of stride/padding/dilation, which the
    out shape already encodes; the kernel's in-feature dim is already
    per-group in XLA's rhs layout)."""
    dn = eqn.params["dimension_numbers"]
    rhs_shape = tuple(eqn.invars[1].aval.shape)
    spatial = [rhs_shape[d] for d in dn.rhs_spec[2:]]
    c_in = rhs_shape[dn.rhs_spec[1]]
    out_numel = _aval_numel(eqn.outvars[0])
    return 2 * out_numel * int(np.prod(spatial, dtype=np.int64)) * c_in


def dot_flops(eqn) -> int:
    """``2 · out_numel · prod(contract dims)`` for a ``dot_general``."""
    (lc, _), _ = eqn.params["dimension_numbers"]
    lhs_shape = tuple(eqn.invars[0].aval.shape)
    k = int(np.prod([lhs_shape[d] for d in lc], dtype=np.int64)) if lc else 1
    return 2 * _aval_numel(eqn.outvars[0]) * k


def eqn_cost(eqn) -> Optional[Tuple[str, int, int, Optional[str]]]:
    """``(kind class, flops, bytes, mxu dtype key)`` for one eqn, or None
    for structural/free eqns. Control-flow eqns return None — the walk
    (:func:`program_cost`) descends into their bodies itself so scan trip
    counts multiply correctly."""
    name = eqn.primitive.name
    if name == "conv_general_dilated":
        return MXU, conv_flops(eqn), _io_bytes(eqn), _mxu_dtype_key(eqn)
    if name == "dot_general":
        return MXU, dot_flops(eqn), _io_bytes(eqn), _mxu_dtype_key(eqn)
    if name == "pallas_call":
        # atomic: the hand-fused kernels' contract is one streaming pass
        # over operands + results; interior ref ops must not double-count
        return MEM, 0, _io_bytes(eqn), None
    from p2p_tpu.analysis.jaxpr_lint import normalize_primitive

    base = normalize_primitive(name)
    if base in _COLLECTIVES:
        return ICI, 0, sum(_aval_nbytes(v) for v in eqn.invars), None
    if name in _FREE:
        return None
    if name in _MOVEMENT:
        return MEM, 0, _io_bytes(eqn), None
    if name in _REDUCTIONS or name.startswith("reduce_"):
        return VPU, sum(_aval_numel(v) for v in eqn.invars), \
            _io_bytes(eqn), None
    if any(hasattr(q, "eqns") or hasattr(q, "jaxpr")
           for p in eqn.params.values()
           for q in (p if isinstance(p, (list, tuple)) else [p])):
        return None                   # control flow: the walk descends
    # everything else is elementwise-ish VPU work: one flop per output
    # element, operands + results moved
    return VPU, sum(_aval_numel(v) for v in eqn.outvars), _io_bytes(eqn), \
        None


def _src_key(eqn) -> str:
    from p2p_tpu.analysis.jaxpr_lint import eqn_location

    fname, line = eqn_location(eqn)
    return f"{fname}:{line}" if fname else "<?>"


def program_cost(jaxpr, top_k: int = 5) -> Dict[str, Any]:
    """Aggregate cost of a traced program: total / per-class flops and
    bytes, arithmetic intensity, the MXU dtype split, and the ``top_k``
    hottest source lines by flops. ``scan`` bodies multiply by trip
    count; ``cond``/``while`` branches count once (documented
    approximation — the repo's in-jit guards are `where`-selects, so
    traced conds are rare and tiny)."""
    from p2p_tpu.analysis.jaxpr_lint import sub_jaxprs

    flops_by_class: Dict[str, int] = defaultdict(int)
    bytes_by_class: Dict[str, int] = defaultdict(int)
    mxu_flops_by_dtype: Dict[str, int] = defaultdict(int)
    by_line: Dict[Tuple[str, str], List[int]] = defaultdict(lambda: [0, 0])
    n_eqns = 0

    def walk(jx, mult: int):
        nonlocal n_eqns
        if hasattr(jx, "jaxpr"):
            jx = jx.jaxpr
        for eqn in jx.eqns:
            name = eqn.primitive.name
            if name == "scan":
                length = int(eqn.params.get("length", 1) or 1)
                walk(eqn.params["jaxpr"], mult * length)
                continue
            cost = eqn_cost(eqn)
            if cost is None:          # structural/free: descend instead
                for sub in sub_jaxprs(eqn.params):
                    walk(sub, mult)
                continue
            n_eqns += 1
            cls, fl, by, dtk = cost
            flops_by_class[cls] += fl * mult
            bytes_by_class[cls] += by * mult
            if dtk is not None:
                mxu_flops_by_dtype[dtk] += fl * mult
            if fl:
                entry = by_line[(name, _src_key(eqn))]
                entry[0] += fl * mult
                entry[1] += by * mult

    walk(jaxpr, 1)
    flops = sum(flops_by_class.values())
    nbytes = sum(bytes_by_class.values())
    top = sorted(by_line.items(), key=lambda kv: -kv[1][0])[:top_k]
    return {
        "flops": int(flops),
        "bytes": int(nbytes),
        "arith_intensity": round(flops / nbytes, 4) if nbytes else 0.0,
        "flops_by_class": {k: int(v) for k, v in flops_by_class.items()},
        "bytes_by_class": {k: int(v) for k, v in bytes_by_class.items()},
        "mxu_flops_by_dtype": {k: int(v)
                               for k, v in mxu_flops_by_dtype.items()},
        "counted_eqns": n_eqns,
        "top_lines": [{"op": op, "src": src, "flops": int(f),
                       "bytes": int(b)}
                      for (op, src), (f, b) in top],
    }


def roofline_summary(cost: Dict[str, Any],
                     chip: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """Static time bounds for one :func:`program_cost` result against a
    chip model: ``t_compute`` sums each MXU dtype bucket at its own peak
    rate (+ VPU flops at the bf16 rate), ``t_memory`` is total bytes over
    HBM bandwidth; the larger bound names the program's roofline side."""
    import os

    chip = chip or CHIP_MODEL
    peaks = chip["peak_flops"]
    bw = float(os.environ.get("P2P_HBM_GBPS", chip["hbm_gbps"])) * 1e9
    t_c = sum(fl / peaks.get(dt, peaks["bfloat16"])
              for dt, fl in cost["mxu_flops_by_dtype"].items())
    t_c += cost["flops_by_class"].get(VPU, 0) / peaks["bfloat16"]
    t_m = cost["bytes"] / bw
    mxu = sum(cost["mxu_flops_by_dtype"].values())
    return {
        "chip": chip["name"],
        "t_compute_us": round(t_c * 1e6, 3),
        "t_memory_us": round(t_m * 1e6, 3),
        "bound": "compute-bound" if t_c >= t_m else "memory-bound",
        "mxu_flops_fraction": round(mxu / cost["flops"], 4)
        if cost["flops"] else 0.0,
        "int8_mxu_fraction": round(
            cost["mxu_flops_by_dtype"].get("int8", 0) / mxu, 4)
        if mxu else 0.0,
    }


# ------------------------------------------------- the budget artifact


#: Canonical-row bounds for ``perf_budget.json`` (the CI-asserted twin of
#: the memory table's ``fits``). Pinned on the lint CLI's FIXED tiny-config
#: trace shapes — deterministic, so the bands are tight-ish (±~40% around
#: the recorded value) and a structural regression (f32 operand doubling
#: bytes, a de-quantized conv zeroing the int8 share, a lost fusion
#: inflating VPU traffic) trips them. Re-pin deliberately when the traced
#: set or the models change — the CI diff of perf_budget.json is the
#: review surface.
PERF_BOUNDS: Dict[str, Dict[str, float]] = {
    # recorded values (tiny-config traces, this tree): ai 2.5717
    "eval_forward[facades]": {
        "min_arith_intensity": 1.6, "max_arith_intensity": 4.0,
        "min_mxu_flops_fraction": 0.9,
    },
    # ai 1.0059, mxu 0.926
    "train_step[facades]": {
        "min_arith_intensity": 0.65, "max_arith_intensity": 1.6,
        "min_mxu_flops_fraction": 0.85,
    },
    # ai 0.734, int8 MXU share 0.4784 — the SHIPPING preset's program
    # (the headline bench row): D + stems-off generator coverage. Floor
    # raised 0.30 → 0.40 post-ISSUE-14 (the recorded value is the
    # drained state for this config; losing any quantized family drops
    # below it).
    "train_step[facades_int8]": {
        "min_arith_intensity": 0.45, "max_arith_intensity": 1.2,
        "min_mxu_flops_fraction": 0.85,
        "min_int8_mxu_fraction": 0.40,
    },
    # ai 1.6768, int8 MXU share 0.9012 — the FULL-COVERAGE program
    # (core.config.int8_full_coverage; the --int8-diff audit subject and
    # the facades_int8_full band-pending sweep row). The 0.80 floor is the
    # post-drain contract: a coverage regression (a de-quantized conv
    # family, a new unknobbed layer) fails CI as out-of-bounds here even
    # before its worklist line is noticed.
    "train_step[facades_int8_full]": {
        "min_arith_intensity": 1.0, "max_arith_intensity": 2.7,
        "min_mxu_flops_fraction": 0.9,
        "min_int8_mxu_fraction": 0.80,
    },
    # ai 5.1726 (the fused chains keep the epilogues out of the byte
    # count — a lost fusion inflates bytes and drops intensity out the
    # bottom of this band)
    "train_step[cityscapes_pallas]": {
        "min_arith_intensity": 3.2, "max_arith_intensity": 8.0,
        "min_mxu_flops_fraction": 0.9,
    },
    # ai 0.9956
    "video_train_step[vid2vid_temporal]": {
        "min_arith_intensity": 0.6, "max_arith_intensity": 1.6,
        "min_mxu_flops_fraction": 0.85,
    },
    # ai 2.62 (the overlap schedule; scan trip counts multiplied in)
    "pp_train_step[reference]": {
        "min_arith_intensity": 1.6, "max_arith_intensity": 4.2,
        "min_mxu_flops_fraction": 0.9,
    },
}

#: preset → the canonical budget row that models its config (None = not
#: yet traced). No caller but its test since PR 27 (ROADMAP D5).
_SWEEP_ROOFLINE = {
    "facades": "train_step[facades]",
    "facades_int8": "train_step[facades_int8]",
    # the facades_int8_full preset's key (a first-class preset on the
    # facades_int8 preset — core.config.int8_full_coverage)
    "facades_int8_full": "train_step[facades_int8_full]",
    "edges2shoes_dp": "train_step[facades]",     # same U-Net family
    "cityscapes_spatial": "train_step[cityscapes_pallas]",
    "pix2pixhd": "train_step[cityscapes_pallas]",  # same fused family
    "vid2vid_temporal": "video_train_step[vid2vid_temporal]",
}


def roofline_row_for(preset: str) -> Optional[str]:
    """The ``perf_budget.json`` row name modeling ``preset``'s program
    family, or None when the traced set does not cover it yet."""
    return _SWEEP_ROOFLINE.get(preset)


def _bounds_violations(row: Dict[str, Any],
                       bounds: Dict[str, float]) -> List[str]:
    out = []
    ai = row["cost"]["arith_intensity"]
    if ai < bounds.get("min_arith_intensity", 0.0):
        out.append(f"arith_intensity {ai} < "
                   f"{bounds['min_arith_intensity']}")
    if ai > bounds.get("max_arith_intensity", float("inf")):
        out.append(f"arith_intensity {ai} > "
                   f"{bounds['max_arith_intensity']}")
    mf = row["roofline"]["mxu_flops_fraction"]
    if mf < bounds.get("min_mxu_flops_fraction", 0.0):
        out.append(f"mxu_flops_fraction {mf} < "
                   f"{bounds['min_mxu_flops_fraction']}")
    i8 = row["roofline"]["int8_mxu_fraction"]
    if i8 < bounds.get("min_int8_mxu_fraction", 0.0):
        out.append(f"int8_mxu_fraction {i8} < "
                   f"{bounds['min_int8_mxu_fraction']}")
    return out


def perf_budget_rows(programs: Sequence[Tuple[str, Any]],
                     ) -> Tuple[List[dict], List[Finding]]:
    """Rows + findings for the ``perf_budget.json`` artifact.

    ``programs`` is ``(name, jaxpr)`` per traced program (the lint CLI's
    set). Every row carries the cost aggregate, the roofline summary and
    its declared bounds; a canonical row outside its bounds emits
    ``perf-roofline-out-of-bounds`` (warning — strict CI fails it), every
    row also reports an info summary line so the gate output shows the
    table at a glance."""
    rows: List[dict] = []
    findings: List[Finding] = []
    for name, jaxpr in programs:
        cost = program_cost(jaxpr)
        roof = roofline_summary(cost)
        bounds = PERF_BOUNDS.get(name, {})
        row = {
            "program": name,
            "canonical": name in PERF_BOUNDS,
            "cost": cost,
            "roofline": roof,
            "bounds": bounds,
        }
        bad = _bounds_violations(row, bounds) if bounds else []
        row["within_bounds"] = not bad
        rows.append(row)
        if bad:
            findings.append(Finding(
                rule=RULE_ROOFLINE_BOUNDS, severity=WARNING, path=name,
                message=f"roofline row outside its declared band: "
                        f"{'; '.join(bad)} — a structural cost regression "
                        "(or a deliberate change that must re-pin "
                        "analysis/hlo_cost.PERF_BOUNDS)",
            ))
        else:
            findings.append(Finding(
                rule=RULE_ROOFLINE_ROW, severity=INFO, path=name,
                message=f"{cost['flops'] / 1e6:.1f} MFLOP, "
                        f"{cost['bytes'] / 1e6:.2f} MB moved, "
                        f"intensity {cost['arith_intensity']}, "
                        f"{roof['bound']}, int8 MXU share "
                        f"{roof['int8_mxu_fraction']}",
            ))
    return rows, findings
