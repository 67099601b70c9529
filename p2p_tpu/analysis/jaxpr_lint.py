"""jaxpr/HLO structural lint library — the reusable form of the test pins.

tests/test_pp.py and tests/test_ops.py grew hand-rolled jaxpr walkers
(``_sub_jaxprs``, the scan-carry ppermute check) and compiled-text
all-gather greps; every new sharding/perf PR re-invented them. This module
is the single source of truth those tests now import, plus the two checks
the lint CLI runs as a standing gate:

- **collective census** — :func:`collect_collectives` over a jaxpr (traced
  primitive names, normalized: ``psum2`` → ``psum``) or compiled HLO text
  (``all-gather``/``collective-permute``/... opcodes, async ``-start``
  forms counted once), with :func:`assert_no_collective` /
  :func:`assert_collective_count` as the pin forms.
- **activation-gather bound** — :func:`assert_no_collective_as_large_as`:
  no ``all-gather`` (or any chosen collective) operand/result shape on the
  compiled text may reach the full-activation element count. This is the
  exact check both HLO pins hand-rolled.
- **scan-carry ppermute** — :func:`scan_ppermute_carry_flags`: for every
  ``ppermute`` directly inside a ``lax.scan`` body, True iff its operand
  is a scan CARRY invar (structurally independent of the tick's compute —
  the latency-hiding schedule pin of docs/PARALLELISM.md).
- **host-callback census** — :func:`host_callback_findings`: callbacks
  (``pure_callback``/``io_callback``/``debug_callback``/``debug_print``)
  inside a program that is supposed to be a hot path.
- **f32-leak detector** — :func:`f32_leak_findings`: walks every
  ``dot_general``/``conv_general_dilated`` eqn's operand dtypes under a
  declared bf16 policy; an f32 operand is compute the policy says should
  not exist. Findings carry the eqn's source ``file:line`` (via jax source
  info), so deliberate f32 islands are waivable in-source with the
  ``# p2p-lint: disable=...`` pragma.

Everything here is trace/text-based: ``jax.make_jaxpr`` over
``ShapeDtypeStruct`` args and ``.lower().compile().as_text()`` — zero
device compute, CPU-safe (the CI contract).
"""

from __future__ import annotations

import re
import sysconfig
from collections import Counter
from typing import Iterable, Iterator, List, Optional, Tuple, Union

import numpy as np

from p2p_tpu.analysis.findings import ERROR, Finding

RULE_HOST_CALLBACK = "jaxpr-host-callback"
RULE_F32_LEAK = "jaxpr-f32-leak"

#: traced collective primitives (normalized names — see normalize_primitive)
COLLECTIVE_PRIMITIVES = frozenset({
    "psum", "pmax", "pmin", "pmean", "ppermute", "pshuffle", "all_gather",
    "all_to_all", "reduce_scatter", "psum_scatter", "pbroadcast", "pgather",
})

#: compiled-HLO collective opcodes (async forms appear as ``<op>-start``)
HLO_COLLECTIVES = (
    "all-gather", "all-reduce", "collective-permute", "all-to-all",
    "reduce-scatter", "collective-broadcast",
)

# an HLO instruction is `%name = <shape> <opcode>(...)`. The shape is not
# parsed: async collectives carry TUPLE shapes, and the TPU compiler writes
# layouts with their own parentheses and spaces inside them
# (`(bf16[1,2,1024,3]{2,3,1,0:T(4,128)(2,1)S(1)}, u32[]{:S(2)})`) — a
# shape matcher dropped every collective of a v5e program from the census
# (PR 21). Operand references (`%all-reduce.1`) never follow whitespace.
_HLO_OP_RE = re.compile(
    r"=\s+.*?\s(" + "|".join(HLO_COLLECTIVES) + r")(-start)?\(")
_HLO_SHAPE_RE = re.compile(r"\w+\[([\d,]+)\]")
_CALLBACK_PRIMITIVES = frozenset({
    "pure_callback", "io_callback", "debug_callback", "debug_print",
    "host_callback", "outside_call",
})


def normalize_primitive(name: str) -> str:
    """Strip jax's versioning/typing suffixes from a primitive name
    (``psum2`` → ``psum``; jax 0.9's vma-typed ``psum_invariant`` /
    ``all_gather_invariant`` → ``psum`` / ``all_gather``) so call sites
    pin semantics, not jax-internal renames."""
    return name.rstrip("0123456789").removesuffix("_invariant")


def sub_jaxprs(params) -> Iterator:
    """Yield every (Closed)Jaxpr hiding in an eqn's params dict — the
    recursion step shared by every structural walk (scan/cond/pjit/
    shard_map/custom_vjp bodies)."""
    for p in params.values():
        vals = p if isinstance(p, (list, tuple)) else [p]
        for q in vals:
            if hasattr(q, "eqns"):
                yield q
            elif hasattr(q, "jaxpr") and hasattr(q.jaxpr, "eqns"):
                yield q.jaxpr


def iter_eqns(jaxpr) -> Iterator:
    """Depth-first over EVERY eqn of a jaxpr, descending into sub-jaxprs.
    Accepts a Jaxpr or ClosedJaxpr."""
    if hasattr(jaxpr, "jaxpr"):        # ClosedJaxpr
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in sub_jaxprs(eqn.params):
            yield from iter_eqns(sub)


_SITE_PACKAGES = tuple(
    {sysconfig.get_paths()[k] for k in ("purelib", "platlib")})


def eqn_location(eqn) -> Tuple[Optional[str], Optional[int]]:
    """(file, line) of the PROGRAM's frame that created an eqn — the
    innermost frame outside jax, the stdlib AND installed third-party
    packages (flax's ``linen/linear.py`` issues the conv, but the line a
    pragma can waive is the model's ``nn.Conv(...)(x)`` call site).
    (None, None) only when the traceback holds no such frame. Reads jax
    0.9's ``source_info_util.user_frames`` (takes the TRACEBACK, not the
    SourceInfo); a jax that moves it fails loudly here rather than
    quietly degrading every finding to location-less."""
    from jax._src import source_info_util

    for frame in source_info_util.user_frames(eqn.source_info.traceback):
        if not frame.file_name.startswith(_SITE_PACKAGES):
            return frame.file_name, int(frame.start_line)
    return None, None


# ------------------------------------------------------------ collectives


def collect_collectives(obj: Union[str, object]) -> Counter:
    """Collective census of a jaxpr (traced primitive names) or compiled
    HLO text (opcode names). Async HLO forms (``all-gather-start``) count
    once under the base opcode; ``-done`` lines are not instructions that
    move data and are ignored."""
    if isinstance(obj, str):
        counts: Counter = Counter()
        for m in _HLO_OP_RE.finditer(obj):
            counts[m.group(1)] += 1
        return counts
    return Counter(
        normalize_primitive(e.primitive.name) for e in iter_eqns(obj)
        if normalize_primitive(e.primitive.name) in COLLECTIVE_PRIMITIVES
    )


def assert_no_collective(obj, kinds: Optional[Iterable[str]] = None) -> None:
    """Pin: the program contains NO collectives (or none of ``kinds``)."""
    found = collect_collectives(obj)
    if kinds is not None:
        found = Counter({k: v for k, v in found.items() if k in set(kinds)})
    assert not found, f"unexpected collectives in program: {dict(found)}"


def assert_collective_count(obj, kind: str, expected: int) -> None:
    """Pin: exactly ``expected`` instances of one collective kind."""
    got = collect_collectives(obj)[kind]
    assert got == expected, (
        f"expected {expected} x {kind!r}, found {got} "
        f"(census: {dict(collect_collectives(obj))})")


def assert_collective_present(obj, kind: str) -> None:
    """Pin: at least one instance of ``kind`` survives in the program
    (e.g. the lowered ppermute was not optimized away on a fake mesh)."""
    got = collect_collectives(obj)[kind]
    assert got >= 1, (
        f"no {kind!r} in program (census: {dict(collect_collectives(obj))})")


def hlo_collective_shapes(text: str,
                          kind: str = "all-gather") -> List[Tuple[int, str]]:
    """Every (element count, line) for shapes on compiled-text lines that
    mention ``kind``. Matches EVERY shape on the line — async forms carry
    tuple shapes, and missing those would pass vacuously (the lesson both
    hand-rolled greps encode)."""
    out: List[Tuple[int, str]] = []
    for ln in text.splitlines():
        if kind not in ln:
            continue
        for m in _HLO_SHAPE_RE.finditer(ln):
            dims = [int(d) for d in m.group(1).split(",") if d]
            out.append((int(np.prod(dims)) if dims else 0, ln))
    return out


_HLO_ARRAY_RE = re.compile(r"\b([a-z]+\d+|pred)\[([\d,]*)\]")
_HLO_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
                    "f16": 2, "bf16": 2, "s32": 4, "u32": 4, "f32": 4,
                    "s64": 8, "u64": 8, "f64": 8}


def hlo_collective_bytes(text: str) -> Counter:
    """Bytes each kind of collective moves in one run of the compiled
    program, summed over its instructions: the arrays of an instruction's
    RESULT type (between ``=`` and the opcode), per partition. The async
    ``-start`` of a collective-permute or an all-gather carries a tuple
    ``(operand, result, ...)``: its second array is counted; an
    all-reduce's start carries its results only. ``-done`` lines are
    not counted (they move nothing of their own)."""
    out: Counter = Counter()
    for ln in text.splitlines():
        m = _HLO_OP_RE.search(ln)
        if not m:
            continue
        kind, is_start = m.group(1), bool(m.group(2))
        sizes = []
        for dtype, dims in _HLO_ARRAY_RE.findall(ln[:m.start(1)]):
            n = _HLO_DTYPE_BYTES.get(dtype)
            if n is None:
                continue
            for d in dims.split(","):
                n *= int(d) if d else 1
            sizes.append(n)
        if is_start and kind in ("collective-permute", "all-gather") \
                and len(sizes) >= 2:
            sizes = sizes[1:2]
        out[kind] += sum(sizes)
    return out


def assert_no_collective_as_large_as(text: str, numel: int,
                                     kind: str = "all-gather") -> None:
    """Pin: no ``kind`` line in the compiled text touches a shape with
    >= ``numel`` elements — the "no full-activation all-gather" contract
    (docs/PARALLELISM.md)."""
    for n, ln in hlo_collective_shapes(text, kind):
        assert n < numel, (
            f"{kind} as large as the pinned bound ({n} >= {numel}): {ln}")


# -------------------------------------------------- scan-carry ppermute


def scan_ppermute_carry_flags(jaxpr) -> List[bool]:
    """For every ``ppermute`` directly inside a ``lax.scan`` body: True iff
    its operand is a scan CARRY invar (the transfer consumes the previous
    tick's value and has no data dependence on this tick's compute — the
    latency-hiding schedule's structural property)."""
    if hasattr(jaxpr, "jaxpr"):
        jaxpr = jaxpr.jaxpr
    out: List[bool] = []

    def walk(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "scan":
                body = eqn.params["jaxpr"].jaxpr
                nc, nk = eqn.params["num_consts"], eqn.params["num_carry"]
                carry = set(map(id, body.invars[nc:nc + nk]))
                for e2 in body.eqns:
                    if normalize_primitive(e2.primitive.name) == "ppermute":
                        out.append(id(e2.invars[0]) in carry)
                walk(body)
            else:
                for sub in sub_jaxprs(eqn.params):
                    walk(sub)

    walk(jaxpr)
    return out


# ------------------------------------------------------- lint findings


def resolve_callback_target(eqn) -> Optional[str]:
    """The USER function behind a callback eqn, or None.

    ``jax.debug.callback`` wraps the user callable in a ``_flat_callback``
    closure, and the repo's obs taps bind theirs through
    ``functools.partial`` (obs/taps.py ``nan_sentinel``) — so the raw
    ``eqn.params['callback']`` never names the function a human would
    recognize. Resolution: look through the jax flat-callback closure,
    then through ONE level of ``functools.partial`` (the repo's binding
    idiom; deeper nesting stays anonymous on purpose — resolve it when a
    real tap needs it)."""
    import functools

    cb = eqn.params.get("callback")
    if cb is None:
        return None
    if getattr(cb, "__name__", "") == "_flat_callback" \
            and getattr(cb, "__closure__", None):
        for cell in cb.__closure__:
            try:
                v = cell.cell_contents
            except ValueError:
                continue
            if callable(v):
                cb = v
                break
    if isinstance(cb, functools.partial):
        cb = cb.func
    return getattr(cb, "__name__", None) or type(cb).__name__


def host_callback_findings(jaxpr, tag: str = "program",
                           allow: Iterable[str] = ()) -> List[Finding]:
    """Findings for host callbacks inside a supposedly-hot program.

    ``allow`` exempts PRIMITIVE names (``debug_callback`` — every debug
    callback passes) or RESOLVED target function names (``_on_counts`` —
    only the obs sentinel's own callback passes, anything else still
    flags; see :func:`resolve_callback_target`)."""
    allowed = {normalize_primitive(a) for a in allow} | set(allow)
    out: List[Finding] = []
    for eqn in iter_eqns(jaxpr):
        name = normalize_primitive(eqn.primitive.name)
        if name not in _CALLBACK_PRIMITIVES:
            continue
        target = resolve_callback_target(eqn)
        if name in allowed or (target is not None and target in allowed):
            continue
        fname, line = eqn_location(eqn)
        what = f"{name}->{target}" if target else name
        out.append(Finding(
            rule=RULE_HOST_CALLBACK, severity=ERROR,
            file=fname, line=line, path=None if fname else tag,
            message=f"host callback {what!r} in hot path {tag!r} — "
                    "route telemetry through p2p_tpu/obs seams or keep "
                    "it out of the jitted step",
        ))
    return out


def f32_leak_findings(jaxpr, tag: str = "program",
                      policy: str = "bfloat16") -> List[Finding]:
    """Findings for ``dot_general``/``conv_general_dilated`` eqns with a
    float32 operand under a declared low-precision compute policy.

    The check is on OPERANDS (not outputs): f32 accumulation via
    ``preferred_element_type`` is the policy-conformant pattern, an f32
    input tensor is a leak — it forces the full-precision MXU path and
    doubles the operand's HBM traffic.

    Findings dedupe per source location: one line of model code expands
    to many eqns (taps, fwd + transpose instances, microbatches) but is
    ONE policy decision — the finding carries the eqn count instead of
    repeating per eqn (which would also let a single waived line inflate
    the waiver-count metric by hundreds)."""
    seen: dict = {}
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name not in ("dot_general", "conv_general_dilated"):
            continue
        dtypes = []
        for v in eqn.invars:
            aval = getattr(v, "aval", None)
            dtypes.append(str(getattr(aval, "dtype", "?")))
        if any(d == "float32" for d in dtypes):
            fname, line = eqn_location(eqn)
            key = (fname, line, eqn.primitive.name, tuple(dtypes))
            if key in seen:
                seen[key] = (seen[key][0], seen[key][1] + 1)
            else:
                seen[key] = (Finding(
                    rule=RULE_F32_LEAK, severity=ERROR,
                    file=fname, line=line, path=None if fname else tag,
                    message=f"{eqn.primitive.name} with float32 operand "
                            f"{tuple(dtypes)} under declared {policy} "
                            f"policy in {tag!r}",
                ), 1)
    out: List[Finding] = []
    for f, n in seen.values():
        if n > 1:
            f.message += f" (x{n} eqns at this line)"
        out.append(f)
    return out
