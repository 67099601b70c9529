"""Sharding-rule auditor — static verification of a partition-rule table
against a state tree, with zero device memory.

:func:`p2p_tpu.parallel.rules.match_partition_rules` raises on an
UNMATCHED leaf, but that is the only failure it can see. This auditor
detects what first-match-wins semantics silently absorb:

- **dead rules** that fire on no leaf at all (typo'd pattern, stale path
  after a model rename) — the rule table claims coverage it doesn't have;
- **shadowed rules**: every leaf a rule matches is claimed by an EARLIER
  pattern, so the rule can never fire — the classic silent layout bug
  when a specific rule lands after a broad one;
- **specs naming mesh axes that don't exist** on the target mesh;
- **indivisible shards**: a spec's sharded axis product does not divide
  the leaf dimension (GSPMD would pad or error at run time — the audit
  says so at lint time);
- spec **rank overflow** (more partitioned dims than the leaf has).

State trees come from ``jax.eval_shape`` over the real constructors
(:func:`abstract_train_state`) — shapes and paths only, no allocation, so
the full-size preset states audit on a CPU CI runner.

The ``tp``-diff mode (:func:`tp_rule_gaps`) diffs the reference
shape-conditional TP assignment (:func:`p2p_tpu.parallel.tp.tp_leaf_spec`)
against a declarative rule table and reports exactly which leaves the
table cannot express. The worklist is DRAINED and the hand-built tree is
retired to a shim (ISSUE 15): the live layouts run from
``parallel/rules.py`` alone, and this diff is the standing proof the
tables still reproduce the reference assignment.
"""

from __future__ import annotations

import re
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from p2p_tpu.analysis.findings import ERROR, INFO, WARNING, Finding

RULE_UNMATCHED = "sharding-unmatched-leaf"
RULE_DEAD = "sharding-dead-rule"
RULE_SHADOWED = "sharding-shadowed-rule"
RULE_UNKNOWN_AXIS = "sharding-unknown-axis"
RULE_INDIVISIBLE = "sharding-indivisible"
RULE_RANK = "sharding-spec-rank"
RULE_TP_GAP = "sharding-tp-rule-gap"

#: patterns treated as an intentional replicate-everything catch-all —
#: exempt from dead/shadow accounting (a catch-all SHOULD be unreachable
#: when earlier rules cover the tree).
_CATCH_ALL = {r".*", r"^.*$", r"(.*)"}

MeshLike = Union[None, Dict[str, int], Any]  # dict of axis sizes or a Mesh


def mesh_axis_sizes(mesh: MeshLike) -> Optional[Dict[str, int]]:
    """Axis-name → size view of a ``jax.sharding.Mesh`` OR a plain dict —
    the audit never needs devices, so a hypothetical topology ({"data": 8,
    "model": 4}) works on a 1-CPU runner."""
    if mesh is None:
        return None
    if isinstance(mesh, dict):
        return {str(k): int(v) for k, v in mesh.items()}
    shape = getattr(mesh, "shape", None)  # Mesh.shape is an axis->size map
    if shape is not None:
        return {str(k): int(v) for k, v in dict(shape).items()}
    raise TypeError(f"mesh must be a Mesh or {{axis: size}} dict, "
                    f"got {type(mesh).__name__}")


def named_leaves(tree: Any) -> List[Tuple[str, str, Tuple[int, ...]]]:
    """(slash-joined rule path, keystr path, shape) for every array-like
    leaf of ``tree`` — works on concrete arrays and on the
    ``ShapeDtypeStruct`` leaves :func:`abstract_train_state` produces."""
    import jax

    from p2p_tpu.parallel.rules import leaf_path_name

    out = []
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    for path, leaf in flat:
        shape = getattr(leaf, "shape", None)
        if shape is None:
            shape = np.shape(leaf)
        out.append((leaf_path_name(path), jax.tree_util.keystr(path),
                    tuple(int(d) for d in shape)))
    return out


def _spec_partitions(spec) -> List[Tuple[int, Tuple[str, ...]]]:
    """(dim index, axis names) for every partitioned dim of a
    PartitionSpec; a dim entry may be one axis or a tuple of axes."""
    out = []
    for d, entry in enumerate(tuple(spec)):
        if entry is None:
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        out.append((d, tuple(str(a) for a in axes)))
    return out


def _is_scalar(shape: Tuple[int, ...]) -> bool:
    # the universal floor rule: scalars / 1-element leaves never partition
    return len(shape) == 0 or int(np.prod(shape)) == 1


def _table_axis_findings(compiled, sizes: Dict[str, int]) -> List[Finding]:
    """Unknown-axis check runs TABLE-level, once per rule, so a dead or
    shadowed rule's bogus axis is still reported (per-leaf checking would
    mask it — the rule never fires on anything). Spec-BUILDER rules
    (callable specs, the fsdp table) have no table-level spec to inspect
    — ``audit_rules`` collects the axes their per-leaf resolutions
    actually name and reports through the same rule id."""
    out: List[Finding] = []
    for idx, (_, pat, spec, _pred) in enumerate(compiled):
        if callable(spec):
            continue
        missing = sorted({a for _, axes in _spec_partitions(spec)
                          for a in axes if a not in sizes})
        if missing:
            out.append(Finding(
                rule=RULE_UNKNOWN_AXIS, severity=ERROR, path=f"rule[{idx}]",
                message=f"rule[{idx}] {pat!r} spec {spec} names mesh "
                        f"ax{'es' if len(missing) > 1 else 'is'} "
                        f"{missing} absent from the target mesh "
                        f"(have {sorted(sizes)})",
            ))
    return out


def _spec_findings(spec, name: str, shape: Tuple[int, ...],
                   sizes: Optional[Dict[str, int]],
                   rule_label: str) -> List[Finding]:
    out: List[Finding] = []
    parts = _spec_partitions(spec)
    if parts and max(d for d, _ in parts) >= len(shape):
        out.append(Finding(
            rule=RULE_RANK, severity=ERROR, path=name,
            message=f"spec {spec} from {rule_label} partitions dim "
                    f"{max(d for d, _ in parts)} of a rank-{len(shape)} "
                    f"leaf (shape {shape})",
        ))
        return out
    for d, axes in parts:
        if sizes is not None:
            if any(a not in sizes for a in axes):
                continue  # reported once, table-level (_table_axis_findings)
            total = int(np.prod([sizes[a] for a in axes]))
            if total > 1 and shape[d] % total != 0:
                out.append(Finding(
                    rule=RULE_INDIVISIBLE, severity=ERROR, path=name,
                    message=f"spec {spec} from {rule_label} shards dim "
                            f"{d} (={shape[d]}) over {axes} "
                            f"(size {total}), which does not divide it",
                ))
    return out


def audit_rules(rules: Sequence[Tuple[str, Any]], tree: Any,
                mesh: MeshLike = None) -> List[Finding]:
    """Statically verify a rule table against a state tree (and optionally
    a mesh topology). Returns findings; an empty list is the audit's
    "every leaf matches, every rule earns its place" certificate."""
    from p2p_tpu.parallel.rules import resolve_spec, rule_parts

    sizes = mesh_axis_sizes(mesh)
    leaves = named_leaves(tree)
    compiled = []
    for rule in rules:
        pat, spec, pred = rule_parts(rule)
        compiled.append((re.compile(pat), pat, spec, pred))
    findings: List[Finding] = []
    if sizes is not None:
        findings.extend(_table_axis_findings(compiled, sizes))
    fired = [0] * len(compiled)
    claimed_by: Dict[str, int] = {}
    #: rule idx -> axes its spec-BUILDER resolutions named (callable
    #: specs have no table-level view — the unknown-axis check runs on
    #: this union after the leaf walk)
    builder_axes: Dict[int, set] = {}

    for name, _, shape in leaves:
        if _is_scalar(shape):
            continue  # the scalar floor never consults the table
        for idx, (cre, pat, spec, pred) in enumerate(compiled):
            if cre.search(name) is not None \
                    and (pred is None or pred(tuple(shape))):
                fired[idx] += 1
                claimed_by[name] = idx
                leaf_spec = resolve_spec(spec, shape)
                if callable(spec):
                    builder_axes.setdefault(idx, set()).update(
                        a for _, axes in _spec_partitions(leaf_spec)
                        for a in axes)
                findings.extend(_spec_findings(
                    leaf_spec, name, shape, sizes,
                    rule_label=f"rule[{idx}] {pat!r}"))
                break
        else:
            findings.append(Finding(
                rule=RULE_UNMATCHED, severity=ERROR, path=name,
                message=f"no rule matches leaf (shape {shape}); tried "
                        f"{len(compiled)} rules — add a catch-all "
                        f"(\".*\", P())",
            ))

    for idx, (cre, pat, spec, pred) in enumerate(compiled):
        if fired[idx] or pat in _CATCH_ALL:
            continue
        # a predicate rule "matches" a leaf only when its predicate also
        # accepts the shape — a regex-hit/predicate-miss leaf is neither
        # claimed nor shadow evidence
        shadow_hits = [(name, claimed_by[name])
                       for name, _, shape in leaves
                       if not _is_scalar(shape) and name in claimed_by
                       and cre.search(name) is not None
                       and (pred is None or pred(tuple(shape)))]
        if shadow_hits:
            name0, by = min(shadow_hits, key=lambda t: t[1])
            by_pat = compiled[by][1]
            findings.append(Finding(
                rule=RULE_SHADOWED, severity=ERROR, path=f"rule[{idx}]",
                message=f"rule[{idx}] {pat!r} matches "
                        f"{len(shadow_hits)} leaves (e.g. {name0!r}) but "
                        f"every one is claimed by the earlier rule[{by}] "
                        f"{by_pat!r} — it can never fire",
            ))
        else:
            findings.append(Finding(
                rule=RULE_DEAD, severity=WARNING, path=f"rule[{idx}]",
                message=f"rule[{idx}] {pat!r} fires on no leaf of the "
                        "audited tree — stale path or typo'd pattern",
            ))
    if sizes is not None:
        for idx, axes in sorted(builder_axes.items()):
            missing = sorted(a for a in axes if a not in sizes)
            if missing:
                findings.append(Finding(
                    rule=RULE_UNKNOWN_AXIS, severity=ERROR,
                    path=f"rule[{idx}]",
                    message=f"rule[{idx}] {compiled[idx][1]!r} "
                            f"(spec builder) resolved specs naming mesh "
                            f"ax{'es' if len(missing) > 1 else 'is'} "
                            f"{missing} absent from the target mesh "
                            f"(have {sorted(sizes)})",
                ))
    return findings


# -------------------------------------------------------- tp-diff mode


def tp_rule_gaps(tree: Any, rules: Optional[Sequence[Tuple[str, Any]]] = None,
                 axis_size: int = 2, min_ch: int = 512,
                 ) -> Tuple[List[dict], List[Finding]]:
    """Diff the shape-conditional TP assignment against a declarative rule
    table, leaf by leaf.

    Returns ``(worklist, findings)``: each worklist entry names a leaf the
    regex table gets WRONG relative to ``tp_leaf_spec`` (either the table
    replicates what TP shards — the common gap, needing a predicate rule —
    or the table shards what TP replicates, e.g. a width gate the regex
    cannot express). This is the ROADMAP item-3 migration worklist; the
    findings mirror it at ``info`` severity so the lint gate reports
    without failing on it.
    """
    from jax.sharding import PartitionSpec as P

    from p2p_tpu.parallel.rules import (
        REPLICATED_RULES,
        resolve_spec,
        rule_parts,
    )
    from p2p_tpu.parallel.tp import tp_leaf_spec

    rules = REPLICATED_RULES if rules is None else rules
    compiled = []
    for rule in rules:
        pat, spec, pred = rule_parts(rule)
        compiled.append((re.compile(pat), spec, pred))
    worklist: List[dict] = []
    findings: List[Finding] = []
    for name, keystr, shape in named_leaves(tree):
        if _is_scalar(shape):
            continue
        tp_spec = tp_leaf_spec(keystr, shape, axis_size, min_ch)
        rule_spec = None
        for cre, spec, pred in compiled:
            if cre.search(name) is not None \
                    and (pred is None or pred(tuple(shape))):
                rule_spec = resolve_spec(spec, shape)
                break
        if rule_spec is None or tuple(tp_spec) == tuple(rule_spec):
            continue  # unmatched leaves are audit_rules' finding, not a gap
        direction = ("needs-predicate-rule" if tuple(rule_spec) == ()
                     or rule_spec == P() else "table-overshards")
        worklist.append({
            "leaf": name, "shape": shape, "tp_spec": str(tp_spec),
            "rule_spec": str(rule_spec), "direction": direction,
        })
        findings.append(Finding(
            rule=RULE_TP_GAP, severity=INFO, path=name,
            message=f"tp_leaf_spec says {tp_spec}, rule table says "
                    f"{rule_spec} (shape {shape}) — {direction}",
        ))
    return worklist, findings


# --------------------------------------------------- shape-only states


def abstract_train_state(cfg, batch_size: Optional[int] = None,
                         train_dtype=None):
    """The preset's full TrainState as a ShapeDtypeStruct tree via
    ``jax.eval_shape`` — real constructors, real paths, ZERO device
    memory, so a 1024×512 preset audits on a laptop CPU."""
    import jax

    from p2p_tpu.train.state import create_train_state

    from p2p_tpu.utils.images import dummy_batch

    sample = dummy_batch(cfg, (batch_size or cfg.data.batch_size,))
    return jax.eval_shape(
        lambda: create_train_state(cfg, jax.random.key(0), sample,
                                   train_dtype=train_dtype))
