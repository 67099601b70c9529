"""Static per-device HBM budgeting + buffer-donation audit.

Three capabilities, all ``eval_shape``/trace/lowering-text based — zero
device compute, so a 1024×512 preset budgets on a 1-CPU CI runner:

1. **State budget** (:func:`state_budget`): per-device bytes of the full
   TrainState — params / optimizer moments / EMA / quant scales / other —
   for a named config × mesh (plain ``{axis: size}`` dicts, no devices).
   Layout comes from THE live partitioner
   (``parallel/rules.trainstate_rules``): Megatron TP pair shards when
   the mesh has a real model axis, ZeRO optimizer/EMA (± param) shards
   when it has a real fsdp axis, replicated otherwise — i.e. the budget
   reflects exactly what the trainers place. Every fsdp row additionally
   carries ``opt_ema_reduction`` vs its fsdp=1 twin, and
   ``memory-fsdp-shortfall`` (error) fires when the sharded
   optimizer+EMA bytes fail the ZeRO arithmetic — at least
   (axis−1)/axis of the replicated bytes must vanish (small slack for
   the indivisible leaves: Adam count scalars, odd-width heads).
2. **Activation peak** (:func:`traced_peak_bytes`): a linear liveness scan
   over the traced train-step jaxpr — allocate each eqn's outputs, free
   every value after its last use, track the high-water mark. An UPPER
   BOUND (XLA fuses/donates/rematerializes below it), but a static one
   that moves with the model, so regressions show as table diffs.
   :func:`memory_budget_table` combines 1+2 into the per-config×mesh
   table the lint CLI publishes as ``memory_budget.json``.
3. **Donation audit** (:func:`donation_findings`): parses the LOWERED
   program text for per-parameter donation markers — single-device
   lowerings resolve donation to ``tf.aliasing_output = N``, multi-device
   lowerings carry the ``jax.buffer_donor`` request — and flags any
   sizeable state leaf with NEITHER on a program that declares
   ``donate_argnums``: that leaf is silently copied instead of donated,
   and the step holds 2× its bytes at peak. ``memory-donation-missing``
   fires when a supposedly-donating program shows no markers at all.

Plus the serving-restore check (:func:`dead_restore_findings`):
``memory-dead-restore`` flags a serving restore template that reads
subtrees the engine immediately discards (the EMA-serving case: restoring
``params_g`` just to swap in ``ema_g`` doubles the generator restore
bytes). It audits the LIVE template helper
(:func:`p2p_tpu.serve.engine.serving_restore_template`), so the gate
holds as the serving path evolves.
"""

from __future__ import annotations

import re
from collections import Counter
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from p2p_tpu.analysis.findings import ERROR, INFO, WARNING, Finding

RULE_DONATION_MISSING = "memory-donation-missing"
RULE_DONATION_DEFEATED = "memory-donation-defeated"
RULE_DEAD_RESTORE = "memory-dead-restore"
RULE_OVER_HBM = "memory-over-hbm"
RULE_FSDP_SHORTFALL = "memory-fsdp-shortfall"

#: tolerated shortfall from the ideal 1/axis optimizer+EMA bytes: the
#: leaves the fsdp spec builder legally replicates (Adam count scalars,
#: inject_hyperparams scalars, dims no axis divides) are a fixed few
#: hundred bytes — 2% covers them on every checked-in config
FSDP_REDUCTION_SLACK = 0.02

#: default per-device HBM budget (v5e-class chip), overridable via
#: ``P2P_HBM_GB`` for other parts
DEFAULT_HBM_GB = 16.0

#: the config × mesh matrix the budget table covers. The FIRST mesh of
#: each preset is its canonical topology (over-budget there is a warning;
#: hypothetical rows report at info level via the table only).
MEMORY_MATRIX: Tuple[Tuple[str, Tuple[Dict[str, int], ...]], ...] = (
    ("facades", ({"data": 1}, {"data": 1, "model": 2},
                 # ISSUE 15 canonical fsdp rows: the ZeRO optimizer+EMA
                 # shard — CI asserts each row's opt_ema_reduction ≥
                 # (axis−1)/axis − slack vs its fsdp=1 twin
                 {"data": 1, "fsdp": 4})),
    ("facades_int8", ({"data": 1}, {"data": 1, "fsdp": 2})),
    ("edges2shoes_dp", ({"data": 8}, {"data": 4, "model": 2},
                        {"data": 2, "fsdp": 4})),
    ("cityscapes_spatial", ({"data": 2, "spatial": 2},)),
    ("pix2pixhd", ({"data": 1, "spatial": 2},
                   {"data": 1, "spatial": 2, "model": 2},
                   {"data": 1, "spatial": 2, "fsdp": 2})),
)


def leaf_nbytes(leaf) -> int:
    shape = tuple(getattr(leaf, "shape", ()) or ())
    dt = np.dtype(getattr(leaf, "dtype", np.float32))
    return int(np.prod(shape, dtype=np.int64)) * dt.itemsize if shape \
        else dt.itemsize


def _component(name: str) -> str:
    head = name.split("/", 1)[0]
    if head.startswith("params_") or head == "pp_stages":
        return "params"   # the PP stage stack IS generator params
    if head.startswith("opt_"):
        return "opt"
    if head == "ema_g":
        return "ema"
    if head.startswith("quant_"):
        return "quant"
    return "other"


def state_budget(cfg, mesh_sizes: Dict[str, int],
                 tp_min_ch: int = 512,
                 fsdp_params: bool = False) -> Dict[str, int]:
    """Per-device TrainState bytes by component for ``cfg`` on a
    hypothetical mesh. The layout law IS the live partitioner
    (``parallel/rules.trainstate_rules`` resolved per leaf): TP channel
    shards when ``model > 1``, ZeRO optimizer/EMA (± param under
    ``fsdp_params``) shards when ``fsdp > 1``, everything else
    replicated — data/spatial/time axes still do NOT divide state
    bytes."""
    import jax

    from p2p_tpu.analysis.sharding_audit import abstract_train_state
    from p2p_tpu.parallel.rules import (
        leaf_path_name,
        match_partition_rules,
        trainstate_rules,
    )

    sizes = {str(k): int(v) for k, v in mesh_sizes.items()}
    rules = trainstate_rules(sizes, tp_min_ch=tp_min_ch,
                             fsdp_params=fsdp_params)
    out: Dict[str, int] = {"params": 0, "opt": 0, "ema": 0, "quant": 0,
                           "other": 0}
    from jax.sharding import PartitionSpec as P

    state = abstract_train_state(cfg)
    specs = match_partition_rules(rules, state)
    flat, _ = jax.tree_util.tree_flatten_with_path(state)
    # P may subclass tuple on this jax — is_leaf keeps each spec atomic
    flat_specs = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, P))
    for (path, leaf), spec in zip(flat, flat_specs):
        name = leaf_path_name(path)
        nbytes = leaf_nbytes(leaf)
        shard = 1
        for entry in tuple(spec or ()):
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            for a in axes:
                shard *= sizes.get(str(a), 1)
        out[_component(name)] += nbytes // max(1, shard)
    out["state_total"] = sum(out.values())
    return out


# ------------------------------------------------------- liveness peak


def traced_peak_bytes(jaxpr) -> int:
    """High-water-mark bytes of a traced program under a linear
    allocate-at-def / free-after-last-use scan of its top-level eqns.
    Sub-jaxprs (scan bodies, custom-vjp branches) are treated as atomic:
    their operands and results count, their internals don't — a
    documented under-approximation inside scans, an over-approximation
    everywhere XLA fuses."""
    if hasattr(jaxpr, "jaxpr"):
        jaxpr = jaxpr.jaxpr

    def nbytes(v) -> int:
        aval = getattr(v, "aval", None)
        if aval is None or not hasattr(aval, "shape"):
            return 0
        try:
            item = np.dtype(aval.dtype).itemsize
        except TypeError:
            item = 4   # extended dtypes (PRNG keys): count the key words
        return int(np.prod(aval.shape, dtype=np.int64)) * item \
            if len(aval.shape) else item

    is_var = lambda v: type(v).__name__ == "Var"  # noqa: E731
    # Literals are unhashable — key everything by id (vars are unique
    # objects within one jaxpr)
    last_use: Dict[int, int] = {}
    size: Dict[int, int] = {}
    n = len(jaxpr.eqns)
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.invars:
            if is_var(v):
                last_use[id(v)] = i
                size[id(v)] = nbytes(v)
    for v in jaxpr.outvars:
        if is_var(v):
            last_use[id(v)] = n
            size[id(v)] = nbytes(v)
    # DropVar outputs (discarded results of multi-output eqns — scan
    # residual slots, unused grads) are materialized at the eqn and dead
    # immediately after: count them toward THIS eqn's peak only, never
    # into the running live set (they have no uses, so the last-use map
    # would otherwise keep their bytes resident forever).
    is_drop = lambda v: type(v).__name__ == "DropVar"  # noqa: E731
    live = sum(nbytes(v) for v in list(jaxpr.invars) + list(jaxpr.constvars))
    peak = live
    for i, eqn in enumerate(jaxpr.eqns):
        dropped = sum(nbytes(v) for v in eqn.outvars if is_drop(v))
        live += sum(nbytes(v) for v in eqn.outvars if not is_drop(v))
        peak = max(peak, live + dropped)
        dead = {id(v) for v in list(eqn.invars) + list(eqn.outvars)
                if is_var(v) and last_use.get(id(v), n + 1) <= i}
        for vid in dead:
            live -= size.get(vid, 0)
    return int(peak)


def activation_peak_bytes(cfg, local_batch: int, train_dtype=None) -> int:
    """Liveness peak of the preset's traced train step at ``local_batch``,
    MINUS the resident state bytes — the activations+workspace share of
    the budget. Pure tracing (``jax.make_jaxpr`` over ShapeDtypeStructs)."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from p2p_tpu.analysis.sharding_audit import abstract_train_state
    from p2p_tpu.train.step import build_train_step

    if train_dtype is None and cfg.train.mixed_precision:
        train_dtype = jnp.bfloat16
    cfg = dataclasses.replace(
        cfg, data=dataclasses.replace(cfg.data,
                                      batch_size=max(1, int(local_batch))))
    state = abstract_train_state(cfg, batch_size=cfg.data.batch_size,
                                 train_dtype=train_dtype)
    sds = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), state)
    from p2p_tpu.utils.images import dummy_batch

    batch = dummy_batch(cfg, (cfg.data.batch_size,), abstract=True)
    step = build_train_step(cfg, train_dtype=train_dtype, jit=False)
    jx = jax.make_jaxpr(step)(sds, batch)
    state_bytes = sum(leaf_nbytes(l) for l in jax.tree_util.tree_leaves(sds))
    return max(0, traced_peak_bytes(jx) - state_bytes)


def memory_budget_table(hbm_gb: Optional[float] = None,
                        matrix=MEMORY_MATRIX,
                        ) -> Tuple[List[dict], List[Finding]]:
    """The per-config×mesh HBM budget table (the ``memory_budget.json``
    artifact) plus findings: ``memory-over-hbm`` (warning) when a preset's
    CANONICAL mesh row exceeds the budget; hypothetical rows only report
    in the table (``fits`` flag)."""
    import os

    from p2p_tpu.core.config import get_preset

    if hbm_gb is None:
        hbm_gb = float(os.environ.get("P2P_HBM_GB", DEFAULT_HBM_GB))
    budget = int(hbm_gb * (1 << 30))
    rows: List[dict] = []
    findings: List[Finding] = []
    for preset, meshes in matrix:
        cfg = get_preset(preset)
        # trace once per preset at local batch 1, scale linearly in the
        # per-device batch and inversely in the activation-sharding axes
        act1 = activation_peak_bytes(cfg, 1)
        for j, mesh in enumerate(meshes):
            # batches shard over data AND fsdp (core/mesh.BATCH_AXES)
            data = int(mesh.get("data", 1)) * int(mesh.get("fsdp", 1))
            act_shard = int(mesh.get("spatial", 1)) * int(mesh.get("time", 1))
            local_bs = max(1, cfg.data.batch_size // max(1, data))
            state = state_budget(cfg, mesh,
                                 tp_min_ch=cfg.parallel.tp_min_ch)
            act = act1 * local_bs // max(1, act_shard)
            total = state["state_total"] + act
            row = {
                "preset": preset,
                "mesh": dict(mesh),
                "canonical": j == 0,
                "local_batch": local_bs,
                "bytes": {**{k: int(v) for k, v in state.items()},
                          "activation_peak": int(act),
                          "total": int(total)},
                "hbm_budget_bytes": budget,
                "fits": total <= budget,
            }
            fsdp = int(mesh.get("fsdp", 1))
            if fsdp > 1:
                # the ZeRO arithmetic, CI-asserted: vs the same config on
                # the fsdp=1 twin mesh, per-device optimizer+EMA bytes
                # must drop by at least (axis-1)/axis (minus the slack
                # the indivisible leaves cost)
                twin = state_budget(cfg, {**mesh, "fsdp": 1},
                                    tp_min_ch=cfg.parallel.tp_min_ch)
                rep = twin["opt"] + twin["ema"]
                shd = state["opt"] + state["ema"]
                reduction = 1.0 - (shd / rep) if rep else 0.0
                row["opt_ema_reduction"] = round(reduction, 4)
                row["fsdp_axis"] = fsdp
                floor = (fsdp - 1) / fsdp - FSDP_REDUCTION_SLACK
                if reduction < floor:
                    findings.append(Finding(
                        rule=RULE_FSDP_SHORTFALL, severity=ERROR,
                        path=f"{preset}×{mesh}",
                        message=f"fsdp={fsdp} sharded optimizer+EMA bytes "
                                f"{shd} vs replicated {rep}: reduction "
                                f"{reduction:.3f} < required "
                                f"{floor:.3f} — the ZeRO rules stopped "
                                "sharding this state (dead rule? pattern "
                                "drift?)",
                    ))
            rows.append(row)
            if j == 0 and not row["fits"]:
                findings.append(Finding(
                    rule=RULE_OVER_HBM, severity=WARNING,
                    path=f"{preset}×{mesh}",
                    message=f"projected per-device HBM "
                            f"{total / (1 << 30):.2f} GiB exceeds the "
                            f"{hbm_gb:.0f} GiB budget on the preset's "
                            "canonical mesh (static bound: state + "
                            "liveness activation peak, no donation/remat "
                            "credit) — shard state (FSDP), enable remat, "
                            "or shrink the local batch",
                ))
            else:
                findings.append(Finding(
                    rule=RULE_OVER_HBM, severity=INFO,
                    path=f"{preset}×{mesh}",
                    message=f"per-device HBM {total / (1 << 30):.2f} GiB "
                            f"of {hbm_gb:.0f} GiB "
                            f"({'fits' if row['fits'] else 'OVER'})",
                ))
    return rows, findings


# ------------------------------------------------------ donation audit


_MAIN_SIG_RE = re.compile(
    r"func\.func public @main\((.*?)\)\s*->\s*(.*?)\s*\{\s*$", re.S | re.M)
_TENSOR_RE = re.compile(r"tensor<((?:\d+x)*)[a-zA-Z]+?(\d+)\w*>")
_ALIAS_RE = re.compile(r"tf\.aliasing_output\s*=\s*(\d+)")


def _tensor_nbytes(entry: str) -> Optional[int]:
    """Byte size of the first ``tensor<...>`` type in a signature entry
    (``tensor<64x64xbf16>`` -> 8192); None when the entry has none."""
    m = _TENSOR_RE.search(entry)
    if m is None:
        return None
    n = 1
    for d in m.group(1).split("x")[:-1]:
        n *= int(d)
    return n * max(1, int(m.group(2)) // 8)


def lowered_donation_markers(lowered_text: str) -> Optional[List[bool]]:
    """Per-argument "this buffer is reused" flags from a lowered
    program's main signature, as jax 0.9 writes it:

    - ``tf.aliasing_output = N``: donation RESOLVED at lowering to
      result N (same shape and dtype) — True;
    - ``jax.buffer_donor = true``: donation handed to XLA, which jax
      does when the exact match is deferred (sharded/auto layouts) OR
      when only the ELEMENT COUNT of some result matches (an
      ``astype``'d leaf). XLA reuses a donor only for a result of the
      same BYTE size, so the flag is True iff such a result is still
      unclaimed (aliased results and earlier donors claim theirs);
    - neither: False.

    None when the main signature cannot be parsed."""
    m = _MAIN_SIG_RE.search(lowered_text)
    if m is None:
        return None
    entries = re.split(r",\s*(?=%arg\d+)", m.group(1))
    results = re.split(r",\s*(?=tensor<|!)", m.group(2).strip("()"))
    aliased = {int(a.group(1)) for e in entries
               for a in _ALIAS_RE.finditer(e)}
    free: Counter = Counter(
        _tensor_nbytes(r) for i, r in enumerate(results)
        if i not in aliased)
    flags = []
    for e in entries:
        if "tf.aliasing_output" in e:
            flags.append(True)
        elif "jax.buffer_donor" in e:
            nbytes = _tensor_nbytes(e)
            reusable = nbytes is not None and free[nbytes] > 0
            if reusable:
                free[nbytes] -= 1
            flags.append(reusable)
        else:
            flags.append(False)
    return flags


def _jaxpr_used_invars(jaxpr) -> List[bool]:
    """Per-invar used flags for a (Closed)Jaxpr — an invar feeding no eqn
    and no output is pruned from the lowered main signature
    (``jit``'s default ``keep_unused=False``)."""
    if hasattr(jaxpr, "jaxpr"):
        jaxpr = jaxpr.jaxpr
    used = set()
    for eqn in jaxpr.eqns:
        for v in eqn.invars:
            used.add(id(v))
    for v in jaxpr.outvars:
        used.add(id(v))
    return [id(v) in used for v in jaxpr.invars]


def donation_findings(lowered_text: str, donated_tree: Any, tag: str,
                      min_bytes: int = 1024, jaxpr=None) -> List[Finding]:
    """Findings for a jitted program that declares ``donate_argnums=0``:
    ``donated_tree`` is the (abstract) first argument; a leaf of at least
    ``min_bytes`` whose lowered parameter carries no donation marker is
    copied instead of donated — the program holds 2× its bytes at peak.

    ``jaxpr`` (the SAME trace the lowering came from) aligns the lowered
    parameter list with the flattened tree: ``jit`` prunes UNUSED args
    from the main signature (``keep_unused=False``), so a positional map
    would attribute flags to the wrong leaves the moment a state leaf
    goes unread — pass it whenever available. Pruned (unused) leaves are
    skipped: no buffer is consumed, so there is nothing to donate."""
    import jax

    flags = lowered_donation_markers(lowered_text)
    if flags is None:
        return [Finding(
            rule=RULE_DONATION_MISSING, severity=ERROR, path=tag,
            message="could not parse the lowered program's main signature "
                    "— donation audit impossible (jax lowering format "
                    "change?)")]
    flat, _ = jax.tree_util.tree_flatten_with_path(donated_tree)
    if jaxpr is not None:
        used = _jaxpr_used_invars(jaxpr)
        if len(used) < len(flat) or sum(used) != len(flags):
            return [Finding(
                rule=RULE_DONATION_MISSING, severity=ERROR, path=tag,
                message=f"argument mapping failed: jaxpr has "
                        f"{len(used)} invars ({sum(used)} used) vs "
                        f"{len(flat)} donated leaves and {len(flags)} "
                        "lowered parameters")]
        leaf_flags: List[Optional[bool]] = []
        pos = 0
        for i in range(len(flat)):
            if used[i]:
                leaf_flags.append(flags[pos])
                pos += 1
            else:
                leaf_flags.append(None)   # pruned: nothing to donate
    else:
        if len(flags) < len(flat):
            return [Finding(
                rule=RULE_DONATION_MISSING, severity=ERROR, path=tag,
                message=f"lowered program has {len(flags)} parameters "
                        f"but the donated tree has {len(flat)} leaves — "
                        "argument mapping failed (pass jaxpr= for "
                        "pruned-arg alignment)")]
        leaf_flags = list(flags[: len(flat)])
    live = [f for f in leaf_flags if f is not None]
    if live and not any(live):
        return [Finding(
            rule=RULE_DONATION_MISSING, severity=ERROR, path=tag,
            message="no donation marker on ANY state parameter — the "
                    "program copies the whole state every step (is "
                    "donate_argnums missing on the jit?)")]
    out: List[Finding] = []
    for i, (path, leaf) in enumerate(flat):
        if leaf_flags[i] is not False:
            continue
        nbytes = leaf_nbytes(leaf)
        if nbytes < min_bytes:
            continue
        out.append(Finding(
            rule=RULE_DONATION_DEFEATED, severity=ERROR,
            path=f"{tag}:{jax.tree_util.keystr(path)}",
            message=f"state leaf ({nbytes} B) declared donated but "
                    "carries no aliasing/donor marker in the lowered "
                    "program — it is copied, not donated (shape/dtype "
                    "changed between input and output?)",
        ))
    return out


# -------------------------------------------------- serving dead restore


def template_dead_restore_findings(template, tag: str) -> List[Finding]:
    """The template-level check behind :func:`dead_restore_findings`: an
    EMA-serving template carrying BOTH ``params_g`` and ``ema_g`` restores
    a generator tree it immediately discards."""
    import jax

    has_ema = bool(jax.tree_util.tree_leaves(template.ema_g))
    has_params = bool(jax.tree_util.tree_leaves(template.params_g))
    if not (has_ema and has_params):
        return []
    nbytes = sum(leaf_nbytes(l) for l in
                 jax.tree_util.tree_leaves(template.params_g))
    return [Finding(
        rule=RULE_DEAD_RESTORE, severity=ERROR, path=tag,
        message=f"EMA-serving template restores BOTH params_g "
                f"({nbytes} B) and ema_g, then discards params_g — 2× "
                "generator restore traffic and transient memory; prune "
                "params_g from the template",
    )]


def dead_restore_findings(presets: Sequence[str] = ("facades",),
                          ) -> List[Finding]:
    """Audit the LIVE serving restore template: any top-level subtree the
    engine restores and then immediately discards is dead restore traffic
    (and transient 2× memory at engine construction). The EMA-serving
    template is the known case: it must prune ``params_g`` and restore
    only the smoothed tree (p2p_tpu/serve/engine.py
    ``serving_restore_template``)."""
    import dataclasses as dc

    import jax

    from p2p_tpu.core.config import get_preset
    from p2p_tpu.serve.engine import serving_restore_template

    out: List[Finding] = []
    for preset in presets:
        cfg = get_preset(preset)
        # the EMA variant is where the dead restore can creep in
        cfg = dc.replace(cfg, health=dc.replace(cfg.health, ema_decay=0.999))
        from p2p_tpu.utils.images import dummy_batch

        sample = dummy_batch(cfg, dtype=np.uint8)
        template = jax.eval_shape(
            lambda c=cfg, s=sample: serving_restore_template(c, s))
        out.extend(template_dead_restore_findings(
            template, tag=f"serving_restore_template[{preset}+ema]"))
    return out
