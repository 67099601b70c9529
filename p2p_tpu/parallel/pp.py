"""Pipeline parallelism (GPipe) over the ``pipe`` mesh axis — SURVEY §2.4 PP row.

The reference is single-device (no pipeline anywhere in /root/reference);
SURVEY §2.4 scoped PP "out-of-scope v1, design mesh axes so it can be
added". This module adds it, TPU-native:

- **Stage unit** — the generator's residual trunk: the only depth-regular,
  FLOP-dominant segment in the zoo (9 identical 128-ch blocks in the
  flagship ExpandNetwork, networks.py:472-480; ``n_blocks`` up to 9 in the
  ResNet family). Each of the S pipeline stages owns ``n_blocks/S``
  consecutive blocks; their parameters are *stacked* along a leading stage
  axis and sharded over ``pipe``, so stage weights live only on their
  stage's devices (the point of PP: fit a deeper trunk than one chip's HBM).
- **Schedule** — GPipe fill/drain over M microbatches inside ONE jitted
  ``shard_map``: every tick each stage applies its block stack
  (``lax.scan`` over the stacked block params) and hands its activation to
  the next stage with a neighbor ``ppermute`` (``pipe`` is the innermost
  mesh axis — the shift is one ICI hop). T = M + S − 1 ticks; bubble
  fraction (S−1)/T exactly as GPipe.
- **Backward** — ``jax.grad`` of the same program: the transpose of
  ``ppermute`` is the reverse shift, so autodiff derives the reverse-order
  pipeline schedule with no hand-written VJP.
- **Norm semantics** — microbatching changes *train-mode BatchNorm*
  statistics (per-microbatch instead of per-batch — the GPipe paper's BN
  caveat), so the pipelined trunk applies blocks with frozen (eval)
  BatchNorm stats. InstanceNorm models are unaffected (per-sample stats):
  for the instance-norm family (cityscapes / pix2pixHD — where model scale
  actually motivates PP) the pipelined forward AND gradients are exact vs
  the train-mode unpipelined model; for the BatchNorm flagship they are
  exact vs eval mode. Both pinned in tests/test_pp.py.

Composability: the microbatch batch axis stays sharded over ``data``
(in-spec ``P(None, 'data', ...)``), so PP composes with DP on one mesh —
exercised by the dryrun phase 5 (data=2 × pipe=4) and tests.

Single-chip note: this environment exposes ONE real TPU chip, so PP here is
validated for numerics on the fake CPU mesh and compile-checked via the
driver dryrun, like TP (parallel/tp.py).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from p2p_tpu.core.mesh import (
    DATA_AXIS,
    PIPE_AXIS,
    pcast_varying,
)

# (block_vars, y) -> y — or -> (y, quant_proposal) for the delayed-int8
# trunk (gpipe_trunk dispatches on the stacked 'quant' collection)
BlockApply = Callable[[Dict[str, Any], jax.Array], Any]


def stack_trunk(variables: Dict[str, Any], n_stages: int,
                prefix: str = "ResidualBlock_") -> Dict[str, Any]:
    """Stack the trunk's per-block variable subtrees into stage-major arrays.

    Returns a tree shaped like one block's variables but with every leaf
    prefixed by ``[S, B]`` axes (S stages × B = n_blocks/S blocks per
    stage); block ``s*B + j`` sits at ``[s, j]``, so scanning j within a
    pipelined stage s applies blocks in the original serial order.
    """
    names = [n for n in variables["params"] if n.startswith(prefix)]
    names.sort(key=lambda n: int(n[len(prefix):]))
    n_blocks = len(names)
    if n_blocks == 0:
        raise ValueError(f"no {prefix}* blocks in variables")
    if n_blocks % n_stages:
        raise ValueError(
            f"{n_blocks} trunk blocks not divisible by {n_stages} stages")
    def gather(collection):
        # ONE stacking law (shared with the init_opt=False opt-moment
        # split): the params-derived block list drives every collection
        return _gather_stack(collection, prefix, n_stages, names=names)

    stacked = {"params": gather(variables["params"])}
    # stage-regular non-param collections ride along: BN running stats and
    # the delayed-int8 'quant' amax scales (both per-block, both [S, B]-
    # stackable — the quant GPipe semantics live in gpipe_trunk)
    for coll in ("batch_stats", "quant"):
        entries = variables.get(coll) or {}
        if names[0] in entries:
            stacked[coll] = gather(entries)
    return stacked


def place_trunk_pp(stacked: Dict[str, Any], mesh: Mesh) -> Dict[str, Any]:
    """Shard the stacked trunk stage-axis over ``pipe`` (each stage's block
    weights live only on that stage's devices)."""
    sh = NamedSharding(mesh, P(PIPE_AXIS))
    return jax.tree.map(lambda a: jax.device_put(a, sh), stacked)


def gpipe_trunk(block_apply: BlockApply, stacked: Dict[str, Any],
                y_mb: jax.Array, mesh: Mesh, overlap: bool = False):
    """Run the stacked trunk over ``y_mb`` [M, mb, H, W, C] with the GPipe
    fill/drain schedule on the mesh's ``pipe`` axis.

    ``block_apply(block_vars, y) -> y`` applies ONE residual block given its
    (unstacked) variable subtree. Output has the same shape/sharding as
    ``y_mb`` (mb stays on ``data``); result is replicated over ``pipe``.

    ``overlap=True`` switches to the LATENCY-HIDING schedule: the hand-off
    is double-buffered — each tick issues the ``ppermute`` on the PREVIOUS
    tick's output (a scan-carry value, structurally independent of this
    tick's block compute), so the ICI transfer runs concurrently with the
    stage compute instead of serializing after it. The stage→stage hop then
    takes two ticks (stage ``s`` holds microbatch ``t − 2s`` at tick ``t``)
    and the schedule runs ``M + 2(S−1)`` ticks vs the serial ``M + S − 1``:
    the doubled fill/drain bubble buys ticks of ``max(compute, transfer)``
    instead of ``compute + transfer`` — a win when ``transfer/compute >
    (S−1)/(M+S−1)``. Numerics are IDENTICAL (same blocks on the same
    microbatches; pinned bitwise in tests/test_pp.py), and the
    issued-from-carry property is pinned structurally on the jaxpr.

    When ``stacked`` carries a ``'quant'`` collection (the delayed-int8
    trunk, ops/int8.py), ``block_apply`` must instead return ``(y, quant
    proposal)`` — the block applied with the FROZEN stored scales plus the
    mutated collection it proposes. Every microbatch then quantizes with
    the same start-of-step scale (exactly the unpipelined batch semantics)
    and the per-microbatch proposals are max-combined over the valid ticks
    and psum-maxed over ``data``, which reproduces the unpipelined
    full-batch ``amax_update`` bitwise (ops/int8.py). Returns ``(y_out,
    new_quant_stack)`` in that case, ``y_out`` alone otherwise.
    """
    n_stages = mesh.shape[PIPE_AXIS]
    n_micro = int(y_mb.shape[0])
    # per-stage microbatch lag: 1 tick/hop serial, 2 ticks/hop overlapped
    lag = 2 if overlap else 1
    ticks = n_micro + lag * (n_stages - 1)
    act_spec = P(None, DATA_AXIS, *([None] * (y_mb.ndim - 2)))
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
    has_quant = "quant" in stacked

    def shard_fn(st, xmb):
        local = jax.tree.map(lambda a: a[0], st)   # this stage's [B, ...]
        idx = jax.lax.axis_index(PIPE_AXIS)

        def stage(y):
            if has_quant:
                def body(c, bv):
                    return block_apply(bv, c)      # (y', quant proposal)
                return jax.lax.scan(body, y, local)

            def body(c, bv):
                return block_apply(bv, c), None
            y, _ = jax.lax.scan(body, y, local)
            return y, {}

        def retire(out, y_out, t):
            # last stage retires microbatch t-lag·(S-1) into its slot
            o_idx = jnp.clip(t - lag * (n_stages - 1), 0, n_micro - 1)
            prev = jax.lax.dynamic_index_in_dim(out, o_idx, 0,
                                                keepdims=False)
            write = jnp.logical_and(t >= lag * (n_stages - 1),
                                    idx == n_stages - 1)
            return jax.lax.dynamic_update_index_in_dim(
                out, jnp.where(write, y_out, prev), o_idx, 0)

        def acc_quant(qacc, qp, t):
            # amax bookkeeping is carried state, never a loss input —
            # cut it out of the autodiff graph (pmax/psum-max below
            # have no differentiation rule, and none is wanted)
            qp = jax.tree.map(jax.lax.stop_gradient, qp)
            # stage `idx` holds microbatch t-lag·idx at tick t — bubble
            # ticks (fill zeros, drain re-feeds) must not touch amax
            valid = jnp.logical_and(t >= lag * idx,
                                    t - lag * idx <= n_micro - 1)
            return jax.tree.map(
                lambda a, p: jnp.where(valid, jnp.maximum(a, p), a),
                qacc, qp)

        def feed_at(t):
            # stage 0 injects microbatch t (clamped re-feeds during drain
            # are bubble ticks whose output is never written)
            return jax.lax.dynamic_index_in_dim(
                xmb, jnp.minimum(t, n_micro - 1), 0, keepdims=False)

        def tick(carry, t):
            act, out, qacc = carry
            y_out, qp = stage(jnp.where(idx == 0, feed_at(t), act))
            if has_quant:
                qacc = acc_quant(qacc, qp, t)
            out = retire(out, y_out, t)
            return (jax.lax.ppermute(y_out, PIPE_AXIS, perm), out, qacc), None

        def tick_overlap(carry, t):
            recv, y_prev, out, qacc = carry
            # double-buffered hand-off: transfer LAST tick's output now —
            # ``y_prev`` is a scan carry, so this collective has no data
            # dependence on this tick's stage compute and the scheduler is
            # free to run the ICI hop under it (the latency-hiding point;
            # pinned structurally by tests/test_pp.py)
            send = jax.lax.ppermute(y_prev, PIPE_AXIS, perm)
            y_out, qp = stage(jnp.where(idx == 0, feed_at(t), recv))
            if has_quant:
                qacc = acc_quant(qacc, qp, t)
            out = retire(out, y_out, t)
            return (send, y_out, out, qacc), None

        # carries are stage-varying (idx enters tick) — pcast the replicated
        # zeros to the varying type shard_map's vma tracking expects
        zero = pcast_varying(
            jnp.zeros(xmb.shape[1:], xmb.dtype), (DATA_AXIS, PIPE_AXIS))
        out0 = pcast_varying(jnp.zeros_like(xmb), (PIPE_AXIS,))
        # amax proposals are >= 0, so max-accumulation starts from zeros
        q0 = jax.tree.map(
            lambda a: pcast_varying(jnp.zeros_like(a),
                                    (DATA_AXIS, PIPE_AXIS)),
            local.get("quant", {}))
        if overlap:
            zero2 = pcast_varying(
                jnp.zeros(xmb.shape[1:], xmb.dtype), (DATA_AXIS, PIPE_AXIS))
            (_, _, out, qacc), _ = jax.lax.scan(
                tick_overlap, (zero, zero2, out0, q0), jnp.arange(ticks))
        else:
            (_, out, qacc), _ = jax.lax.scan(
                tick, (zero, out0, q0), jnp.arange(ticks))
        # non-last stages accumulated zeros; the masked psum replicates the
        # last stage's outputs to every pipe shard
        y_full = jax.lax.psum(
            jnp.where(idx == n_stages - 1, out, jnp.zeros_like(out)),
            PIPE_AXIS)
        # each data shard saw only its rows — the global amax is the max
        # over the data axis (exact: max of maxes), stage-local otherwise
        q_new = jax.tree.map(
            lambda a: jax.lax.pmax(a, DATA_AXIS)[None], qacc)
        return y_full, q_new

    y_out, q_new = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(PIPE_AXIS), act_spec),
        out_specs=(act_spec, P(PIPE_AXIS)),
    )(stacked, y_mb)
    return (y_out, q_new) if has_quant else y_out


# ---------------------------------------------------------------------------
# Generator wiring: pipelined trunk inside the REAL model module
# ---------------------------------------------------------------------------


def _quant_applier(block):
    """Applier for a delayed-int8 block: frozen stored scales in the
    forward, mutated 'quant' collection returned as the update proposal
    (gpipe_trunk max-combines proposals — the semantics contract is
    ops/int8.py amax_update)."""

    def apply_mut(bvars, y):
        out, mut = block.apply(bvars, y, False, mutable=["quant"])
        return out, mut["quant"]

    return apply_mut


def make_expand_block_apply(model_cfg, dtype=None) -> BlockApply:
    """Block applier for ExpandNetwork's ``ResidualBlock_i`` trunk
    (frozen-stat norms — see module docstring). The int8 trunk (dynamic or
    delayed scales) pipelines too: the delayed form returns ``(y, quant
    proposal)`` pairs for gpipe_trunk's stacked-quant path."""
    from p2p_tpu.models.expand import ResidualBlock

    int8_g = model_cfg.int8 and model_cfg.int8_generator
    block = ResidualBlock(
        model_cfg.ngf * 4, norm=model_cfg.norm, int8=int8_g,
        int8_delayed=model_cfg.int8_delayed,
        legacy_layout=model_cfg.legacy_layout, dtype=dtype)
    if int8_g and model_cfg.int8_delayed:
        return _quant_applier(block)

    def apply_one(bvars, y):
        return block.apply(bvars, y, False)

    return apply_one


def make_resnet_block_apply(features: int, norm: str = "instance",
                            legacy_layout: bool = False, int8: bool = False,
                            int8_delayed: bool = False,
                            dtype=None) -> BlockApply:
    """Block applier for the ResNet family's ``ResnetBlock_i`` trunk
    (models/resnet_gen.py — cityscapes and pix2pixHD's ``global``/G1,
    whose 1024-channel trunk is where PP actually pays). Use with
    ``stack_trunk(variables, n_stages, prefix="ResnetBlock_")`` and
    ``gpipe_trunk``. Instance norm is per-sample, so the pipelined trunk
    is exact vs train mode (module docstring)."""
    from p2p_tpu.models.resnet_gen import ResnetBlock

    block = ResnetBlock(features, norm=norm, int8=int8,
                        int8_delayed=int8_delayed,
                        legacy_layout=legacy_layout, dtype=dtype)
    if int8 and int8_delayed:
        return _quant_applier(block)

    def apply_one(bvars, y):
        return block.apply(bvars, y, False)

    return apply_one


def mb_major_flatten(t: jax.Array) -> jax.Array:
    """[M, mb, ...] -> [mb*M, ...] with the data-sharded mb axis OUTERMOST,
    so GSPMD keeps flat-batch (encoder/decoder) compute data-parallel — an
    M-major flatten interleaves the shards and forces XLA to all-gather the
    full batch onto every device. The ONE definition of the carve order
    (its inverse below; pinned by the no-all-gather HLO test)."""
    n_micro, mb = t.shape[0], t.shape[1]
    return jnp.swapaxes(t, 0, 1).reshape((mb * n_micro,) + t.shape[2:])


def mb_major_unflatten(t: jax.Array, n_micro: int) -> jax.Array:
    """Inverse of :func:`mb_major_flatten`: [mb*M, ...] -> [M, mb, ...]."""
    mb = t.shape[0] // n_micro
    return jnp.swapaxes(t.reshape((mb, n_micro) + t.shape[1:]), 0, 1)


_TRUNK_PREFIX = {"expand": "ResidualBlock_", "resnet": "ResnetBlock_"}


def trunk_prefix(model_cfg) -> str:
    try:
        return _TRUNK_PREFIX[model_cfg.generator]
    except KeyError:
        raise NotImplementedError(
            f"pp pipelines the expand/resnet trunk families, not "
            f"{model_cfg.generator!r} (docs/PARALLELISM.md v2 boundaries)"
        ) from None


def _trunk_block_apply(model_cfg, dtype=None) -> BlockApply:
    if model_cfg.generator == "expand":
        return make_expand_block_apply(model_cfg, dtype)
    # ResnetGenerator via define_G uses its default n_downsampling=2 and
    # no feature cap → the trunk width is ngf * 4
    int8_g = model_cfg.int8 and model_cfg.int8_generator
    return make_resnet_block_apply(
        model_cfg.ngf * 4, norm=model_cfg.norm,
        legacy_layout=model_cfg.legacy_layout, int8=int8_g,
        int8_delayed=model_cfg.int8_delayed, dtype=dtype)


def pp_generator_forward(model_cfg, variables: Dict[str, Any],
                         x_mb: jax.Array, mesh: Mesh,
                         stacked: Optional[Dict[str, Any]] = None,
                         dtype=None, with_quant: bool = False,
                         overlap: bool = False):
    """Full pipelined generator forward (expand / resnet trunk families).

    ``x_mb``: [M, mb, H, W, 3] microbatched input (mb sharded over ``data``).
    Encoder/decoder run replicated over ``pipe`` on the mb-major flat batch
    (they are <15% of the FLOPs — networks.py:460-520; pipelining them buys
    nothing at this depth) through the REAL model module via its
    ``trunk_fn`` hook — no hand-mirrored forward to drift — while the
    residual trunk runs the GPipe schedule. The mb-major flatten keeps the
    data-sharded mb axis outermost so GSPMD keeps the encoder/decoder
    data-parallel (an M-major flatten interleaves the shards and forces
    XLA to all-gather the full batch onto every device — pinned by the HLO
    test in tests/test_pp.py).

    ``with_quant=True`` additionally returns the updated stacked 'quant'
    collection (None when the trunk carries none).
    """
    from p2p_tpu.models.registry import define_G

    prefix = trunk_prefix(model_cfg)
    if stacked is None:
        stacked = stack_trunk(variables, mesh.shape[PIPE_AXIS],
                              prefix=prefix)
    block_apply = _trunk_block_apply(model_cfg, dtype)

    n_micro = int(x_mb.shape[0])
    q_new = None

    def trunk_fn(y):
        nonlocal q_new
        r = gpipe_trunk(block_apply, stacked,
                        mb_major_unflatten(y, n_micro), mesh,
                        overlap=overlap)
        if "quant" in stacked:
            y_mb, q_new = r
        else:
            y_mb = r
        return mb_major_flatten(y_mb)

    g = define_G(model_cfg, dtype=dtype)
    y = g.apply(
        {"params": variables["params"],
         "batch_stats": variables.get("batch_stats", {})},
        mb_major_flatten(x_mb), False, trunk_fn=trunk_fn,
    )
    y = mb_major_unflatten(y, n_micro)
    return (y, q_new) if with_quant else y


def pp_expand_forward(model_cfg, variables: Dict[str, Any], x_mb: jax.Array,
                      mesh: Mesh,
                      stacked: Optional[Dict[str, Any]] = None,
                      dtype=None, overlap: bool = False) -> jax.Array:
    """Pipelined flagship (ExpandNetwork) forward — the expand-only entry
    point kept for compatibility; :func:`pp_generator_forward` is the
    general form (and the one the PP train step uses)."""
    if model_cfg.generator != "expand":
        raise NotImplementedError(
            "pp_expand_forward pipelines the ExpandNetwork trunk; use "
            "pp_generator_forward for the ResNet family")
    return pp_generator_forward(model_cfg, variables, x_mb, mesh,
                                stacked=stacked, dtype=dtype,
                                overlap=overlap)


# ---------------------------------------------------------------------------
# Trainer wiring: TrainState surgery for the PP step (train/step.py
# build_pp_train_step)
# ---------------------------------------------------------------------------


def _trunk_dict_map(tree, prefix: str, fn):
    """Apply ``fn`` to every dict node of ``tree`` that holds trunk-block
    entries (keys starting with ``prefix``), leaving everything else —
    including the optax wrapper scalars (counts, hyperparams) — intact.
    The Adam mu/nu trees mirror the param tree, so ONE traversal rule
    restructures params, batch_stats, quant, and both moments."""
    def is_trunk_dict(x):
        return isinstance(x, dict) and any(
            isinstance(k, str) and k.startswith(prefix) for k in x)

    return jax.tree_util.tree_map(
        lambda n: fn(n) if is_trunk_dict(n) else n,
        tree, is_leaf=is_trunk_dict)


def _trunk_names(tree: Dict[str, Any], prefix: str):
    names = [n for n in tree if n.startswith(prefix)]
    names.sort(key=lambda n: int(n[len(prefix):]))
    return names


def _gather_stack(tree: Dict[str, Any], prefix: str, n_stages: int,
                  names=None):
    """{block_i: subtree} → one-block-shaped subtree with [S, B] leaves —
    THE stacking law: block ``s*B + j`` lands at ``[s, j]``. Used by
    :func:`stack_trunk` (which passes the params-derived ``names`` so a
    collection missing a block fails loudly) and by the init_opt=False
    moment split on any param-mirroring dict."""
    if names is None:
        names = _trunk_names(tree, prefix)
    per = len(names) // n_stages
    blocks = [tree[n] for n in names]
    flat = jax.tree.map(lambda *leaves: jnp.stack(leaves), *blocks)
    return jax.tree.map(
        lambda a: a.reshape((n_stages, per) + a.shape[1:]), flat)


def unstack_trunk(stacked: Dict[str, Any], prefix: str) -> Dict[str, Any]:
    """Inverse of the ``stack_trunk`` gather on ONE collection subtree:
    a one-block-shaped tree with [S, B] leading axes → ``{prefix}{i}``
    per-block subtrees, block ``s*B + j`` read from ``[s, j]`` (the same
    ordering law, so merge-then-split round-trips bitwise)."""
    leaves = jax.tree_util.tree_leaves(stacked)
    if not leaves:
        return {}
    s, b = leaves[0].shape[:2]
    flat = jax.tree.map(lambda a: a.reshape((s * b,) + a.shape[2:]), stacked)
    return {f"{prefix}{i}": jax.tree.map(lambda a: a[i], flat)
            for i in range(s * b)}


def pp_merge_state(state, cfg, steps_per_epoch: int = 1):
    """Inverse of :func:`pp_split_state`: fold the stage-stacked trunk
    (``pp_stages`` + ``opt_s``) back into the flat generator tree.

    The per-block params / batch_stats / quant entries re-enter
    ``params_g``/``batch_stats_g``/``quant_g`` under their original
    ``{prefix}{i}`` names, and ``opt_g`` is rebuilt over the full tree
    with the trunk leaves' Adam moments UNSTACKED from ``opt_s`` (per-leaf
    Adam is independent per leaf, so the merged trajectory is the split
    one — nothing is re-initialized). The elastic pipe-width migration
    (p2p_tpu.resilience.reshape) uses merge → :func:`pp_split_state`
    (``init_opt=False``) to re-express a checkpoint at any new width,
    pipe→no-pipe and no-pipe→pipe included.
    """
    from p2p_tpu.train.state import make_optimizers

    if state.pp_stages is None:
        return state
    prefix = trunk_prefix(cfg.model)
    stacked = state.pp_stages
    params_g = {**state.params_g, **unstack_trunk(stacked["params"], prefix)}
    batch_stats_g = state.batch_stats_g
    if "batch_stats" in stacked:
        batch_stats_g = {**(batch_stats_g or {}),
                         **unstack_trunk(stacked["batch_stats"], prefix)}
    quant_g = state.quant_g
    if "quant" in stacked:
        quant_g = {**(quant_g or {}),
                   **unstack_trunk(stacked["quant"], prefix)}

    # Rebuild the full-tree opt STRUCTURE, then fill every leaf from its
    # source: non-trunk paths (and the wrapper's count/hyperparams
    # scalars) exist verbatim in opt_g; trunk paths strip their block
    # segment and index [s, j] into the stacked opt_s leaf.
    opt_g, _, _ = make_optimizers(cfg, steps_per_epoch)
    template = opt_g.init(params_g)
    rest = {jax.tree_util.keystr(p): leaf for p, leaf
            in jax.tree_util.tree_flatten_with_path(state.opt_g)[0]}
    stacked_opt = {jax.tree_util.keystr(p): leaf for p, leaf
                   in jax.tree_util.tree_flatten_with_path(state.opt_s)[0]}
    s_b = jax.tree_util.tree_leaves(stacked["params"])[0].shape[:2]
    per = int(s_b[1])

    def fill(path, zero):
        key = jax.tree_util.keystr(path)
        if key in rest:
            return rest[key]
        for k in path:
            name = getattr(k, "key", None)
            if isinstance(name, str) and name.startswith(prefix):
                i = int(name[len(prefix):])
                stripped = key.replace(f"['{name}']", "", 1)
                return stacked_opt[stripped][i // per, i % per]
        raise KeyError(f"opt leaf {key} in neither opt_g nor opt_s")

    merged_opt = jax.tree_util.tree_map_with_path(fill, template)
    return state.replace(
        params_g=params_g,
        batch_stats_g=batch_stats_g,
        quant_g=quant_g,
        opt_g=merged_opt,
        pp_stages=None,
        opt_s=None,
    )


def pp_split_state(state, cfg, mesh: Optional[Mesh] = None,
                   steps_per_epoch: int = 1,
                   n_stages: Optional[int] = None,
                   init_opt: bool = True, place: bool = True):
    """Move the generator trunk out of a flat TrainState into the
    pipe-sharded ``pp_stages`` stack with its own optimizer state.

    The trunk's per-block ``params`` / ``batch_stats`` / ``quant`` entries
    leave ``params_g``/``batch_stats_g``/``quant_g`` (stage weights live
    only on their stage's devices — the point of PP); ``opt_s`` gets the
    same optimizer over the stacked stage params. Per-leaf Adam makes the
    split update trajectory identical to the fused one.

    ``init_opt=True`` (training START): ``opt_g``/``opt_s`` are freshly
    initialized — fresh Adam state is zeros either way. ``init_opt=False``
    (the elastic pipe-width migration): the flat state's LIVE optimizer
    moments are carried — the trunk-less remainder stripped in place, the
    trunk moments stacked under the same [S, B] law as the params — so a
    mid-run checkpoint re-expresses at a new width without losing its
    trajectory. ``n_stages`` defaults to the mesh's pipe width;
    ``place=False`` skips the device placement (template building for a
    cross-topology restore needs shapes, not a mesh).
    """
    from p2p_tpu.train.state import make_optimizers

    prefix = trunk_prefix(cfg.model)
    if n_stages is None:
        n_stages = mesh.shape[PIPE_AXIS]
    variables = {"params": state.params_g}
    if state.batch_stats_g:
        variables["batch_stats"] = state.batch_stats_g
    if state.quant_g:
        variables["quant"] = state.quant_g
    stacked = stack_trunk(variables, n_stages, prefix=prefix)
    if place:
        stacked = place_trunk_pp(stacked, mesh)

    def strip(tree):
        if not tree:
            return tree
        return {k: v for k, v in tree.items() if not k.startswith(prefix)}

    params_rest = strip(state.params_g)
    if init_opt:
        # optax transforms are stateless — ONE generator-family optimizer
        # serves both the trunk-less tree and the stage stack
        opt_g, _, _ = make_optimizers(cfg, steps_per_epoch)
        new_opt_g = opt_g.init(params_rest)
        new_opt_s = opt_g.init(stacked["params"])
    else:
        new_opt_g = _trunk_dict_map(state.opt_g, prefix, strip)
        new_opt_s = _trunk_dict_map(
            state.opt_g, prefix,
            lambda t: _gather_stack(t, prefix, n_stages))
    return state.replace(
        params_g=params_rest,
        batch_stats_g=strip(state.batch_stats_g),
        quant_g=(strip(state.quant_g)
                 if state.quant_g is not None else None),
        opt_g=new_opt_g,
        pp_stages=stacked,
        opt_s=new_opt_s,
    )
