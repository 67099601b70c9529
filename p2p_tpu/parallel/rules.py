"""Rule-driven partition-spec derivation over named state trees — THE
sharding authority for the whole TrainState (ROADMAP item 3, closed by
ISSUE 15).

The regex-over-named-tree ``match_partition_rules`` pattern of SNIPPETS
[1]/[2]: ONE ordered rule table matched against slash-joined leaf paths
produces the PartitionSpec tree for an arbitrary pytree (params,
optimizer moments, EMA, or a whole TrainState; adam's mu/nu mirror the
param paths, so one param rule covers all three). Every live layout —
CLI trainer placement, serving-engine placement, the elastic restore
targets, the static memory budget — derives from
:func:`state_target_shardings` over :func:`trainstate_rules`; the old
hand-built TP tree builder in ``parallel/tp.py`` is a thin shim over
these tables (a CI grep gate keeps it that way).

Rule entries, first ``re.search`` match wins:

- ``(regex, PartitionSpec)``;
- ``(regex, PartitionSpec, predicate)`` — **predicate rules**: fires only
  when ``predicate(shape)`` also accepts the leaf shape (the TP tables
  gate every channel shard on width/divisibility, which a bare regex
  cannot see);
- ``(regex, spec_builder)`` where ``spec_builder(shape) -> PartitionSpec``
  — **spec-builder rules** (ISSUE 15): the FSDP table needs a
  per-shape DIMENSION choice (shard a conv kernel's C_out, a bias's only
  dim), which a fixed spec cannot express; the builder keeps the table
  declarative while choosing the partitioned dim per leaf.

Tables:

- :func:`make_tp_rules` — the union of the per-family Megatron TP tables
  (U-Net + ResNet/pix2pixHD/Expand trunks + PatchGAN chains), pinned
  equal to the retired hand-built assignment (zero tp-diff gaps, CI-
  grepped);
- :func:`make_fsdp_rules` — ZeRO-style state sharding over the ``fsdp``
  mesh axis: Adam moments (``opt_g/d/c``) and ``ema_g`` partition along
  the data dimension (ZeRO-1); ``fsdp_params=True`` additionally shards
  ``params_g/d/c`` (ZeRO-3-ish, gather-on-use left to GSPMD via the pjit
  in/out shardings — no hand-written collectives anywhere);
- :func:`trainstate_rules` composes them for a mesh: TP pairs claim
  their leaves first (a TP-sharded moment mirrors its param shard), the
  FSDP rules claim the rest of the optimizer/EMA state, a catch-all
  replicates the remainder.

Scalars (and 1-element leaves) never partition — the universal floor rule
the snippets agree on.
"""

from __future__ import annotations

import re
from typing import Any, Callable, Dict, Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from p2p_tpu.core.mesh import FSDP_AXIS, MODEL_AXIS

#: ``(regex, spec_or_builder[, predicate])`` entries, first match wins
#: (re.search semantics; a predicate rule only matches when
#: ``predicate(shape)`` is also true; a callable spec is resolved per
#: leaf as ``spec(shape)``).
Rules = Sequence[Tuple]

ShapePredicate = Callable[[Tuple[int, ...]], bool]
SpecBuilder = Callable[[Tuple[int, ...]], P]
SpecLike = Union[P, SpecBuilder]


def rule_parts(rule) -> Tuple[str, SpecLike, Optional[ShapePredicate]]:
    """Normalize a 2- or 3-tuple rule entry to ``(pattern, spec, pred)``."""
    if len(rule) == 2:
        return rule[0], rule[1], None
    pat, spec, pred = rule
    return pat, spec, pred


def resolve_spec(spec: SpecLike, shape) -> P:
    """A rule's concrete PartitionSpec for one leaf: fixed specs pass
    through, spec builders are called with the leaf shape."""
    return spec(tuple(shape)) if callable(spec) else spec

#: The baseline table: fully-replicated state — correct for DP and for
#: every mesh whose extra axes (spatial/time/pipe) shard activations, not
#: parameters. trainstate_rules layers the TP/FSDP tables ON TOP.
REPLICATED_RULES: Rules = ((r".*", P()),)


def leaf_path_name(path) -> str:
    """``jax.tree_util`` key path → slash-joined rule-matchable name,
    e.g. ``params_g/down1/conv/kernel``."""
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "name"):
            parts.append(str(k.name))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            # pinned fallback for unknown key types (a future jax key kind
            # must not silently change every rule-matchable path): the
            # type name is part of the segment, so a rule written against
            # the old ``str(k)`` form fails LOUDLY instead of matching a
            # different leaf. Format pinned by tests/test_elastic.py.
            parts.append(f"<{type(k).__name__}:{k}>")
    return "/".join(parts)


def match_partition_rules(rules: Rules, tree: Any):
    """PartitionSpec pytree for ``tree`` from an ordered rule table.

    Every leaf must match some rule (append a ``(".*", P())`` catch-all
    for replicate-by-default); an unmatched leaf raises — silently
    replicating a leaf the table meant to shard is how layout bugs hide.
    """

    def spec_for(path, leaf):
        name = leaf_path_name(path)
        shape = np.shape(leaf) if not hasattr(leaf, "shape") else leaf.shape
        if len(shape) == 0 or int(np.prod(shape)) == 1:
            return P()  # never partition scalars
        for rule in rules:
            pat, ps, pred = rule_parts(rule)
            if re.search(pat, name) is not None \
                    and (pred is None or pred(tuple(shape))):
                return resolve_spec(ps, shape)
        tried = "; ".join(f"[{i}] {rule_parts(r)[0]!r}"
                          for i, r in enumerate(rules))
        raise ValueError(f"no partition rule matched leaf {name!r} "
                         f"(shape {tuple(shape)}); tried "
                         f"{tried or '<empty table>'} — add a catch-all "
                         f"rule ('.*', P())")

    return jax.tree_util.tree_map_with_path(spec_for, tree)


def state_target_shardings(state: Any, mesh: Mesh,
                           rules: Optional[Rules] = None,
                           tp_min_ch: int = 512,
                           fsdp_params: bool = False):
    """NamedSharding pytree: THE layout of ``state`` on ``mesh`` — the
    single source of truth for trainer placement, serving placement, and
    the elastic restore targets.

    ``rules=None`` derives the table from the mesh itself via
    :func:`trainstate_rules`: Megatron TP pair shards when the ``model``
    axis is real, ZeRO optimizer/EMA shards when the ``fsdp`` axis is
    real (params too under ``fsdp_params``), replicated otherwise.
    """
    if rules is None:
        rules = trainstate_rules(dict(mesh.shape), tp_min_ch=tp_min_ch,
                                 fsdp_params=fsdp_params)
    specs = match_partition_rules(rules, state)
    return jax.tree_util.tree_map(lambda ps: NamedSharding(mesh, ps), specs,
                                  is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# Family TP tables — predicate rules reproducing parallel/tp.tp_leaf_spec
# declaratively, family by family (the item-3 worklist drain).
# ---------------------------------------------------------------------------

_OUT_K = P(None, None, None, MODEL_AXIS)   # conv kernel, C_out sharded
_IN_K = P(None, None, MODEL_AXIS, None)    # conv kernel, C_in sharded
_OUT_B = P(MODEL_AXIS)                     # bias riding a sharded C_out


def _gate_out(axis_size: int, min_ch: int) -> ShapePredicate:
    return lambda s: (len(s) == 4 and s[3] >= min_ch
                      and s[3] % axis_size == 0)


def _gate_in(axis_size: int, min_ch: int) -> ShapePredicate:
    return lambda s: (len(s) == 4 and s[2] >= min_ch
                      and s[2] % axis_size == 0)


def _gate_bias(axis_size: int, min_ch: int) -> ShapePredicate:
    return lambda s: (len(s) == 1 and s[0] >= min_ch
                      and s[0] % axis_size == 0)


def _log2_odd(n: int) -> bool:
    # exact power of two with odd exponent — the PatchGAN chain parity key
    return n > 0 and (n & (n - 1)) == 0 and (n.bit_length() - 1) % 2 == 1


def make_unet_tp_rules(axis_size: int = 2, min_ch: int = 512) -> Tuple:
    """The U-Net generator's Megatron pairs as predicate rules: (down3 →
    down4) and the bottleneck (down5 → up5), kernels only (the U-Net down
    convs carry no bias — BatchNorm absorbs it). Width/divisibility gates
    mirror :func:`p2p_tpu.parallel.tp.tp_leaf_spec` exactly."""
    out, inn = _gate_out(axis_size, min_ch), _gate_in(axis_size, min_ch)
    return (
        (r"down3/kernel$", _OUT_K, out),
        (r"down4/kernel$", _IN_K, inn),
        (r"down5/kernel$", _OUT_K, out),
        (r"up5/kernel$", _IN_K, inn),
    )


def make_patchgan_tp_rules(axis_size: int = 2, min_ch: int = 512) -> Tuple:
    """The PatchGAN discriminator chains as predicate rules. The conv
    names differ per preset (``_PlainConv_k`` / ``SpectralConv_k``), so
    the rules key on the channel-doubling chain's log2-parity — the same
    shape law ``tp_leaf_spec`` applies: an odd-power C_in in-shards (with
    one psum), an odd-power C_out out-shards, gates replicate the rest.
    The bare in-parity rule (no gate) BLOCKS a gate-failed in-parity
    kernel from falling through to the out rule — precedence mirrors
    ``_tp_spec`` checking C_in first."""
    out, inn = _gate_out(axis_size, min_ch), _gate_in(axis_size, min_ch)
    bias = _gate_bias(axis_size, min_ch)
    return (
        (r"scale\d+/.*/kernel$", _IN_K,
         lambda s: len(s) == 4 and _log2_odd(s[2]) and inn(s)),
        (r"scale\d+/.*/kernel$", P(),
         lambda s: len(s) == 4 and _log2_odd(s[2])),
        (r"scale\d+/.*/kernel$", _OUT_K,
         lambda s: len(s) == 4 and _log2_odd(s[3]) and out(s)),
        (r"scale\d+/.*/bias$", _OUT_B,
         lambda s: len(s) == 1 and _log2_odd(s[0]) and bias(s)),
    )


def make_resnet_tp_rules(axis_size: int = 2, min_ch: int = 512) -> Tuple:
    """The ResNet-trunk Megatron pairs as predicate rules (ISSUE 13
    satellite — the item-3 worklist drain for the ResNet/pix2pixHD
    families): each residual block's conv pair (``ConvLayer_0`` C_out →
    ``ConvLayer_1`` C_in, one psum per block), the encoder's deepest
    transition (``ConvLayer_3`` → ``ConvLayer_4``) and the decoder's
    (``UpsampleConvLayer_0`` → ``UpsampleConvLayer_1``) — cityscapes at
    the generator root, pix2pixHD under its ``global`` subtree, the
    flagship ExpandNetwork via the ``ResidualBlock`` naming. Kernels
    only: these trunks run norm layers that absorb no bias and their
    convs carry none (a model that grows sharded-width biases shows up
    as a tp-diff gap, which is exactly the worklist's job). The
    ``(?:^|/)`` anchor keeps ``ConvLayer_3`` from matching inside
    ``UpsampleConvLayer_3``-style names."""
    out, inn = _gate_out(axis_size, min_ch), _gate_in(axis_size, min_ch)
    return (
        (r"Res(?:net|idual)Block_\d+/ConvLayer_0/Conv_0/kernel$",
         _OUT_K, out),
        (r"Res(?:net|idual)Block_\d+/ConvLayer_1/Conv_0/kernel$",
         _IN_K, inn),
        (r"(?:^|/)ConvLayer_3/Conv_0/kernel$", _OUT_K, out),
        (r"(?:^|/)ConvLayer_4/Conv_0/kernel$", _IN_K, inn),
        (r"(?:^|/)UpsampleConvLayer_0/Conv_0/kernel$", _OUT_K, out),
        (r"(?:^|/)UpsampleConvLayer_1/Conv_0/kernel$", _IN_K, inn),
    )


def make_window_transformer_rules() -> Tuple:
    """The leaves of a window-attention generator (models/swinir.py),
    REPLICATED over the ``model`` axis by name: LayerNorm's affine, the
    qkv / proj / fc1 / fc2 kernels and biases, the relative-position bias
    table. No Megatron pair is declared for them (a q k v split by heads
    with proj in-sharded, fc1 out / fc2 in, is not in the tree), so a
    ``model`` axis leaves these whole, by these rows and not by the
    catch-all."""
    return (
        (r"attn/relative_position_bias_table$", P()),
        (r"(?:attn/qkv|attn/proj|fc1|fc2)/(?:kernel|bias)$", P()),
        (r"(?:norm1|norm2|norm|patch_norm)/(?:scale|bias)$", P()),
    )


def make_ffc_rules() -> Tuple:
    """The leaves of a generator of fast Fourier convolutions (models/
    ffc.py), REPLICATED over the ``model`` axis by name: the k3 kernels
    between the local and the global branch, the spectral transform's
    three 1x1 kernels (the Fourier unit's among them), the branches'
    BatchNorm affines and the transposed convolutions. No Megatron pair
    is declared for them (a split of the global branch's channels would
    cut through the transforms' real / imaginary interleave), so a
    ``model`` axis leaves these whole, by these rows and not by the
    catch-all."""
    return (
        (r"block_\d+/conv[12]/(?:l2l|l2g|g2l)/kernel$", P()),
        (r"g2g/(?:conv1|conv2|fu/conv)/kernel$", P()),
        (r"(?:bn_l|bn_g|g2g/bn1|fu/bn)/BatchNorm_0/(?:scale|bias)$", P()),
        (r"(?:^|/)up_\d+/kernel$", P()),
    )


def tp_equivalence_rules(cfg, axis_size: int = 2,
                         min_ch: int = 512) -> Optional[Rules]:
    """The declarative table reproducing ``tp_leaf_spec`` for ``cfg``'s
    model family, or None for an unknown family. ALL preset families are
    drained (zero tp-diff gaps, pinned + CI-grepped): the facades family
    (U-Net G + PatchGAN D), and — ISSUE 13 — the ResNet/pix2pixHD/Expand
    trunks plus their multiscale PatchGAN discriminators.

    The trunk rules join the table only when the family's widest trunk
    conv can clear the ``min_ch`` floor (pix2pixHD's global trunk tops
    out at ``16·ngf``, the plain ResNet/Expand trunks at ``4·ngf``) —
    below it every trunk gate is provably never-true and the rules would
    only audit as dead. The audit + tp-diff pins in tests/test_analysis
    verify the width law against the real preset states."""
    gen = cfg.model.generator
    if gen == "unet":
        return (make_unet_tp_rules(axis_size, min_ch)
                + make_patchgan_tp_rules(axis_size, min_ch)
                + ((r".*", P()),))
    if gen in ("resnet", "pix2pixhd", "expand"):
        trunk_top = cfg.model.ngf * (16 if gen == "pix2pixhd" else 4)
        trunk = (make_resnet_tp_rules(axis_size, min_ch)
                 if trunk_top >= min_ch else ())
        return (trunk + make_patchgan_tp_rules(axis_size, min_ch)
                + ((r".*", P()),))
    return None


# ---------------------------------------------------------------------------
# The ONE partitioner (ISSUE 15): TP union + FSDP tables + composition.
# ---------------------------------------------------------------------------


def make_tp_rules(axis_size: int = 2, min_ch: int = 512) -> Tuple:
    """The family-agnostic Megatron TP table: the UNION of every drained
    family's predicate rules (the generator naming families are disjoint
    — ``down3`` only exists in the U-Net, ``ConvLayer``/``ResnetBlock``
    only in the ResNet trunks, ``scale\\d+`` only in the PatchGAN Ds — so
    the union reproduces the retired hand-built assignment on ANY state
    tree the repo builds; the per-preset zero-gap pins in
    tests/test_analysis are the proof). No catch-all: this composes
    inside :func:`trainstate_rules`."""
    return (make_unet_tp_rules(axis_size, min_ch)
            + make_resnet_tp_rules(axis_size, min_ch)
            + make_patchgan_tp_rules(axis_size, min_ch)
            + make_window_transformer_rules()
            + make_ffc_rules())


#: the TrainState fields the FSDP table shards (ZeRO-1: pure per-device
#: replicated memory today — exactly what memory_budget.json quantifies).
#: ``opt_s``/``pp_stages`` are deliberately absent: the PP stage stack
#: shards over the ``pipe`` axis through parallel/pp.py's own machinery,
#: and composing fsdp×pipe layouts is not expressible until a real mesh
#: needs it.
FSDP_STATE_RE = r"^(?:opt_[gdc]|ema_g)(?:/|$)"
FSDP_PARAMS_RE = r"^params_[gdc](?:/|$)"


def fsdp_shard_spec(axis_size: int, axis: str = FSDP_AXIS) -> SpecBuilder:
    """Spec builder: partition the TRAILING divisible dim of a leaf over
    ``axis`` (C_out on a conv kernel, the only dim of a bias/scale),
    replicate when no dim divides — the ZeRO floor that keeps odd-width
    leaves (a 3-channel image-head kernel's C_out) legal without
    per-leaf wiring. Trailing-first keeps the partitioned dim the
    channel dim wherever one exists, mirroring the TP convention."""
    n = int(axis_size)

    def spec(shape: Tuple[int, ...]) -> P:
        for d in range(len(shape) - 1, -1, -1):
            if shape[d] >= n and shape[d] % n == 0:
                entries = [None] * len(shape)
                entries[d] = axis
                return P(*entries)
        return P()

    return spec


def make_fsdp_rules(axis_size: int, fsdp_params: bool = False) -> Tuple:
    """ZeRO-style state sharding over the ``fsdp`` mesh axis as TWO
    spec-builder rules: Adam moments + EMA always (ZeRO-1 — the state
    that is pure replicated HBM today), ``params_*`` behind the
    ``fsdp_params`` knob (ZeRO-3-ish; GSPMD inserts the gather-on-use
    from the pjit in/out shardings). Gradient reduce-scatter (ZeRO-2)
    falls out for free: XLA sees sharded moment outputs and scatters the
    grads feeding them instead of all-reducing."""
    builder = fsdp_shard_spec(axis_size)
    rules: Tuple = ((FSDP_STATE_RE, builder),)
    if fsdp_params:
        rules = ((FSDP_PARAMS_RE, builder),) + rules
    return rules


def trainstate_rules(axis_sizes: Dict[str, int], tp_min_ch: int = 512,
                     fsdp_params: bool = False) -> Rules:
    """THE rule table for a mesh topology (axis-name → size dict; no
    devices needed, so hypothetical meshes audit/budget on one CPU):
    TP pair rules first when the ``model`` axis is real (a TP-claimed
    moment mirrors its param's channel shard), then the FSDP state rules
    when the ``fsdp`` axis is real, then the replicate catch-all."""
    rules: Tuple = ()
    model = int(axis_sizes.get(MODEL_AXIS, 1) or 1)
    if model > 1:
        rules += make_tp_rules(model, tp_min_ch)
    fsdp = int(axis_sizes.get(FSDP_AXIS, 1) or 1)
    if fsdp > 1:
        rules += make_fsdp_rules(fsdp, fsdp_params=fsdp_params)
    return rules + ((r".*", P()),)
