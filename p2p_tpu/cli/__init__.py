"""Command-line drivers.

- ``python -m p2p_tpu.cli.train`` — training (reference train.py:133-157
  flag parity + TPU mesh/preset knobs).
- ``python -m p2p_tpu.cli.infer`` — batched inference from a checkpoint
  through the serving engine (replaces reference test.py, which could not
  load train.py's checkpoints — SURVEY Q5).
- ``python -m p2p_tpu.cli.serve`` — micro-batching serving frontend
  (directory-driven requests → bucket-batched predictions; docs/SERVING.md).
- ``python -m p2p_tpu.cli.generate_dataset`` — offline paired-dataset
  generation (reference generate_dataset.py:150-165 flag parity).
- ``python -m p2p_tpu.cli.lint`` — static-analysis gate over the repo
  (p2p_tpu.analysis: sharding audit, jaxpr/HLO lint, AST rules;
  docs/STATIC_ANALYSIS.md). ``--strict`` is the CI mode.
"""

import dataclasses


def with_label_classes(model, classes):
    """``--label_classes`` on a label-map preset's ``ModelConfig`` (None =
    the flag was not given): the conditioning map's channel count follows
    (classes + the edge channel), as ``input_nc`` states it."""
    if classes is None:
        return model
    return dataclasses.replace(
        model, label_classes=classes,
        input_nc=classes + int(model.label_edge))


def add_vq_flags(parser) -> None:
    """The sizes of the ``vqgan`` generator a run may shrink (train and
    infer name the same ones; the preset's are the published model's)."""
    parser.add_argument("--vq_ch_mult", type=str, default=None,
                        help="vqgan presets: channel multipliers a level, "
                             "e.g. 1,1,2,2,4")
    parser.add_argument("--vq_res_blocks", type=int, default=None)
    parser.add_argument("--vq_codes", type=int, default=None)
    parser.add_argument("--vq_embed_dim", type=int, default=None,
                        help="vqgan presets: the codebook's width, and "
                             "the latent's channels with it")


def with_vq_sizes(model, args):
    """The ``add_vq_flags`` values on a ``ModelConfig`` (flags not given
    leave the preset's)."""
    mult = (tuple(int(m) for m in args.vq_ch_mult.split(","))
            if args.vq_ch_mult else None)
    return apply_overrides(
        model, vq_ch_mult=mult, vq_res_blocks=args.vq_res_blocks,
        vq_codes=args.vq_codes, vq_embed_dim=args.vq_embed_dim)


def apply_overrides(obj, **kw):
    """dataclasses.replace with None-valued (unset flag) entries dropped —
    the shared preset-override rule for every CLI."""
    kw = {k: v for k, v in kw.items() if v is not None}
    return dataclasses.replace(obj, **kw) if kw else obj
