"""model.attn_ms_per_step (ms; layer: models; moves train_img_per_s).

Device time a train step spends in the attention blocks (scope ``attn``: their GroupNorm, the four 1x1 convolutions, the two products over the 256 positions and the softmax, forward and backward; 7 blocks in the published VQGAN): the ops named under the scope in the join of the traced window with the compiled step's text (``benchmark/scope_time.by_scope``), which the driver ``train_vq`` keeps in ``run["vq_scopes"]``. A program without the scope, or a driver without the join, leaves nothing to read.
"""

META = {"name": "model.attn_ms_per_step", "unit": "ms", "layer": "models",
        "moves": "train_img_per_s"}


def read(run):
    from benchmark import harness

    return harness.load_by_path(
        "layer_metrics", "model.gn_swish_ms_per_step").scope_ms(
            run, ("attn",))
