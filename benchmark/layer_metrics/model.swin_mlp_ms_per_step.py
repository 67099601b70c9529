"""model.swin_mlp_ms_per_step (ms; layer: models; moves train_img_per_s).

Device time a train step spends in the Swin layers' MLPs (scope ``swin_mlp``: fc1 180 -> 360, the erf GELU, fc2 360 -> 180, forward and backward; 36 layers): the ops named under the scope in the join the driver ``train_sr`` keeps in ``run["sr_scopes"]`` (see ``model.swin_attn_ms_per_step``). A program without the scope, or a driver without the join, leaves nothing to read.
"""

META = {"name": "model.swin_mlp_ms_per_step", "unit": "ms",
        "layer": "models", "moves": "train_img_per_s"}


def read(run):
    from benchmark import harness

    return harness.load_by_path(
        "layer_metrics", "model.swin_attn_ms_per_step").scope_ms(
            run, "sr_scopes", ("swin_mlp",))
