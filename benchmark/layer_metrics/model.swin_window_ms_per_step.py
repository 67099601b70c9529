"""model.swin_window_ms_per_step (ms; layer: models; moves train_img_per_s).

Device time a train step spends in the window split's LAYOUT ops alone (scope ``swin_window``: the cyclic roll, the partition into 8x8 windows, its reverse and the roll back, forward and backward): the ops named under the scope in the join the driver ``train_sr`` keeps in ``run["sr_scopes"]`` (see ``model.swin_attn_ms_per_step``). Copies XLA fused into a neighbour named after another scope are counted there, so this is what the split costs in ops of its own; the driver prints how many such ops the compiled step holds (``swin_window_ops``). A program without the scope, or a driver without the join, leaves nothing to read.
"""

META = {"name": "model.swin_window_ms_per_step", "unit": "ms",
        "layer": "models", "moves": "train_img_per_s"}


def read(run):
    from benchmark import harness

    return harness.load_by_path(
        "layer_metrics", "model.swin_attn_ms_per_step").scope_ms(
            run, "sr_scopes", ("swin_window",))
