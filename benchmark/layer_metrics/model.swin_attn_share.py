"""model.swin_attn_share (%; layer: models; moves train_img_per_s).

``model.swin_attn_ms_per_step`` over ``step.device_ms``: the share of a train step's device time spent in window attention. Its two products are a ninth of the step's multiply-adds at shapes (64 tokens, heads of 30) that fill no MXU tile, so this share against that arithmetic says what a window-attention kernel, or a layout padded to the tiles, could win.
"""

META = {"name": "model.swin_attn_share", "unit": "%", "layer": "models",
        "moves": "train_img_per_s"}


def read(run):
    from benchmark import harness

    attn = harness.load_by_path(
        "layer_metrics", "model.swin_attn_ms_per_step").read(run)
    step = harness.load_by_path("layer_metrics", "step.device_ms").read(run)
    return 100.0 * attn / step if attn and step else None
