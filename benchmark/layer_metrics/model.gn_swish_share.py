"""model.gn_swish_share (%; layer: models; moves train_img_per_s).

``model.gn_swish_ms_per_step`` over ``step.device_ms``: the share of a train step's device time spent in the GroupNorm + swish passes outside any convolution (see that reader: a lower bound). It says how much of the step is memory-bound normalisation beside MXU-bound convolutions, which is what a fused GroupNorm + swish kernel would go after.
"""

META = {"name": "model.gn_swish_share", "unit": "%", "layer": "models",
        "moves": "train_img_per_s"}


def read(run):
    from benchmark import harness

    gn = harness.load_by_path(
        "layer_metrics", "model.gn_swish_ms_per_step").read(run)
    step = harness.load_by_path("layer_metrics", "step.device_ms").read(run)
    return 100.0 * gn / step if gn and step else None
