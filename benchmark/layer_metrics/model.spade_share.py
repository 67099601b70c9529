"""model.spade_share (%; layer: models; moves train_img_per_s).

``model.spade_ms_per_step`` over ``step.device_ms``: the share of a train step's device time spent in ops that do work of the scope ``spade`` (an upper bound where XLA fused the scope's passes into a convolution outside it: see that reader). It says whether the mechanism does most of the step's work, as the configuration's arithmetic predicts (~70% of the generator's multiply-adds), or does not.
"""

META = {"name": "model.spade_share", "unit": "%", "layer": "models",
        "moves": "train_img_per_s"}


def read(run):
    from benchmark import harness

    spade = harness.load_by_path(
        "layer_metrics", "model.spade_ms_per_step").read(run)
    step = harness.load_by_path("layer_metrics", "step.device_ms").read(run)
    return 100.0 * spade / step if spade and step else None
