"""model.ffc_spectral_share (%; layer: models; moves train_img_per_s).

``model.ffc_spectral_ms_per_step`` over ``step.device_ms``: the share of a train step's device time spent in the spectral transforms. Their convolutions are a seventh of a block's multiply-adds (0.22 of 1.26 MMAC a position) and their transforms none, so this share against that arithmetic says what the float32 transforms, their layout changes and casts cost beside the matrix products.
"""

META = {"name": "model.ffc_spectral_share", "unit": "%", "layer": "models",
        "moves": "train_img_per_s"}


def read(run):
    from benchmark import harness

    spectral = harness.load_by_path(
        "layer_metrics", "model.ffc_spectral_ms_per_step").read(run)
    step = harness.load_by_path("layer_metrics", "step.device_ms").read(run)
    return 100.0 * spectral / step if spectral and step else None
