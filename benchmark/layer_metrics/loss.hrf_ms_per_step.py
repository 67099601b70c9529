"""loss.hrf_ms_per_step (ms; layer: models; moves train_img_per_s).

Device time a train step spends in the high-receptive-field perceptual term (scope ``loss_hrf`` of ``p2p_tpu/losses/perceptual.py``: two forwards of the dilated ResNet50, on the generated and on the real image, the squared differences of its four stages and one backward to the generated image), from the join the driver ``train_inpaint`` keeps in ``run["inpaint_scopes"]``.
"""

META = {"name": "loss.hrf_ms_per_step", "unit": "ms", "layer": "models",
        "moves": "train_img_per_s"}


def read(run):
    from benchmark import harness

    return harness.load_by_path(
        "layer_metrics", "model.ffc_spectral_ms_per_step").scope_ms(
            run, "inpaint_scopes", ("loss_hrf",))
