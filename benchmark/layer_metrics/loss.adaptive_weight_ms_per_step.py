"""loss.adaptive_weight_ms_per_step (ms; layer: models; moves train_img_per_s).

Device time a train step spends in the adaptive adversarial weight (scope ``loss_adaptive``: one weight-gradient convolution of the generator's last layer for the two cotangents side by side, their norms, and the scaling of the GAN term's cotangent): the ops named under the scope in the join of the traced window with the compiled step's text (``benchmark/scope_time.by_scope``), which the driver ``train_vq`` keeps in ``run["vq_scopes"]``. A program without the scope, or a driver without the join, leaves nothing to read.
"""

META = {"name": "loss.adaptive_weight_ms_per_step", "unit": "ms", "layer": "models",
        "moves": "train_img_per_s"}


def read(run):
    from benchmark import harness

    return harness.load_by_path(
        "layer_metrics", "model.gn_swish_ms_per_step").scope_ms(
            run, ("loss_adaptive",))
