"""loss.r1_ms_per_step (ms; layer: models; moves train_img_per_s).

Device time a train step spends under the R1 gradient penalty (scope ``d_r1`` of ``p2p_tpu/train/step.py``: D's real call, which is the penalty's forward, the backward to the image, and the backward of both with respect to D's parameters, the step's one second-order pass), from the join the driver ``train_inpaint`` keeps in ``run["inpaint_scopes"]``. The scope lies inside the step's ``D_real``, so this time is a part of what a join by the step's own scopes counts as D's.
"""

META = {"name": "loss.r1_ms_per_step", "unit": "ms", "layer": "models",
        "moves": "train_img_per_s"}


def read(run):
    from benchmark import harness

    return harness.load_by_path(
        "layer_metrics", "model.ffc_spectral_ms_per_step").scope_ms(
            run, "inpaint_scopes", ("d_r1",))
