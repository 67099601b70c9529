"""model.ffc_local_ms_per_step (ms; layer: models; moves train_img_per_s).

Device time a train step spends in the k3 convolutions of the residual blocks' fast Fourier convolutions (scope ``ffc_local`` of ``p2p_tpu/models/ffc.py``: local to local, global to local, local to global on reflect-padded inputs, forward and backward: 1.03 of a block's 1.26 MMAC a position), from the join the driver ``train_inpaint`` keeps in ``run["inpaint_scopes"]``.
"""

META = {"name": "model.ffc_local_ms_per_step", "unit": "ms",
        "layer": "models", "moves": "train_img_per_s"}


def read(run):
    from benchmark import harness

    return harness.load_by_path(
        "layer_metrics", "model.ffc_spectral_ms_per_step").scope_ms(
            run, "inpaint_scopes", ("ffc_local",))
