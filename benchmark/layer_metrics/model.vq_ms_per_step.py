"""model.vq_ms_per_step (ms; layer: models; moves train_img_per_s).

Device time a train step spends in the learned quantizer (scope ``vq``: the two 1x1 convolutions around it, the float32 distance product of the latent's rows with the 16384 codes, the argmin, the gather and, in the backward, the codebook's scatter-add): the ops named under the scope in the join of the traced window with the compiled step's text (``benchmark/scope_time.by_scope``), which the driver ``train_vq`` keeps in ``run["vq_scopes"]``. A program without the scope, or a driver without the join, leaves nothing to read.
"""

META = {"name": "model.vq_ms_per_step", "unit": "ms", "layer": "models",
        "moves": "train_img_per_s"}


def read(run):
    from benchmark import harness

    return harness.load_by_path(
        "layer_metrics", "model.gn_swish_ms_per_step").scope_ms(
            run, ("vq",))
