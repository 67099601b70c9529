"""model.d_unet_ms_per_step (ms; layer: models; moves train_img_per_s).

Device time a train step spends in the U-Net discriminator (the step's scopes ``D_fake`` and ``D_real``: both forwards at the full 256x256 extent, D's own backward and the pull of G's adversarial term through the fake call's residuals): the ops under those two scopes in the join of the traced window with the compiled step's text by the step's own scopes (``benchmark/scope_time.by_scope``), which the driver ``train_sr`` keeps in ``run["sr_nets"]``. A driver without the join leaves nothing to read.
"""

META = {"name": "model.d_unet_ms_per_step", "unit": "ms", "layer": "models",
        "moves": "train_img_per_s"}


def read(run):
    from benchmark import harness

    return harness.load_by_path(
        "layer_metrics", "model.swin_attn_ms_per_step").scope_ms(
            run, "sr_nets", ("D_fake", "D_real"))
