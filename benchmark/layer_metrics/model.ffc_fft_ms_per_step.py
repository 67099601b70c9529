"""model.ffc_fft_ms_per_step (ms; layer: models; moves train_img_per_s).

Device time a train step spends in the Fourier units' transforms (scope ``ffc_fft`` of ``p2p_tpu/models/ffc.py``: rfft2 and irfft2 over H and W in float32 with the casts, the real / imaginary interleave and the layout changes around them, forward and backward: four transforms a unit a step, 144 in the published Big LaMa). The scope lies INSIDE ``ffc_spectral``, so it is joined alone: the ops whose name holds ``ffc_fft`` in the join the driver ``train_inpaint`` keeps in ``run["inpaint_fft"]``; it is a part of ``model.ffc_spectral_ms_per_step``, not beside it.
"""

META = {"name": "model.ffc_fft_ms_per_step", "unit": "ms", "layer": "models",
        "moves": "train_img_per_s"}


def read(run):
    from benchmark import harness

    return harness.load_by_path(
        "layer_metrics", "model.ffc_spectral_ms_per_step").scope_ms(
            run, "inpaint_fft", ("ffc_fft",))
