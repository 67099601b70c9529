"""model.ffc_fft_hbm_share (%; layer: models; moves train_img_per_s).

The Fourier units' transforms against the chip's memory bandwidth: least time / traced time. Least time = the bytes of every transform's operand and result, each counted ONCE from the shapes (:func:`fft_bytes_per_step`; the driver hands the shapes in ``run["ffc_shapes"]``), over the chip's peak HBM bytes per second (``benchmark/peaks.json``); traced time = ``model.ffc_fft_ms_per_step`` (the scope ``ffc_fft``: the transforms with the casts and layout changes around them). The bytes are a LOWER bound of the traffic (whatever the transforms stage, transpose or cast between their passes is not counted), so the share cannot pass 100% on a sound timing; a low share says the transforms are bound by something other than reading and writing their tensors once.
"""

META = {"name": "model.ffc_fft_hbm_share", "unit": "%", "layer": "models",
        "moves": "train_img_per_s"}


def fft_bytes_per_step(units: int, n: int, h: int, w: int, c: int) -> int:
    """Operand plus result bytes of the transforms of one train step:
    ``units`` Fourier units, each one rfft2 (float32 ``[n, h, w, c]`` in,
    complex64 ``[n, h, w/2+1, c]`` out) and one irfft2 (the reverse) in
    the forward pass and the transposes of both in the backward."""
    real = 4 * n * h * w * c
    spectrum = 8 * n * h * (w // 2 + 1) * c
    return units * 4 * (real + spectrum)


def read(run):
    from benchmark import harness

    shapes = run.get("ffc_shapes")
    fft_ms = harness.load_by_path(
        "layer_metrics", "model.ffc_fft_ms_per_step").read(run)
    if not shapes or not fft_ms:
        return None
    peak = harness.load_peaks()[run["device_kind"]]["hbm_bytes_per_s"]
    least_s = fft_bytes_per_step(**shapes) / peak
    return 100.0 * least_s / (fft_ms / 1000.0)
