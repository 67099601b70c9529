"""model.ffc_spectral_ms_per_step (ms; layer: models; moves train_img_per_s).

Device time a train step spends in the spectral transforms of the fast Fourier convolutions (scope ``ffc_spectral`` of ``p2p_tpu/models/ffc.py``: the 1x1 convolution down to half the global channels with its BatchNorm and ReLU, the Fourier unit — rfft2, the 1x1 convolution on real / imaginary channels with its BatchNorm and ReLU, irfft2 — and the 1x1 convolution back, forward and backward; 36 of them in the published Big LaMa): the ops named under the scope in the join of the traced window with the compiled step's text (``benchmark/scope_time.by_scope``), which the driver ``train_inpaint`` keeps in ``run["inpaint_scopes"]``. An op is counted under the FIRST of the scopes ``ffc_local`` / ``ffc_spectral`` / ``d_r1`` / ``loss_hrf`` in its name, so a fusion XLA names after one of its instructions counts whole under that instruction's scope. A program without the scope, or a driver without the join, leaves nothing to read.
"""

META = {"name": "model.ffc_spectral_ms_per_step", "unit": "ms",
        "layer": "models", "moves": "train_img_per_s"}


def scope_ms(run, key, scopes):
    """Device ms a step in the ops the join ``run[key]`` put under
    ``scopes``; None where the run holds no such join or the join none of
    them."""
    scoped = run.get(key)
    if not scoped or not scoped.get("executions"):
        return None
    seconds = sum(scoped["scope_s"].get(s, 0.0) for s in scopes)
    return 1000.0 * seconds / scoped["executions"] if seconds else None


def read(run):
    return scope_ms(run, "inpaint_scopes", ("ffc_spectral",))
