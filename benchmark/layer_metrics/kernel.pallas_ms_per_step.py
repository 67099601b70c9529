"""kernel.pallas_ms_per_step (ms; layer: kernels; moves train_img_per_s).

Summed device time of the tpu_custom_call (Pallas) events per train step.
"""

META = {"name": "kernel.pallas_ms_per_step", "unit": "ms", "layer": "kernels",
        "moves": "train_img_per_s"}


def read(run):
    tr, n = run.get("trace"), run.get("steps")
    if not tr or not n or not tr["n_kernel_events"]:
        return None
    return 1000.0 * tr["kernel_s"] / n
