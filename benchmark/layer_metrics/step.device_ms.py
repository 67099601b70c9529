"""step.device_ms (ms; layer: step program; moves train_img_per_s).

Device busy time per train step: union of device op intervals over the steps of the traced window.
"""

META = {"name": "step.device_ms", "unit": "ms", "layer": "step program",
        "moves": "train_img_per_s"}


def read(run):
    tr, n = run.get("trace"), run.get("steps")
    return 1000.0 * tr["busy_s"] / n if tr and n else None
