"""device.idle_share.train (%; layer: device; moves train_img_per_s).

1 - device busy time / traced window, training cells.
"""

META = {"name": "device.idle_share.train", "unit": "%", "layer": "device",
        "moves": "train_img_per_s"}


def read(run):
    tr = run.get("trace")
    return 100.0 * tr["idle_share"] if tr and "steps" in run else None
