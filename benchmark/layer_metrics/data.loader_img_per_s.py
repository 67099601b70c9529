"""data.loader_img_per_s (img/s; layer: data; moves train_img_per_s).

The cell's own loader iterated alone for ~2 s (memo filled, nothing sent to the device).
"""

META = {"name": "data.loader_img_per_s", "unit": "img/s", "layer": "data",
        "moves": "train_img_per_s"}


def read(run):
    return run.get("loader_img_per_s")
