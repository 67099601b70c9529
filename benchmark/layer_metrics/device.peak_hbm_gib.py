"""device.peak_hbm_gib (GiB; layer: device; moves train_img_per_s).

Peak device memory of the fullest chip after the window: peak_bytes_in_use + peak_bytes_reserved (harness.peak_memory_bytes).
"""

META = {"name": "device.peak_hbm_gib", "unit": "GiB", "layer": "device",
        "moves": "train_img_per_s"}


def read(run):
    p = run.get("peak_bytes")
    return p / 2 ** 30 if p and "steps" in run else None
