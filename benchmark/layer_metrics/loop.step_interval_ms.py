"""loop.step_interval_ms (ms; layer: trainer loop; moves train_img_per_s).

The program's own reading of a step's time on the device: the steps-weighted mean over the window's epochs of the epoch records' ``step_interval_median_s``, the median interval from one step's completion to the next as the Trainer's delayed read of each dispatch's metrics stamps it. Equal to ``step.device_ms`` while the device is never starved.
"""

META = {"name": "loop.step_interval_ms", "unit": "ms", "layer": "trainer loop",
        "moves": "train_img_per_s"}


def read(run):
    from benchmark import epoch_records

    epochs = [e for e in epoch_records.window_epochs(run) or ()
              if "step_interval_median_s" in e]
    steps = sum(e["steps"] for e in epochs)
    if not steps:
        return None
    return 1000.0 * sum(e["steps"] * e["step_interval_median_s"]
                        for e in epochs) / steps
