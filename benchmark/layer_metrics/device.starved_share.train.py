"""device.starved_share.train (%; layer: device; moves train_img_per_s).

Idle time the HOST caused in steady state: the epoch records' ``device_starved_s`` (the excess over the epoch's median of every completion-to-completion interval in which the Trainer's delayed read found the device already done) summed over the window's epochs, over those epochs' seconds. Epoch starts and drains are not in it (``loop.epoch_start_ms``, ``loop.first_step_late_ms``).
"""

META = {"name": "device.starved_share.train", "unit": "%", "layer": "device",
        "moves": "train_img_per_s"}


def read(run):
    from benchmark import epoch_records

    epochs = [e for e in epoch_records.window_epochs(run) or ()
              if "device_starved_s" in e]
    # the ring's record holds the epoch's seconds as dur_s, the JSONL's as sec
    seconds = sum(e.get("dur_s", e.get("sec", 0.0)) for e in epochs)
    if not seconds:
        return None
    return 100.0 * sum(e["device_starved_s"] for e in epochs) / seconds
