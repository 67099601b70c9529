"""loop.first_step_late_ms (ms; layer: trainer loop; moves train_img_per_s).

Mean over the window's epochs of the epoch records' ``first_step_late_s``: the first dispatch's start to its completion, less the epoch's median step interval, floored at 0. The launch and first-transfer delay of an epoch's first step, which no span of the program covers: the part of ``device.idle_share.train`` that lies under ``step_bookkeeping`` at step 1.
"""

META = {"name": "loop.first_step_late_ms", "unit": "ms",
        "layer": "trainer loop", "moves": "train_img_per_s"}


def read(run):
    from benchmark import epoch_records

    late = [e["first_step_late_s"]
            for e in epoch_records.window_epochs(run) or ()
            if "first_step_late_s" in e]
    return 1000.0 * sum(late) / len(late) if late else None
