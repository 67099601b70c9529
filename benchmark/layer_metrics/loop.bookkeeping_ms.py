"""loop.bookkeeping_ms (ms; layer: trainer loop; moves train_img_per_s).

Mean host time a step of the window spent between its dispatch's return and the next wait for a batch (``step_bookkeeping``): the health queue's read of the PREVIOUS step's metrics (a wait on the device while the host runs ahead), masking, the loss sums, the log record, the preempt poll.
"""

META = {"name": "loop.bookkeeping_ms", "unit": "ms", "layer": "trainer loop",
        "moves": "train_img_per_s"}


def read(run):
    from benchmark import epoch_records

    epochs = [e for e in epoch_records.window_epochs(run) or ()
              if "step_bookkeeping_s" in e]
    steps = sum(e["steps"] for e in epochs)
    if not steps:
        return None
    return 1000.0 * sum(e["step_bookkeeping_s"] for e in epochs) / steps
