"""loop.epoch_start_ms (ms; layer: trainer loop; moves train_img_per_s).

Mean over the window's epochs of the time from the entry of ``Trainer.train_epoch()`` to the start of its first ``train_dispatch`` (the epoch records' ``epoch_start_s``): loader set-up, the prefetch fill, the first batches' stack and H2D.
"""

META = {"name": "loop.epoch_start_ms", "unit": "ms", "layer": "trainer loop",
        "moves": "train_img_per_s"}


def read(run):
    from benchmark import epoch_records

    epochs = epoch_records.window_epochs(run)
    starts = [e["epoch_start_s"] for e in epochs or ()
              if "epoch_start_s" in e]
    return 1000.0 * sum(starts) / len(starts) if starts else None
