"""loop.host_ms_per_step (ms; layer: trainer loop; moves train_img_per_s).

Mean host time a step of the window that was the host's OWN work: the epoch records' ``host_s`` (``feed_next`` without each epoch's first, ``train_dispatch`` and ``step_bookkeeping`` less the ``device_wait`` inside it) over the window's steps. What ``loop.bookkeeping_ms`` was taken for: how far the host is from setting the pace.
"""

META = {"name": "loop.host_ms_per_step", "unit": "ms", "layer": "trainer loop",
        "moves": "train_img_per_s"}


def read(run):
    from benchmark import epoch_records

    epochs = [e for e in epoch_records.window_epochs(run) or ()
              if "host_s" in e]
    steps = sum(e["steps"] for e in epochs)
    if not steps:
        return None
    return 1000.0 * sum(e["host_s"] for e in epochs) / steps
