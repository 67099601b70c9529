"""data.feed_wait_ms (ms; layer: data; moves train_img_per_s).

Mean time a step of the window waited for its device batch (``feed_next``: the loop's ``next()`` on the prefetching feed), each epoch's first wait left out: it holds the prefetch fill and is in ``loop.epoch_start_ms``.
"""

META = {"name": "data.feed_wait_ms", "unit": "ms", "layer": "data",
        "moves": "train_img_per_s"}


def read(run):
    from benchmark import epoch_records

    epochs = [e for e in epoch_records.window_epochs(run) or ()
              if "feed_next_s" in e]
    waits = sum(e["steps"] - 1 for e in epochs)
    if waits <= 0:
        return None
    return 1000.0 * sum(e["feed_next_s"] - e["first_feed_next_s"]
                        for e in epochs) / waits
