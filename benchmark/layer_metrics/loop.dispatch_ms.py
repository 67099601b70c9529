"""loop.dispatch_ms (ms; layer: trainer loop; moves train_img_per_s).

Mean host time per train-step dispatch over the window (the Trainer's dispatch_secs histogram).
"""

META = {"name": "loop.dispatch_ms", "unit": "ms", "layer": "trainer loop",
        "moves": "train_img_per_s"}


def read(run):
    n = run.get("dispatches")
    return 1000.0 * run["dispatch_s"] / n if n else None
