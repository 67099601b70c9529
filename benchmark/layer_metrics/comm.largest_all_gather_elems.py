"""comm.largest_all_gather_elems (elems; layer: parallel; moves train_img_per_s).

The largest all-gather OR all-to-all of the compiled sharded train step, in elements: the program's own gauge ``step_largest_all_gather_elements``, set by the Trainer from the compiled text when a sharded step compiles (the larger of the two: GSPMD undid the H shard around the k7 reflect pads with all-to-alls, which a gather bound alone passes). An activation re-sharded along H (a shard silently undone) shows here as millions; a healthy step gathers only small tiles or nothing.
"""

META = {"name": "comm.largest_all_gather_elems", "unit": "elems",
        "layer": "parallel", "moves": "train_img_per_s"}

GAUGE = "step_largest_all_gather_elements"


def read(run):
    if "steps" not in run:
        return None
    from benchmark import epoch_records

    # a reader is handed ``run`` only; the gauge lives in the registry of
    # the Trainer that is alive in this process (as the epoch records do)
    trainer = epoch_records.live_trainer()
    gauge = None if trainer is None else trainer.obs.snapshot().get(GAUGE)
    return None if gauge is None else gauge["value"]
