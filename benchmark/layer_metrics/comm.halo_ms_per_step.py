"""comm.halo_ms_per_step (ms; layer: parallel; moves train_img_per_s).

The collective-permute part of comm.collective_ms_per_step: the halo exchanges of the H-sharded convolutions and poolings, per train step, mean over the chips.
"""

META = {"name": "comm.halo_ms_per_step", "unit": "ms", "layer": "parallel",
        "moves": "train_img_per_s"}


def read(run):
    from benchmark import comm_time

    return comm_time.ms_per_step(run, ("collective-permute",))
