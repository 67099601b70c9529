"""comm.collective_ms_per_step (ms; layer: parallel; moves train_img_per_s).

Device time a chip spends in collective ops (all-reduce, collective-permute, all-gather, reduce-scatter, all-to-all and their -start / -done halves) per train step of the traced window, mean over the chips: what the step pays for its communication (benchmark/comm_time.py).
"""

META = {"name": "comm.collective_ms_per_step", "unit": "ms",
        "layer": "parallel", "moves": "train_img_per_s"}


def read(run):
    from benchmark import comm_time

    return comm_time.ms_per_step(run)
