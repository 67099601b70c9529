"""``tools/control.py --kind train`` for a cell of any name, on ONE chip:
the generator path (sound, then the program's own int8 path in its
place) on the first seeded batch at the cell's size against the plain
reference, for the limits of a cell whose traffic is not called
``train`` and whose step needs more chips than the control has.

    chiprun -- python benchmark/tools/control_cell.py \
        --workload pix2pixhd_2048x1024.train_spatial4 --seeds 3

The generator path is jitted on the state ``create_train_state`` makes of
the seed, with no Trainer and no mesh, so a four-chip cell's generator
numbers can be read on one chip (its forward fits one: no gradients, no
optimizer state). The whole step's int8 control needs the cell's own
chips and ``tools/control.py``'s ``steps`` kind.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first_seed", type=int, default=2147480000)
    ap.add_argument("--bench_file", default=None)
    ap.add_argument("--allow_cpu", action="store_true")
    args = ap.parse_args(argv)

    from benchmark import harness
    from benchmark.tools import control

    rows = []
    for k in range(args.seeds):
        seed = args.first_seed + 7919 * k
        cell = harness.load_cell(args.workload, seed, 0.0, False,
                                 time.perf_counter(), args.bench_file,
                                 require_tpu=not args.allow_cpu)
        if k == 0:
            harness.prepare_jax_env(cell)
            print(json.dumps({"device": harness.device_info(
                1, not args.allow_cpu)}), flush=True)
        reference = harness.load_by_path("reference",
                                         cell.config["reference"])
        row = dict(control.train_row(cell, reference), seed=seed)
        rows.append(row)
        print(json.dumps(row), flush=True)
    keys = sorted({k for r in rows for k in r if k != "seed"})
    print(json.dumps({"summary": {k: {"min": min(r[k] for r in rows),
                                      "max": max(r[k] for r in rows)}
                                  for k in keys}, "seeds": len(rows)}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
