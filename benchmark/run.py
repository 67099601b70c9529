"""The benchmark's command.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run = one new process tree: set-up (inputs and weights from the seed,
compile or cache load, warm-up of the cell's own shapes), a measured
window of ``--seconds``, the output check, and ONE last line of JSON.
Everything else goes on earlier lines or into ``benchmark/.work/``.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        # the system under test must be there: a directory that holds only
        # the benchmark's own files fails here, before any result
        import p2p_tpu  # noqa: F401

        cell = harness.load_cell(args.workload, args.seed, args.seconds,
                                 bool(args.trace), T_START)
        driver = harness.load_by_path("drivers", cell.workload["driver"])
        line = driver.run(cell)
    except harness.CellError as e:
        print(f"benchmark: {e}", file=sys.stderr, flush=True)
        return harness.NO_ACCELERATOR_EXIT
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
