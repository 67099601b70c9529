"""One traced run of a train cell read by the program's own names: the
device's idle seconds by the PHASE of ``Trainer.train_epoch()`` the host
was in, and the step's device time by NET (``benchmark/scope_time.py``).

    python benchmark/tools/trace_phases.py --workload reference_256.train --seed 2147483659

It is the cell's own ``--trace 1`` run, through the cell's own driver,
with the two things the driver would do itself had it the lines for them
(it is the accepted yardstick; only a ``benchmark`` PR may edit it): the
program's phase names stand in the driver's ``GAP_PRIORITY`` ahead of
``bench_epoch``, so the result line's ``breakdown.idle_gaps`` puts each
gap down to what the program was doing; and before the driver removes the
trace, the step's compiled text is taken (a cache load) and joined with
it. Prints the driver's lines, then ``{"by_scope": ..., "per_step":
...}``, then the result line.
"""

import argparse
import json
import os
import sys
import time

T_START = time.perf_counter()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

#: host annotations a gap of the device is named by, innermost first: the
#: program's phases, then the driver's own
PHASE_PRIORITY = ("train_dispatch", "h2d_put", "loader_next", "feed_next",
                  "epoch_setup", "step_bookkeeping", "epoch_drain",
                  "bench_fence", "bench_epoch")


def compiled_step_text(trainer) -> str:
    """The text of the executable the Trainer's step runs: lowered again
    from the live state and a batch of the cell's shape, and "compiled"
    by a load from the cache the run's own compile filled."""
    from p2p_tpu.data.pipeline import device_prefetch

    # fed the way the loop feeds it: lowered from a host array the step's
    # text numbers its private functions another way, which is another
    # cache key and a cold compile of minutes
    (batch,) = device_prefetch([trainer._host_batch_sample()],
                               trainer.batch_sharding)
    return trainer.train_step.lower(trainer.state, batch).compile().as_text()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--bench_file", default=None)
    ap.add_argument("--allow_cpu", action="store_true")
    args = ap.parse_args(argv)

    from benchmark import epoch_records, harness, scope_time, trace_reduce

    cell = harness.load_cell(args.workload, args.seed, args.seconds, True,
                             T_START, args.bench_file,
                             require_tpu=not args.allow_cpu)
    driver = harness.load_by_path("drivers", cell.workload["driver"])
    driver.GAP_PRIORITY = PHASE_PRIORITY
    reduce_trace = trace_reduce.reduce_trace

    def reduce_and_join(xplane, *a, **kw):
        # called by the driver after the window, with the Trainer's state
        # still alive and the trace still on disk
        scoped = scope_time.by_scope(
            xplane, compiled_step_text(epoch_records.live_trainer()))
        harness.say(by_scope=scoped,
                    per_step=scope_time.per_step_numbers(scoped))
        return reduce_trace(xplane, *a, **kw)

    trace_reduce.reduce_trace = reduce_and_join
    try:
        print(driver.run(cell), flush=True)
    finally:
        trace_reduce.reduce_trace = reduce_trace
    return 0


if __name__ == "__main__":
    sys.exit(main())
