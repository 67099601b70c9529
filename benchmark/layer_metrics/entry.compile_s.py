"""entry.compile_s (s; layer: entry points; moves setup_s).

Seconds XLA spent compiling during set-up (the RetraceWatchdog's xla_compile_secs); near 0 once the cache holds the cell's programs.
"""

META = {"name": "entry.compile_s", "unit": "s", "layer": "entry points",
        "moves": "setup_s"}


def read(run):
    return run["setup"]["compile_s"] if "setup" in run else None
