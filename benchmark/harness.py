"""What every driver shares: finding a cell's files by name, the device
check, the compile/cache counters and the result line.

The yardstick lives here and in the sibling modules; from the program a
driver takes only the system under test, its counters and its spans.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import sys
from typing import Any, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
WORK = os.path.join(BENCH_DIR, ".work")
#: the cap the chip tool's machine puts on its own cache directory
#: (JAX_COMPILATION_CACHE_MAX_SIZE there); a cell's programs are printed
#: against it so a reader sees whether such a machine could hold them
MACHINE_CACHE_CAP = 192 * 2 ** 20
NO_ACCELERATOR_EXIT = 2


class CellError(Exception):
    """The cell cannot run here (bad name, missing file, wrong device)."""


# ------------------------------------------------------------------ lookup


def _read_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def _by_name(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise CellError(f"no {what} named {name!r}; known: "
                    f"{sorted(e['name'] for e in entries)}")


@dataclasses.dataclass
class Cell:
    """One run's inputs: the cell's entry in the benchmark file, its
    workload file, its configuration file, and the run's arguments."""

    name: str
    entry: dict            # the ``workloads`` entry
    workload: dict         # benchmark/workloads/<name>.json
    config_name: str
    config: dict           # the configuration file
    end_to_end: List[dict]
    per_layer: List[dict]
    seed: int
    seconds: float
    trace: bool
    t_start: float         # perf_counter at process start
    require_tpu: bool = True
    root: str = ROOT

    @property
    def work(self) -> str:
        """Scratch of this cell and seed (git-ignored, inside the checkout)."""
        return os.path.join(WORK, self.name, f"seed_{self.seed}")

    @property
    def cache_dir(self) -> str:
        """The compile cache of this cell: a FIXED path inside the checkout
        (the path is part of the cache key). One directory per cell, so
        one cell's programs never evict another's."""
        return os.path.join(WORK, "jax_cache", self.name)

    def metrics_for(self, group: List[dict]) -> List[dict]:
        return [m for m in group
                if "workloads" not in m or self.name in m["workloads"]]


def load_cell(name: str, seed: int, seconds: float, trace: bool,
              t_start: float, bench_file: Optional[str] = None,
              require_tpu: bool = True) -> Cell:
    """Find everything the cell names. ``bench_file`` defaults to the
    checkout's BENCHMARK.json; the CPU rehearsal passes its own."""
    bench_file = bench_file or os.path.join(ROOT, "BENCHMARK.json")
    bench = _read_json(bench_file)
    entry = _by_name(bench["workloads"], name, "workload")
    cfg_entry = _by_name(bench["configs"], entry["config"], "config")
    base = os.path.dirname(os.path.abspath(bench_file))
    workload_file = os.path.join(BENCH_DIR, "workloads", f"{name}.json")
    if not os.path.exists(workload_file):
        workload_file = os.path.join(base, "workloads", f"{name}.json")
    cfg_file = os.path.join(ROOT, cfg_entry["file"])
    if not os.path.exists(cfg_file):
        cfg_file = os.path.join(base, cfg_entry["file"])
    workload = _read_json(workload_file)
    if workload["config"] != entry["config"]:
        raise CellError(f"{workload_file} names config "
                        f"{workload['config']!r}, the benchmark file "
                        f"{entry['config']!r}")
    return Cell(name=name, entry=entry, workload=workload,
                config_name=entry["config"], config=_read_json(cfg_file),
                end_to_end=bench["end_to_end"], per_layer=bench["per_layer"],
                seed=seed, seconds=seconds, trace=trace, t_start=t_start,
                require_tpu=require_tpu)


def load_by_path(kind: str, name: str):
    """Import ``benchmark/<kind>/<name>.py`` — names may hold dots, so a
    plain ``import`` will not do."""
    path = os.path.join(BENCH_DIR, kind, f"{name}.py")
    if not os.path.exists(path):
        raise CellError(f"no {kind} file {path}")
    mod_name = f"benchmark.{kind}.{name.replace('.', '_')}"
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def read_layer_metrics(cell: Cell, run: Dict[str, Any]) -> Dict[str, dict]:
    """Every per-layer metric of the cell through its own reader. A reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in cell.metrics_for(cell.per_layer):
        value = load_by_path("layer_metrics", m["name"]).read(run)
        if value is not None and math.isfinite(value):
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# ------------------------------------------------------------------ device


def load_peaks() -> dict:
    return _read_json(os.path.join(BENCH_DIR, "peaks.json"))


def device_info(chips: int, require_tpu: bool) -> dict:
    """The devices as jax reports them. A BENCHMARK.json cell never times
    the CPU: off a TPU, on a ``device_kind`` the peaks table lacks, or on
    fewer chips than the cell asks for, this raises."""
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_tpu:
        if info["platform"] != "tpu":
            raise CellError(f"no accelerator: jax reports {info}")
        if info["kind"] not in load_peaks():
            raise CellError(f"device kind {info['kind']!r} is not in "
                            "benchmark/peaks.json")
        if info["count"] < chips:
            raise CellError(f"cell needs {chips} chips, jax reports {info}")
    return info


def peak_memory_bytes() -> Optional[int]:
    """Peak device memory on the fullest chip: the peak of live buffers
    plus the peak the runtime RESERVED for programs' temporaries. On this
    TPU runtime ``peak_bytes_in_use`` leaves a program's temp space out
    (it read 0.58 GB for a step whose compiler-reported temp is 13.4 GiB);
    ``peak_bytes_reserved`` holds it (14.3 GB for that step)."""
    import jax

    peaks = []
    for d in jax.local_devices():
        st = d.memory_stats() or {}
        if "peak_bytes_in_use" in st:
            peaks.append(int(st["peak_bytes_in_use"])
                         + int(st.get("peak_bytes_reserved", 0)))
    return max(peaks) if peaks else None


def prepare_jax_env(cell: Cell) -> None:
    """Before jax is imported: the cell's compile cache inside the
    checkout, uncapped (the directory is the benchmark's own), and the
    host CPU backend kept reachable for the float32 reference."""
    os.makedirs(cell.cache_dir, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cell.cache_dir
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    plats = os.environ.get("JAX_PLATFORMS", "")
    if plats and "cpu" not in plats.split(","):
        os.environ["JAX_PLATFORMS"] = plats + ",cpu"


def dir_bytes(path: str) -> int:
    total = 0
    for base, _, files in os.walk(path):
        for f in files:
            try:
                total += os.path.getsize(os.path.join(base, f))
            except OSError:
                pass
    return total


class CompileMeter:
    """XLA compiles, their seconds and the persistent cache's hits and
    misses, as the program's own RetraceWatchdog counts them (public
    ``jax.monitoring`` events)."""

    def __init__(self):
        from p2p_tpu.obs import MetricsRegistry, RetraceWatchdog

        self.registry = MetricsRegistry()
        self.dog = RetraceWatchdog(registry=self.registry)

    def counts(self) -> Dict[str, float]:
        return {"n_compiles": self.dog.compiles,
                "compile_s": self.registry.histogram("xla_compile_secs").sum,
                "cache_hits": self.dog.cache_hits,
                "cache_misses": self.dog.cache_misses}

    def close(self) -> None:
        self.dog.close()


def delta(after: Dict[str, float], before: Dict[str, float]) -> Dict[str, float]:
    return {k: after[k] - before[k] for k in after}


# ------------------------------------------------------------------ numbers


def say(**fields) -> None:
    """One JSON object on an earlier line of stdout."""
    print(json.dumps(fields), flush=True)


def result_line(cell: Cell, correct: bool, attempted: int, failed: int,
                measured: Dict[str, float], run: Dict[str, Any],
                device: dict) -> str:
    """The last line. ``--trace 0``: the cell's end-to-end metrics;
    ``--trace 1``: its per-layer metrics, the traced window in ``device``
    and the breakdown."""
    line: Dict[str, Any] = {"correct": bool(correct),
                            "attempted": int(attempted),
                            "failed": int(failed)}
    if cell.trace:
        line["metrics"] = read_layer_metrics(cell, run)
        tr = run.get("trace")
        if tr is not None:
            device = dict(device, busy_s=tr["busy_s"],
                          window_s=tr["window_s"])
            line["breakdown"] = {"device_ops": tr["device_ops"][:10],
                                 "idle_gaps": tr["idle_gaps"][:10]}
    else:
        line["metrics"] = {
            m["name"]: {"value": float(measured[m["name"]]),
                        "unit": m["unit"]}
            for m in cell.metrics_for(cell.end_to_end)}
    line["device"] = device
    return json.dumps(line)
