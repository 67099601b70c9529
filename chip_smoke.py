"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, one TPU chip, the CLIs' own ``main(argv)``:

1. ``native``  build ``p2p_tpu/native/_fastimage.so`` from the committed
               ``fastimage.cpp``; the loader must use it (no PIL fallback).
2. ``train``   ``cli.train --preset reference`` at full width (ngf=64, 9
               blocks, 3-scale D, 256x256, VGG19 from the seed) on a paired
               dataset written from ``--seed``: one epoch of 8 steps, the
               PSNR/SSIM eval, a checkpoint save.
3. ``infer``   ``cli.infer`` from that checkpoint (params-only restore);
               every output image written and decodable.
4. ``serve``   ``cli.serve --http`` in this process on an ephemeral port; a
               few ``POST /v1/<name>/translate`` answered with PNGs of the
               right size, ``n_compiles == len(buckets)``, SIGTERM drain.
5. ``pallas``  two ``cli.train --preset pix2pixhd --mesh data=1`` steps at
               1024x512 bs1; the step's lowered program must contain
               ``tpu_custom_call`` (the Pallas kernels, not an XLA or
               interpret-mode stand-in).

``--multichip`` (four chips, run by hand) runs ONLY the cross-chip path and
its reference: the pix2pixhd step at bs2 on ``--mesh data=2,spatial=2`` and
on ``--mesh data=1``, same seed and data; the logged losses must agree, the
compiled step must hold halo ``collective-permute``s and neither an
all-gather nor an all-to-all as large as its smallest normed activation.
Run and passed on one four-chip "TPU v5 lite" host (2x2, one process) in
PR 25: 446 s cold, 600 collective-permutes, 78 all-reduces, no all-gather,
72 kernel calls, loss parity at 0.40 of the tolerance (PERF.md section 6).

Every phase prints one JSON line; any failure raises and the script exits
non-zero at once. It refuses to start unless ``jax.devices()[0].platform``
is ``"tpu"``. The last stdout line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}``.

The phases are functions of :class:`Sizes`; tests/test_chip_smoke.py calls
them at toy size on the CPU. Depth of the RUN is what is cut here (8 + 2
steps, a handful of requests) — never widths or resolution.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import glob
import io
import json
import math
import os
import shutil
import signal
import socket
import sys
import threading
import time
import urllib.request
from typing import Dict, List, Optional, Sequence

REPO = os.path.dirname(os.path.abspath(__file__))
KERNEL_MARKER = "tpu_custom_call"
#: sharded-vs-unsharded metric parity — the tolerance tests/test_parallel.py
#: holds GSPMD-partitioned steps to against their single-device twin
PARITY_RTOL = PARITY_ATOL = 8e-4


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Run sizes. The defaults ARE the chip run (preset widths, the
    presets' default extents: pix2pixhd's 1024x512 is half the paper's
    2048x1024 each way, which takes four chips: PERF.md section 4); the
    CPU rehearsal test shrinks them."""

    image_size: int = 256                 # reference phases (square)
    hd_hw: Sequence[int] = (512, 1024)    # pix2pixhd H, W
    ngf: Optional[int] = None             # None = the preset's width
    ndf: Optional[int] = None
    n_blocks: Optional[int] = None
    lambda_vgg: Optional[float] = None    # None = the preset's (10, VGG19)
    train_steps: int = 8
    n_test: int = 2
    serve_requests: int = 4
    hd_steps: int = 2

    def width_flags(self) -> List[str]:
        out: List[str] = []
        for flag, v in (("--ngf", self.ngf), ("--ndf", self.ndf),
                        ("--n_blocks", self.n_blocks),
                        ("--lambda_vgg", self.lambda_vgg)):
            if v is not None:
                out += [flag, str(v)]
        return out

    def model_flags(self) -> List[str]:
        """The subset cli.infer / cli.serve accept."""
        out: List[str] = []
        for flag, v in (("--ngf", self.ngf), ("--n_blocks", self.n_blocks)):
            if v is not None:
                out += [flag, str(v)]
        return out


# ------------------------------------------------------------------ meter


class PhaseMeter:
    """Times a phase and reads what the process counted during it: XLA
    compiles and their seconds, persistent-cache hits/misses (the obs
    RetraceWatchdog's counters), device memory high-water mark."""

    def __init__(self):
        from p2p_tpu.obs import MetricsRegistry, RetraceWatchdog

        self.registry = MetricsRegistry()
        self.dog = RetraceWatchdog(registry=self.registry)

    def _counts(self) -> Dict[str, float]:
        return {
            "n_compiles": self.dog.compiles,
            "compile_seconds": self.registry.histogram(
                "xla_compile_secs").sum,
            "persistent_cache_hits": self.dog.cache_hits,
            "persistent_cache_misses": self.dog.cache_misses,
        }

    @contextlib.contextmanager
    def phase(self, name: str):
        import jax

        before = self._counts()
        result: Dict[str, object] = {}
        t0 = time.perf_counter()
        yield result
        seconds = time.perf_counter() - t0
        after = self._counts()
        line = {"phase": name, "seconds": round(seconds, 3)}
        for k, v in after.items():
            d = v - before[k]
            line[k] = round(d, 3) if isinstance(d, float) else d
        stats = jax.local_devices()[0].memory_stats() or {}
        # process-lifetime high-water mark (the runtime keeps no per-phase
        # peak) and what is still held after the phase
        line["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
        line["bytes_in_use"] = stats.get("bytes_in_use")
        line.update(result)
        print(json.dumps(line), flush=True)

    def close(self) -> None:
        self.dog.close()


# ---------------------------------------------------------------- helpers


def _read_jsonl(path: str) -> List[dict]:
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def _require_finite(records: Sequence[dict], what: str) -> None:
    for rec in records:
        for k, v in rec.items():
            if isinstance(v, float) and not math.isfinite(v):
                raise AssertionError(f"{what}: non-finite {k}={v} in {rec}")


def _fresh_dir(path: str) -> str:
    """An empty directory at ``path`` — a previous run's checkpoint there
    would turn this run into a resume that trains nothing."""
    if os.path.isdir(path):
        shutil.rmtree(path)
    os.makedirs(path)
    return path


def _records(workdir: str, name: str, kind: str) -> List[dict]:
    return [r for r in _read_jsonl(os.path.join(
        workdir, f"metrics_{name}.jsonl")) if r.get("kind") == kind]


def _train_records(workdir: str, name: str, steps: int) -> List[dict]:
    """The ``kind=train`` records of a finished run: exactly ``steps`` of
    them, numbered 1..steps, every value finite."""
    train = _records(workdir, name, "train")
    got = [int(r["step"]) for r in train]
    if got != list(range(1, steps + 1)):
        raise AssertionError(
            f"{name}: asked for {steps} steps, metrics hold steps {got}")
    _require_finite(train, name)
    return train


def _step_ir(dump_dir: str) -> str:
    """Text of the train step jax lowered while ``jax_dump_ir_to`` pointed
    at ``dump_dir`` (written at lowering, so also on a warm-cache run)."""
    files = glob.glob(os.path.join(dump_dir, "*_jit_step*_compile.mlir"))
    if len(files) != 1:
        raise AssertionError(
            f"expected ONE lowered train step under {dump_dir}, found "
            f"{sorted(os.path.basename(f) for f in files)} — more than "
            "one means the step compiled twice")
    with open(files[0]) as f:
        return f.read()


@contextlib.contextmanager
def _dump_ir_to(dump_dir: str):
    import jax

    _fresh_dir(dump_dir)
    jax.config.update("jax_dump_ir_to", dump_dir)
    try:
        yield dump_dir
    finally:
        jax.config.update("jax_dump_ir_to", None)


# ----------------------------------------------------------------- phases


def phase_native(result: dict) -> None:
    from p2p_tpu import native

    result["built"] = os.path.relpath(native.build(), REPO)
    native.require()
    result["decoder"] = "native"


def phase_train(result: dict, out: str, seed: int, sizes: Sizes) -> dict:
    """cli.train --preset reference: one epoch, eval, checkpoint."""
    from p2p_tpu import native
    from p2p_tpu.cli import train
    from p2p_tpu.data.synthetic import make_synthetic_dataset

    name, dataset = "smoke_ref", "synthetic"
    data_root = make_synthetic_dataset(
        _fresh_dir(os.path.join(out, "data_ref")),
        n_train=sizes.train_steps, n_test=sizes.n_test,
        size=sizes.image_size, seed=seed)
    # the loader's decoder for these files: native fast path or PIL
    sample = os.path.join(data_root, "train", "a", "synth_0000.png")
    fast = native.load_image_fast(
        sample, expect_hw=(sizes.image_size, sizes.image_size))
    if fast is None:
        raise AssertionError(f"native decoder refused {sample}")
    workdir = _fresh_dir(os.path.join(out, "work_ref"))
    argv = ["--preset", "reference", "--data_root", data_root,
            "--workdir", workdir, "--name", name, "--dataset", dataset,
            "--image_size", str(sizes.image_size), "--batch_size", "1",
            "--nepoch", "1", "--epochsave", "1", "--threads", "0",
            "--seed", str(seed), "--log_every", "1",
            *sizes.width_flags()]
    rc = train.main(argv)
    if rc != 0:
        raise AssertionError(f"cli.train exited {rc}")
    recs = _train_records(workdir, name, sizes.train_steps)
    evals = _records(workdir, name, "eval")
    if len(evals) != 1 or int(evals[0]["n_images"]) != sizes.n_test:
        raise AssertionError(f"expected one eval over {sizes.n_test} "
                             f"images, got {evals}")
    _require_finite(evals, "eval")
    ckpt = os.path.join(workdir, "checkpoint", dataset, name,
                        str(sizes.train_steps))
    if not os.path.isdir(ckpt):
        raise AssertionError(f"no checkpoint at {ckpt}")
    result.update(steps=len(recs), loss_g=recs[-1]["loss_g"],
                  loss_d=recs[-1]["loss_d"], psnr=evals[0]["psnr_mean"],
                  ssim=evals[0]["ssim_mean"], decoder="native")
    return {"workdir": workdir, "data_root": data_root, "name": name,
            "dataset": dataset}


def phase_infer(result: dict, run: dict, sizes: Sizes) -> None:
    """cli.infer from the train phase's checkpoint (params-only restore)."""
    import numpy as np
    from PIL import Image

    from p2p_tpu.cli import infer

    pred = os.path.join(run["workdir"], "pred")
    rc = infer.main(["--preset", "reference", "--name", run["name"],
                     "--dataset", run["dataset"],
                     "--data_root", run["data_root"],
                     "--workdir", run["workdir"], "--out", pred,
                     "--image_size", str(sizes.image_size),
                     *sizes.model_flags()])
    if rc != 0:
        raise AssertionError(f"cli.infer exited {rc}")
    files = sorted(os.listdir(pred))
    if len(files) != sizes.n_test:
        raise AssertionError(
            f"cli.infer wrote {files}, expected {sizes.n_test} images")
    spread = []
    for f in files:
        img = np.asarray(Image.open(os.path.join(pred, f)).convert("RGB"))
        if img.shape != (sizes.image_size, sizes.image_size, 3):
            raise AssertionError(f"{f}: shape {img.shape}")
        spread.append(float(img.std()))
    result.update(images=len(files), pixel_std_min=round(min(spread), 3))


def _serve_client(base: str, alias: str, sizes: Sizes, box: dict) -> None:
    """The HTTP client thread: wait for /healthz, POST the requests, check
    every answer, then SIGTERM this process so the server (running in the
    main thread, where its signal guard lives) drains and returns."""
    import numpy as np
    from PIL import Image

    try:
        deadline = time.time() + 900
        while True:
            try:
                with urllib.request.urlopen(base + "/healthz",
                                            timeout=2) as r:
                    if r.status == 200:
                        break
            except OSError:
                pass
            if time.time() > deadline:
                raise AssertionError("server never became healthy")
            time.sleep(0.25)
        rng = np.random.default_rng(0)
        latencies = []
        for _ in range(sizes.serve_requests):
            img = rng.integers(0, 256, (sizes.image_size, sizes.image_size,
                                        3), dtype=np.uint8)
            buf = io.BytesIO()
            Image.fromarray(img).save(buf, format="PNG")
            req = urllib.request.Request(
                f"{base}/v1/{alias}/translate", data=buf.getvalue(),
                method="POST")
            t0 = time.perf_counter()
            with urllib.request.urlopen(req, timeout=300) as r:
                body = r.read()
                if r.status != 200:
                    raise AssertionError(f"translate answered {r.status}")
            latencies.append(time.perf_counter() - t0)
            got = Image.open(io.BytesIO(body))
            got.load()
            if got.size != (sizes.image_size, sizes.image_size):
                raise AssertionError(f"answer is {got.size}")
        with urllib.request.urlopen(base + "/healthz", timeout=10) as r:
            tenant = json.loads(r.read())["tenants"][alias]
        if tenant["n_compiles"] != len(tenant["buckets"]):
            raise AssertionError(f"mid-serve recompile: {tenant}")
        box.update(requests=len(latencies), buckets=tenant["buckets"],
                   n_bucket_compiles=tenant["n_compiles"],
                   request_seconds_median=round(
                       sorted(latencies)[len(latencies) // 2], 4))
    except BaseException as exc:  # noqa: BLE001 — re-raised by the phase
        box["error"] = exc
    finally:
        # SIGTERM is the server's drain request — but only once its guard
        # handles the signal; the default action would kill this process
        # with no verdict. A server that never installs one (still
        # compiling, or wedged) must not hang the smoke: hard exit.
        deadline = time.time() + 300
        while signal.getsignal(signal.SIGTERM) in (
                signal.SIG_DFL, signal.SIG_IGN, None):
            if box.get("server_returned"):
                return
            if time.time() > deadline:
                print(f"serve phase wedged: {box.get('error')!r}",
                      file=sys.stderr, flush=True)
                os._exit(3)
            time.sleep(0.25)
        os.kill(os.getpid(), signal.SIGTERM)


def phase_serve(result: dict, run: dict, sizes: Sizes) -> None:
    """cli.serve --http in THIS process (the main thread: its graceful
    drain is a signal guard), driven by a client thread."""
    from p2p_tpu.cli import serve

    with socket.socket() as s:           # an ephemeral port, handed over
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    box: dict = {}
    client = threading.Thread(
        target=_serve_client, daemon=True,
        args=(f"http://127.0.0.1:{port}", run["name"], sizes, box))
    client.start()
    rc = serve.main(["--http", f"127.0.0.1:{port}", "--preset", "reference",
                     "--name", run["name"], "--dataset", run["dataset"],
                     "--workdir", run["workdir"],
                     "--image_size", str(sizes.image_size),
                     "--max_batch", "2", "--linger_ms", "5",
                     *sizes.model_flags()])
    box["server_returned"] = True
    if rc != 0:
        raise AssertionError(f"cli.serve exited {rc}")
    client.join(60)
    if client.is_alive():
        raise AssertionError("serve client still running after the drain")
    if "error" in box:
        raise box["error"]
    box.pop("server_returned")
    result.update(box, shutdown="clean")


def _hd_train(out: str, tag: str, seed: int, sizes: Sizes, batch: int,
              mesh: str, data_root: str) -> List[dict]:
    """``sizes.hd_steps`` steps of cli.train --preset pix2pixhd on ``mesh``;
    returns the train records."""
    from p2p_tpu.cli import train

    name = f"smoke_hd_{tag}"
    workdir = _fresh_dir(os.path.join(out, f"work_hd_{tag}"))
    h, w = sizes.hd_hw
    rc = train.main(["--preset", "pix2pixhd", "--mesh", mesh,
                     "--data_root", data_root, "--workdir", workdir,
                     "--name", name, "--dataset", "synthetic_hd",
                     "--image_size", str(h), "--image_width", str(w),
                     "--batch_size", str(batch), "--test_batch_size", "1",
                     "--nepoch", "1", "--epochsave", "1000",
                     "--threads", "0", "--seed", str(seed),
                     "--log_every", "1", *sizes.width_flags()])
    if rc != 0:
        raise AssertionError(f"cli.train pix2pixhd ({mesh}) exited {rc}")
    return _train_records(workdir, name, sizes.hd_steps)


def _hd_dataset(out: str, seed: int, sizes: Sizes, batch: int) -> str:
    """Square seeded pairs; the loader resizes them to the HD extent."""
    from p2p_tpu.data.synthetic import make_synthetic_dataset

    return make_synthetic_dataset(
        _fresh_dir(os.path.join(out, "data_hd")),
        n_train=sizes.hd_steps * batch, n_test=1,
        size=min(sizes.hd_hw), seed=seed)


def phase_pallas(result: dict, out: str, seed: int, sizes: Sizes,
                 kernel_marker: Optional[str] = KERNEL_MARKER) -> None:
    """Two pix2pixhd steps at the preset's default extent on ONE device;
    the lowered step must carry ``kernel_marker`` (None: the CPU rehearsal, where the
    kernels run interpreted and there is no custom call to find)."""
    data_root = _hd_dataset(out, seed, sizes, batch=1)
    with _dump_ir_to(os.path.join(out, "ir_hd")) as dump:
        recs = _hd_train(out, "one", seed, sizes, 1, "data=1", data_root)
        ir = _step_ir(dump)
    n_kernels = ir.count(kernel_marker) if kernel_marker else None
    if kernel_marker and not n_kernels:
        raise AssertionError(
            f"no {kernel_marker} in the lowered pix2pixhd step — the "
            "Pallas kernels did not run")
    result.update(steps=len(recs), loss_g=recs[-1]["loss_g"],
                  loss_d=recs[-1]["loss_d"], kernel_marker=kernel_marker,
                  kernel_calls_in_step=n_kernels)


class _MemorySampler(threading.Thread):
    """Max ``bytes_in_use`` per local device while a run is in flight (the
    state is gone by the time ``main`` returns)."""

    def __init__(self):
        super().__init__(daemon=True)
        self.max_in_use: Dict[int, int] = {}
        self._stop_evt = threading.Event()

    def run(self) -> None:
        import jax

        while not self._stop_evt.wait(0.2):
            for d in jax.local_devices():
                used = (d.memory_stats() or {}).get("bytes_in_use")
                if used is not None:
                    self.max_in_use[d.id] = max(
                        self.max_in_use.get(d.id, 0), used)

    def stop(self) -> Dict[int, int]:
        self._stop_evt.set()
        self.join(10)
        return dict(self.max_in_use)


def phase_multichip(result: dict, out: str, seed: int, sizes: Sizes,
                    xla_dump: Optional[str]) -> None:
    """The cross-chip path and its reference: pix2pixhd bs2 on
    data=2 x spatial=2, then the same steps on one device. Every check
    is evaluated and the evidence printed BEFORE the phase fails, so one
    four-chip call says everything it can."""
    import jax

    from p2p_tpu.analysis.jaxpr_lint import (
        collect_collectives,
        hlo_collective_shapes,
    )

    failures: List[str] = []
    data_root = _hd_dataset(out, seed, sizes, batch=2)
    sampler = _MemorySampler()
    sampler.start()
    with _dump_ir_to(os.path.join(out, "ir_hd4")) as dump:
        sharded = _hd_train(out, "mesh4", seed, sizes, 2,
                            "data=2,spatial=2", data_root)
        ir = _step_ir(dump)
    in_use = sampler.stop()
    # the batch really is laid out over the mesh, not on one device
    if "mhlo.num_partitions = 4" not in ir:
        failures.append("the sharded step is not a 4-partition program")
    batch_arg = next(
        (ln for ln in ir.split("%arg") if "batch['input']" in ln), "")
    if '"data"' not in batch_arg or '"spatial"' not in batch_arg:
        failures.append("batch['input'] is not sharded over data x "
                        f"spatial: {batch_arg[:200]}")
    # where the backend reports memory (TPU): all four devices hold state
    if in_use and (len(in_use) != 4 or min(in_use.values()) < (1 << 20)):
        failures.append(f"a device holds no state: {in_use}")
    result["max_bytes_in_use_per_device"] = {
        str(k): v for k, v in sorted(in_use.items())}
    if xla_dump is not None:
        hlos = glob.glob(os.path.join(
            xla_dump, "*jit_step*after_optimizations.txt"))
        if not hlos:
            raise AssertionError(f"no compiled step dumped in {xla_dump}")
        with open(max(hlos, key=os.path.getsize)) as f:
            hlo = f.read()
        census = dict(collect_collectives(hlo))
        gathered, resharded = (
            max((n for n, _ in hlo_collective_shapes(hlo, kind)), default=0)
            for kind in ("all-gather", "all-to-all"))
        # the smallest Pallas-normed activation of the step (bs2 at 1/16
        # extent, 1024 ch): an all-gather that large undid a shard, and so
        # did an all-to-all (GSPMD's answer to a reverse along H moved the
        # whole tensor from H to W and back: the k7 layers' reflect pad
        # until PR 25)
        h, w = sizes.hd_hw
        bound = 2 * (h // 16) * (w // 16) * 1024
        if not census.get("collective-permute"):
            failures.append(f"no halo collective-permute: {census}")
        for kind, n in (("all-gather", gathered), ("all-to-all", resharded)):
            if n >= bound:
                failures.append(f"{kind} of {n} elements >= the "
                                f"activation bound {bound}")
        result.update(collectives=census,
                      largest_all_gather_elements=gathered,
                      largest_all_to_all_elements=resharded,
                      kernel_calls_compiled=hlo.count(KERNEL_MARKER))
    single = _hd_train(out, "ref1", seed, sizes, 2, "data=1", data_root)
    worst = 0.0
    for a, b in zip(sharded, single):
        for k in ("loss_g", "loss_d", "g_gan", "g_feat", "g_vgg"):
            if k not in a:
                continue
            frac = abs(a[k] - b[k]) / (PARITY_ATOL + PARITY_RTOL * abs(b[k]))
            worst = max(worst, frac)
            if frac > 1.0:
                failures.append(
                    f"step {int(a['step'])} {k}: sharded {a[k]} vs "
                    f"single-device {b[k]} (tolerance {PARITY_RTOL})")
    keys = ("loss_g", "loss_d", "g_gan", "g_feat", "g_vgg")
    result.update(
        steps=len(sharded), devices=len(jax.devices()),
        sharded=[{k: r[k] for k in keys if k in r} for r in sharded],
        single=[{k: r[k] for k in keys if k in r} for r in single],
        parity_worst_fraction_of_tolerance=round(worst, 4),
        failures=failures)
    if failures:
        print(json.dumps({"phase": "multichip_failed", **result}),
              flush=True)
        raise AssertionError("; ".join(failures))


# ------------------------------------------------------------------- main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--multichip", action="store_true",
                    help="run only the four-chip path and its one-device "
                         "reference (needs four chips)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", type=str,
                    default=os.path.join(REPO, "chip_smoke_out"),
                    help="the only directory written (besides the compile "
                         "cache and the native .so)")
    args = ap.parse_args(argv)
    xla_dump = None
    if args.multichip:
        # the collective assertions read the COMPILED step, which only a
        # real compile dumps: XLA dump on (before the backend starts),
        # persistent cache off for this mode
        xla_dump = os.path.join(args.out, "xla_hd4")
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_dump_to={xla_dump} --xla_dump_hlo_as_text"
              " --xla_dump_hlo_module_re=.*jit_step.*").strip()

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke.py needs a TPU; jax found {dev.platform!r}",
              file=sys.stderr)
        return 2
    n_dev = len(jax.devices())
    if args.multichip and n_dev != 4:
        print(f"--multichip needs four chips, found {n_dev}",
              file=sys.stderr)
        return 2

    from p2p_tpu.core.cache import enable_compilation_cache

    os.makedirs(args.out, exist_ok=True)
    cache_dir = enable_compilation_cache()
    print(json.dumps({"phase": "start", "device_kind": dev.device_kind,
                      "devices": n_dev, "cache_dir": cache_dir,
                      "cache_entries": len(os.listdir(cache_dir)),
                      "seed": args.seed}), flush=True)
    sizes = Sizes()
    meter = PhaseMeter()
    if args.multichip:
        jax.config.update("jax_enable_compilation_cache", False)
        if os.path.isdir(xla_dump):
            shutil.rmtree(xla_dump)
        with meter.phase("multichip") as r:
            phase_multichip(r, args.out, args.seed, sizes, xla_dump)
    else:
        with meter.phase("native") as r:
            phase_native(r)
        with meter.phase("train") as r:
            run = phase_train(r, args.out, args.seed, sizes)
        with meter.phase("infer") as r:
            phase_infer(r, run, sizes)
        with meter.phase("serve") as r:
            phase_serve(r, run, sizes)
        with meter.phase("pallas") as r:
            phase_pallas(r, args.out, args.seed, sizes)
    meter.close()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": n_dev}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
