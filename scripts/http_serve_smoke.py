"""CI smoke: the HTTP serving headline contract, end-to-end over a real
subprocess (python -m p2p_tpu.cli.serve --http) — the acceptance pin of
ISSUE 12 / docs/SERVING.md "HTTP API":

1. TWO tenants resident in one process serve concurrent HTTP clients
   with zero mid-serve recompiles (per-tenant n_compiles == buckets);
2. a mid-traffic hot-swap (POST /admin/reload) completes with ZERO
   dropped/failed requests;
3. a corrupt-manifest swap is REJECTED (409) while the old engine keeps
   serving;
4. /metrics exposes latency histograms + queue depth + shed counters +
   batch occupancy, tenant-tagged;
5. SIGTERM → graceful drain → exit 0.

One process per device: this parent never initialises jax. Checkpoints
are written by short CPU-pinned children (``--save-step``), and the server
child runs on whatever backend the environment names.

Run: JAX_PLATFORMS=cpu python scripts/http_serve_smoke.py [workdir]
"""

import io
import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request


_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _child_env(**extra):
    return {**os.environ, **extra,
            "PYTHONPATH": _REPO + os.pathsep
            + os.environ.get("PYTHONPATH", "")}


def _save_step_child(name: str, step: int, seed: int, workdir: str) -> int:
    """``--save-step`` mode: init a tiny facades TrainState from ``seed``
    and save it as ``step`` — run in a child of its own (see
    :func:`save_step`); prints the checkpoint directory."""
    import dataclasses

    import jax

    from p2p_tpu.core.config import get_preset
    from p2p_tpu.data.synthetic import synthetic_batch
    from p2p_tpu.serve.tenancy import checkpoint_dir
    from p2p_tpu.train.checkpoint import CheckpointManager
    from p2p_tpu.train.state import create_train_state

    cfg = get_preset("facades")
    cfg = dataclasses.replace(
        cfg, name=name,
        model=dataclasses.replace(cfg.model, ngf=4),
        data=dataclasses.replace(cfg.data, dataset="synth", image_size=16))
    batch = synthetic_batch(1, 16, dtype="uint8")
    state = create_train_state(cfg, jax.random.key(seed), batch, 1)
    d = checkpoint_dir(cfg, workdir)
    mgr = CheckpointManager(d)
    mgr.save(step, state, wait=True)
    mgr.close()
    print(d, flush=True)
    return 0


def save_step(name: str, step: int, seed: int, workdir: str) -> str:
    """Write a checkpoint from a CPU-pinned child that exits. This
    process never initialises jax: a device belongs to one process at a
    time, and the SERVER child below must be the one that gets it."""
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--save-step",
         name, str(step), str(seed), workdir],
        env=_child_env(JAX_PLATFORMS="cpu"), check=True,
        capture_output=True, text=True, timeout=300)
    return out.stdout.strip().splitlines()[-1]


def main() -> int:
    if len(sys.argv) > 1 and sys.argv[1] == "--save-step":
        name, step, seed, workdir = sys.argv[2:6]
        return _save_step_child(name, int(step), int(seed), workdir)
    workdir = sys.argv[1] if len(sys.argv) > 1 else "serve_smoke"
    os.makedirs(workdir, exist_ok=True)

    import numpy as np
    from PIL import Image

    d1 = save_step("m1", 1, 0, workdir)
    save_step("m2", 1, 7, workdir)
    print("checkpoints saved for tenants m1, m2", flush=True)

    # ephemeral port, then hand it to the subprocess (tiny race window —
    # acceptable in CI, and the server fails loudly if it loses it)
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    base = f"http://127.0.0.1:{port}"

    proc = subprocess.Popen([
        sys.executable, "-m", "p2p_tpu.cli.serve",
        "--http", f"127.0.0.1:{port}",
        "--tenant", "alias=m1,preset=facades,name=m1,dataset=synth,"
                    "image_size=16,ngf=4",
        "--tenant", "alias=m2,preset=facades,name=m2,dataset=synth,"
                    "image_size=16,ngf=4",
        "--workdir", workdir, "--max_batch", "2", "--dtype", "f32",
        "--linger_ms", "5", "--retry_delay_ms", "20",
    ], env=_child_env())   # the backend the caller's environment names

    def get(path, timeout=10):
        with urllib.request.urlopen(base + path, timeout=timeout) as r:
            return r.status, r.read()

    def post(path, data, timeout=60):
        req = urllib.request.Request(base + path, data=data,
                                     method="POST")
        try:
            with urllib.request.urlopen(req, timeout=timeout) as r:
                return r.status, r.read()
        except urllib.error.HTTPError as e:
            return e.code, e.read()

    try:
        deadline = time.time() + 300
        up = False
        while time.time() < deadline:
            if proc.poll() is not None:
                raise SystemExit(f"server died early: rc={proc.returncode}")
            try:
                st, _ = get("/healthz", timeout=2)
                if st == 200:
                    up = True
                    break
            except (urllib.error.URLError, ConnectionError, OSError):
                time.sleep(0.5)
        assert up, "server never became healthy"
        print("server healthy", flush=True)

        img = np.random.default_rng(3).integers(
            0, 256, (16, 16, 3), dtype=np.uint8)
        buf = io.BytesIO()
        Image.fromarray(img).save(buf, format="PNG")
        body = buf.getvalue()

        # -- phase 1: concurrent clients against both tenants, and a
        # hot-swap landing MID-TRAFFIC: every request must succeed
        results = []
        stop = threading.Event()

        def client(alias):
            while not stop.is_set():
                st, out = post(f"/v1/{alias}/translate", body)
                results.append((alias, st))
                if st == 200:
                    Image.open(io.BytesIO(out)).verify()
                time.sleep(0.01)

        clients = [threading.Thread(target=client, args=(a,), daemon=True)
                   for a in ("m1", "m2", "m1", "m2")]
        for c in clients:
            c.start()
        time.sleep(1.0)

        save_step("m1", 2, 1, workdir)  # new weights land on disk
        st, out = post("/admin/reload",
                       json.dumps({"tenant": "m1"}).encode())
        assert st == 200 and json.loads(out)["step"] == 2, (st, out)
        print("hot-swap m1 -> step 2 under traffic", flush=True)
        time.sleep(1.0)
        stop.set()
        for c in clients:
            c.join(60)
        n_ok = sum(1 for _, st in results if st == 200)
        assert n_ok == len(results) and n_ok > 20, (
            f"failed requests around the swap: "
            f"{[r for r in results if r[1] != 200]} of {len(results)}")
        print(f"phase 1 OK: {n_ok} concurrent requests, all 200, "
              "zero failures across the swap", flush=True)

        # -- phase 2: zero mid-serve recompiles, per tenant
        st, h = get("/healthz")
        h = json.loads(h)
        for alias in ("m1", "m2"):
            tstat = h["tenants"][alias]
            assert tstat["n_compiles"] == len(tstat["buckets"]), tstat
        assert h["tenants"]["m1"]["step"] == 2
        print("phase 2 OK: n_compiles == len(buckets) on both tenants",
              flush=True)

        # -- phase 3: corrupt-manifest swap rejected, old engine serves on
        save_step("m1", 3, 2, workdir)
        integ = f"{d1}.aux/3.integrity.json"
        m = json.load(open(integ))
        leaf = next(iter(m["leaves"]))
        m["leaves"][leaf]["crc32"] = (m["leaves"][leaf]["crc32"] + 1) \
            % (2 ** 32)
        json.dump(m, open(integ, "w"))
        st, out = post("/admin/reload",
                       json.dumps({"tenant": "m1", "step": 3}).encode())
        assert st == 409, (st, out)
        st, _ = post("/v1/m1/translate", body)
        assert st == 200, "old engine must keep serving after rejection"
        st, h = get("/healthz")
        assert json.loads(h)["tenants"]["m1"]["step"] == 2
        print("phase 3 OK: corrupt swap rejected (409), step 2 serving",
              flush=True)

        # -- phase 4: /metrics SLO series, tenant-tagged
        st, mtext = get("/metrics")
        mtext = mtext.decode()
        for needle in ("serve_request_latency_seconds",
                       "serve_queue_depth", "serve_shed_total",
                       "serve_batch_occupancy", "serve_http_requests_total",
                       'tenant="m1"', 'tenant="m2"'):
            assert needle in mtext, f"missing {needle} in /metrics"
        print("phase 4 OK: /metrics exposes the SLO series", flush=True)

        # -- phase 5: SIGTERM → graceful drain → exit 0
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=120)
        assert rc == 0, f"drain exit code {rc}"
        print("phase 5 OK: SIGTERM → graceful drain → exit 0", flush=True)
        print("http serve smoke OK", flush=True)
        return 0
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)


if __name__ == "__main__":
    sys.exit(main())
