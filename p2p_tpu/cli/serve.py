"""Serving CLI — directory-watching and HTTP frontends over the engine.

Two transports, ONE hardened request lifecycle (p2p_tpu/serve/frontend.py
— bounded queue, load shedding, deadlines, decode-retry with backoff,
poison quarantine, bucket-occupancy accounting):

**Directory mode** (default): ``python -m p2p_tpu.cli.serve`` watches a
directory of request images (raw files are the "RPC": drop an image in,
get its translation out), groups arrivals into micro-batches (up to
``--max_batch``, lingering at most ``--linger_ms`` for stragglers), pads
each group to an AOT-compiled bucket, and writes predictions named after
their inputs. ``--once`` processes the directory's current contents and
exits — the CI smoke mode.

**HTTP mode** (``--http HOST:PORT``): the network-native frontend
(p2p_tpu/serve/server.py) — ``POST /v1/{model}/translate`` with an image
body returns the translated PNG; ``/healthz``; Prometheus ``/metrics``;
``POST /admin/reload`` hot-swaps a tenant's weights with zero downtime.
``--tenant`` (repeatable) makes N models resident in this one process,
each with its own engine and bucket programs, sharing the persistent
compilation cache; requests are batched CONTINUOUSLY across concurrent
in-flight connections (serve/batcher.py). SIGTERM drains gracefully
(stop accepting → run queues down → exit 0). Full API + runbook:
docs/SERVING.md.

Request semantics per preset family (same as eval — SURVEY Q10): with a
compression net the request image is the TARGET (G runs from its
quantized compressed form); plain pix2pix presets treat it as the INPUT.

Engine policies (params-only restore, buckets, bf16/frozen-int8 dtype,
TP mesh, persistent compilation cache) are shared with cli/infer.py —
see docs/SERVING.md.

Hardening (p2p_tpu.resilience, docs/RESILIENCE.md): the request queue is
BOUNDED (``--max_queue``; overflow arrivals are shed and counted), each
request carries a deadline (``--deadline_ms``; expired requests are
dropped at dispatch, not served late), decode failures retry with backoff
up to ``--max_attempts`` and then the file is MOVED to a quarantine dir
(``--quarantine_dir``, default ``<input_dir>/failed``) so one poison
input can never wedge the server, and predictions are written atomically
(temp + rename — serve/io.py). Over HTTP the same ladder answers in
status codes: shed → 429, deadline → 504, poison → 422, draining → 503.
``--chaos``/``P2P_CHAOS`` inject faults at the decode/write seams to
rehearse all of the above.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

from p2p_tpu.serve.frontend import default_buckets  # noqa: F401 — re-export


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description="p2p_tpu serving frontend")
    p.add_argument("--preset", type=str, default="reference")
    p.add_argument("--name", type=str, default=None,
                   help="training name (checkpoint subdir; default preset)")
    p.add_argument("--dataset", type=str, default=None)
    p.add_argument("--step", type=int, default=None,
                   help="checkpoint step to serve (default: latest)")
    p.add_argument("--workdir", type=str, default=".")
    p.add_argument("--input_dir", type=str, default=None,
                   help="directory mode's request directory: image files "
                        "dropped here are served in arrival order "
                        "(required unless --http)")
    p.add_argument("--out", type=str, default=None,
                   help="prediction dir (default <input_dir>_out)")
    p.add_argument("--image_size", type=int, default=None)
    p.add_argument("--ngf", type=int, default=None)
    p.add_argument("--n_blocks", type=int, default=None)
    p.add_argument("--once", action="store_true",
                   help="serve the directory's current contents, drain, "
                        "exit (CI smoke mode)")
    p.add_argument("--max_requests", type=int, default=None,
                   help="exit after this many served requests (watch mode)")
    p.add_argument("--max_batch", type=int, default=16,
                   help="micro-batch cap (also the largest default bucket)")
    p.add_argument("--linger_ms", type=float, default=50.0,
                   help="max wait for stragglers before dispatching a "
                        "partial micro-batch")
    p.add_argument("--poll_ms", type=float, default=200.0,
                   help="directory scan cadence in watch mode")
    p.add_argument("--buckets", type=str, default=None,
                   help="comma-separated batch buckets (default: powers of "
                        "two up to --max_batch)")
    p.add_argument("--dtype", type=str, default="bf16",
                   choices=["bf16", "f32"])
    p.add_argument("--ema_decay", type=float, default=None,
                   help="the checkpoint was trained with --ema_decay: "
                        "restore the EMA generator weights and serve the "
                        "SMOOTHED G (bitwise == raw at decay 0)")
    p.add_argument("--mesh", type=str, default=None,
                   help="serving mesh: positional 'data,spatial,time"
                        "[,model]' or named 'axis=size,...'")
    p.add_argument("--tp_min_ch", type=int, default=None)
    p.add_argument("--io_threads", type=int, default=4)
    p.add_argument("--compilation_cache", type=str, default=None,
                   metavar="DIR")
    # --- network frontend (docs/SERVING.md "HTTP API") -------------------
    p.add_argument("--http", type=str, default=None, metavar="HOST:PORT",
                   help="serve over HTTP instead of a watched directory "
                        "(e.g. '0.0.0.0:8000'; ':0' binds an ephemeral "
                        "port). POST /v1/<tenant>/translate, /healthz, "
                        "/metrics, POST /admin/reload")
    p.add_argument("--tenant", action="append", default=None,
                   metavar="SPEC",
                   help="HTTP mode: make a model resident, repeatable. "
                        "SPEC is comma-separated key=value overriding the "
                        "base flags, e.g. 'alias=hd,preset=pix2pixhd,"
                        "name=run3,step=2000' (keys: alias preset name "
                        "dataset step image_size ngf n_blocks ema_decay). "
                        "Default: one tenant from the base flags")
    p.add_argument("--drain_timeout", type=float, default=30.0,
                   help="HTTP mode: max seconds after SIGTERM to run the "
                        "queues down before stragglers are answered 503")
    p.add_argument("--tenant_quota", type=int, default=None,
                   help="HTTP mode: max in-flight requests PER TENANT "
                        "(admitted, not yet answered); arrivals beyond "
                        "it get 429 + serve_quota_rejected_total — the "
                        "fairness cap so one tenant's burst cannot "
                        "starve the other tenants' queue slots "
                        "(default: unlimited)")
    # --- resilience knobs (docs/RESILIENCE.md) ---------------------------
    p.add_argument("--max_queue", type=int, default=512,
                   help="request queue depth cap; overflow arrivals are "
                        "SHED (counted, never served) — bounded memory "
                        "and bounded worst-case latency under overload")
    p.add_argument("--deadline_ms", type=float, default=0.0,
                   help="per-request deadline from arrival; requests "
                        "older than this at dispatch time are dropped "
                        "(0 = no deadline)")
    p.add_argument("--max_attempts", type=int, default=3,
                   help="decode attempts per request before the file is "
                        "moved to the quarantine dir (HTTP: before the "
                        "request is answered 422)")
    p.add_argument("--retry_delay_ms", type=float, default=1000.0,
                   help="base delay between decode attempts (a file still "
                        "being copied in gets this grace window, with "
                        "exponential backoff)")
    p.add_argument("--quarantine_dir", type=str, default=None,
                   help="poison inputs land here after --max_attempts "
                        "failed decodes (default <input_dir>/failed)")
    p.add_argument("--chaos", type=str, default=None, metavar="SPEC",
                   help="arm fault injection, e.g. 'decode:0.3' or "
                        "'serve_write:0.2x5' (p2p_tpu.resilience.chaos; "
                        "P2P_CHAOS env works too)")
    return p


def _build_config(args, overrides=None):
    """One tenant's Config from the base flags plus optional per-tenant
    SPEC overrides ({key: str})."""
    import dataclasses

    from p2p_tpu.cli import apply_overrides as over
    from p2p_tpu.core.config import get_preset

    ov = dict(overrides or {})
    preset = ov.get("preset", args.preset)
    cfg = get_preset(preset)

    def _get(key, cast, default):
        if key in ov:
            return cast(ov[key])
        return default

    data = over(cfg.data,
                dataset=_get("dataset", str, args.dataset),
                image_size=_get("image_size", int, args.image_size))
    model = over(cfg.model, ngf=_get("ngf", int, args.ngf),
                 n_blocks=_get("n_blocks", int, args.n_blocks))
    health = over(cfg.health,
                  ema_decay=_get("ema_decay", float, args.ema_decay))
    name = _get("name", str, args.name) or cfg.name
    return dataclasses.replace(cfg, data=data, model=model, health=health,
                               name=name)


def _parse_tenant_spec(spec: str):
    """'alias=hd,preset=pix2pixhd,step=2000' → (alias, {key: value})."""
    allowed = {"alias", "preset", "name", "dataset", "step", "image_size",
               "ngf", "n_blocks", "ema_decay"}
    kv = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        k, eq, v = part.partition("=")
        if not eq or k not in allowed:
            raise ValueError(
                f"bad --tenant entry {part!r} (allowed keys: "
                f"{sorted(allowed)})")
        kv[k] = v
    alias = kv.pop("alias", None) or kv.get("name") or kv.get("preset")
    if not alias:
        raise ValueError(f"--tenant {spec!r} needs an alias= (or name=/"
                         "preset= to derive one)")
    return alias, kv


def _engine_kw(args, buckets):
    from p2p_tpu.cli.infer import _parse_mesh

    return dict(
        buckets=buckets, dtype=args.dtype, mesh=_parse_mesh(args.mesh),
        tp_min_ch=args.tp_min_ch, with_metrics=False,
        compilation_cache_dir=args.compilation_cache,
        io_workers=args.io_threads,
    )


def _serve_http(args, buckets) -> int:
    """The network frontend: N resident tenants, continuous batching,
    hot-swap, graceful drain (p2p_tpu/serve/server.py)."""
    from p2p_tpu.obs import get_registry
    from p2p_tpu.resilience import ChaosMonkey, install_chaos
    from p2p_tpu.serve.server import ServeApp, run_server
    from p2p_tpu.serve.tenancy import Tenant, checkpoint_dir

    host, _, port = args.http.rpartition(":")
    host = host or "0.0.0.0"
    try:
        port = int(port)
    except ValueError:
        print(f"--http wants HOST:PORT, got {args.http!r}",
              file=sys.stderr)
        return 2
    reg = get_registry()
    try:
        specs = ([_parse_tenant_spec(s) for s in args.tenant]
                 if args.tenant else [(None, {})])
    except ValueError as e:
        print(str(e), file=sys.stderr)
        return 2
    prev_chaos = None
    if args.chaos:
        prev_chaos = install_chaos(
            ChaosMonkey.from_spec(args.chaos, registry=reg))
    app = ServeApp(
        registry=reg, io_threads=args.io_threads,
        max_queue=args.max_queue, deadline_ms=args.deadline_ms,
        linger_ms=args.linger_ms, group_cap=args.max_batch,
        max_attempts=args.max_attempts,
        retry_delay_ms=args.retry_delay_ms,
        tenant_quota=args.tenant_quota)
    try:
        for alias, ov in specs:
            cfg = _build_config(args, ov)
            alias = alias or cfg.name
            if alias in app.tenants:
                # caught BEFORE the (expensive) restore + AOT warmup —
                # two specs deriving the same alias is a flag error
                print(f"duplicate tenant alias {alias!r} — give each "
                      "--tenant a distinct alias=", file=sys.stderr)
                return 2
            step = int(ov["step"]) if "step" in ov else args.step
            t0 = time.perf_counter()
            try:
                tenant = Tenant(
                    alias, cfg, checkpoint_dir(cfg, args.workdir),
                    step=step, registry=reg, **_engine_kw(args, buckets))
            except (FileNotFoundError, ValueError) as e:
                print(f"tenant {alias!r}: {e}", file=sys.stderr)
                return 1
            tenant.warmup()
            app.add_tenant(tenant)
            print(f"tenant {alias!r}: checkpoint step {tenant.step}, "
                  f"{len(tenant.engine.buckets)} bucket programs in "
                  f"{time.perf_counter() - t0:.2f}s "
                  f"(buckets {list(tenant.engine.buckets)})", flush=True)
        return run_server(app, host, port,
                          drain_timeout_s=args.drain_timeout)
    finally:
        if args.chaos:
            install_chaos(prev_chaos)


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    from p2p_tpu.core.cache import enable_compilation_cache

    enable_compilation_cache(args.compilation_cache)

    buckets = ([int(b) for b in args.buckets.split(",")] if args.buckets
               else default_buckets(args.max_batch))
    if args.http:
        return _serve_http(args, buckets)
    if not args.input_dir:
        print("--input_dir is required in directory mode (or pass --http)",
              file=sys.stderr)
        return 2

    from p2p_tpu.data.generate import is_image_file
    from p2p_tpu.data.pipeline import load_image
    from p2p_tpu.serve import engine_from_checkpoint
    from p2p_tpu.serve.frontend import DispatchLoop
    from p2p_tpu.serve.tenancy import checkpoint_dir, serving_sample_batch

    cfg = _build_config(args)
    if cfg.data.n_frames > 1:
        print("serve covers image presets; use cli/infer.py for video",
              file=sys.stderr)
        return 2

    # the request image drives the input slot wherever the input's extent
    # differs from the target's (model.scale > 1)
    h, w = cfg.input_hw
    as_uint8 = cfg.data.uint8_pipeline

    def decode_path(path):
        # eval semantics: the request image drives whichever slot the
        # preset reads (target for compression-net presets, input
        # otherwise); the engine's batch spec names the keys it compiled.
        # The `decode` chaos seam lives HERE, not in load_image — serving
        # has retry/quarantine around this call; training decode fails
        # fast and must never see injected faults.
        from p2p_tpu.resilience.chaos import chaos_point

        chaos_point("decode")
        if cfg.model.label_classes:
            from p2p_tpu.data.pipeline import load_label_map

            return load_label_map(path, h, w)
        return load_image(path, h, w, as_uint8=as_uint8)

    try:
        engine, step = engine_from_checkpoint(
            cfg, checkpoint_dir(cfg, args.workdir),
            serving_sample_batch(cfg),
            step=args.step, **_engine_kw(args, buckets))
    except FileNotFoundError as e:
        print(str(e), file=sys.stderr)
        return 1
    t0 = time.perf_counter()
    engine.warmup()
    print(f"serving checkpoint step {step}: {len(engine.buckets)} bucket "
          f"programs compiled in {time.perf_counter() - t0:.2f}s "
          f"(buckets {list(engine.buckets)})", flush=True)

    out_dir = args.out or args.input_dir.rstrip("/") + "_out"
    os.makedirs(out_dir, exist_ok=True)
    from p2p_tpu.obs import get_registry
    from p2p_tpu.resilience import (
        BoundedRequestQueue,
        ChaosMonkey,
        Quarantine,
        install_chaos,
    )

    reg = get_registry()
    prev_chaos = None
    if args.chaos:
        prev_chaos = install_chaos(
            ChaosMonkey.from_spec(args.chaos, registry=reg))
    # serve-side counters are tenant-tagged even in single-model directory
    # mode (tenant = the model's name), so dashboards aggregate the two
    # frontends identically and the summary attributes failures per model
    tenant = cfg.name
    queue = BoundedRequestQueue(
        max_depth=args.max_queue,
        deadline_s=(args.deadline_ms / 1e3) if args.deadline_ms > 0 else None,
        registry=reg, tenant=tenant,
    )
    quarantine = Quarantine(
        args.quarantine_dir or os.path.join(args.input_dir, "failed"),
        registry=reg, tenant=tenant,
    )
    from p2p_tpu.serve import AsyncImageWriter

    # fail_fast=False: a poison OUTPUT path (directory squatting on the
    # target name, dead volume) is recorded + counted, never fatal — the
    # write-side analog of decode quarantine
    writer = AsyncImageWriter(args.io_threads, fail_fast=False)
    retry_delay = args.retry_delay_ms / 1e3
    seen = set()

    # requests queue as NAMES (BoundedRequestQueue of file names); decode
    # happens per micro-batch at dispatch time (a 10k-file backlog must
    # not be decoded into host RAM — or delay the first response — before
    # the first batch ships). The dispatch/decode-retry/quarantine
    # mechanics live in the shared DispatchLoop (serve/frontend.py);
    # the callbacks below are the directory frontend's POLICY.
    def decode_req(req):
        return decode_path(os.path.join(args.input_dir, req.name))

    def deliver(reqs, pred, n_real):
        paths = [os.path.join(out_dir,
                              os.path.splitext(req.name)[0] + ".png")
                 for req in reqs]
        writer.submit_batch(pred, paths)

    def on_poison(req, e):
        path = os.path.join(args.input_dir, req.name)
        dest = quarantine.quarantine(
            path, f"{req.attempts} failed decodes; last: {e!r}")
        print(f"WARNING: quarantined request {req.name!r} "
              f"after {req.attempts} failed decodes → "
              f"{dest or 'GONE'}: {e}",
              file=sys.stderr, flush=True)

    def on_expired(req):
        print(f"note: request {req.name!r} exceeded its "
              f"{args.deadline_ms:.0f} ms deadline — dropped",
              file=sys.stderr, flush=True)

    def on_retry_shed(req):
        # dropping the name from `seen` lets a later, quieter scan
        # re-offer the file instead of stranding it unserved
        seen.discard(req.name)
        print(f"WARNING: queue full — decode retry for "
              f"{req.name!r} shed; the file stays in the "
              "input dir for a later scan",
              file=sys.stderr, flush=True)

    loop = DispatchLoop(
        engine, queue, decode=decode_req, deliver=deliver,
        on_poison=on_poison, on_expired=on_expired,
        on_retry_shed=on_retry_shed, max_attempts=args.max_attempts,
        retry_delay_s=retry_delay, registry=reg, tenant=tenant,
        group_cap=args.max_batch,
    )

    def scan():
        """Enqueue new arrivals; a full queue sheds them (counted). A
        shed arrival is dropped from `seen` so a later, quieter scan can
        re-offer the file — under transient overload shedding defers
        service rather than permanently denying it (watch mode; --once
        scans exactly once, so its sheds are final)."""
        try:
            entries = sorted(os.listdir(args.input_dir))
        except FileNotFoundError:
            return 0
        fresh = 0
        shed_now = 0
        for f in entries:
            if f in seen or not is_image_file(f):
                continue
            seen.add(f)
            if queue.offer(f):
                fresh += 1
            else:
                seen.discard(f)
                shed_now += 1
        if shed_now:
            print(f"WARNING: queue full ({args.max_queue}) — shed "
                  f"{shed_now} arrivals (files stay in the input dir for "
                  "a later scan)", file=sys.stderr, flush=True)
        return fresh

    try:
        scan()
        if args.once:
            loop.drain()
            while len(queue):    # wait out retry-backoff windows, then finish
                time.sleep(min(retry_delay / 2, 0.25))
                loop.drain()
        else:
            try:
                linger_start = time.perf_counter() if len(queue) else None
                while (args.max_requests is None
                       or loop.served < args.max_requests):
                    if len(queue) >= args.max_batch or (
                        len(queue)
                        and linger_start is not None
                        and (time.perf_counter() - linger_start) * 1e3
                        >= args.linger_ms
                    ):
                        loop.drain()
                        linger_start = None
                    time.sleep(args.poll_ms / 1e3 if not len(queue) else
                               args.linger_ms / 1e3)
                    scan()
                    if len(queue) and linger_start is None:
                        linger_start = time.perf_counter()
            except KeyboardInterrupt:
                loop.drain()
        n_written = writer.drain()
        writer.close()
        for path, err in writer.write_errors:
            print(f"WARNING: prediction write failed permanently for "
                  f"{path!r}: {err}", file=sys.stderr, flush=True)
    finally:
        if args.chaos:
            # disarm even on a crashed serve: chaos is process-global and
            # in-process callers (tests) must not inherit the fault spec
            install_chaos(prev_chaos)
    wall = time.perf_counter() - t0

    occ = loop.occupancy_mean
    print(json.dumps({
        "kind": "serve_summary", "tenant": tenant, "served": loop.served,
        "written": n_written,
        "out_dir": out_dir, "buckets": list(engine.buckets),
        "n_compiles": engine.n_compiles,
        "encode_sec": round(writer.encode_sec, 4),
        "wall_sec": round(wall, 4),
        "shed": queue.shed_count,
        "deadline_expired": queue.expired_count,
        "quarantined": quarantine.count,
        "write_failures": len(writer.write_errors),
        "decode_retries": loop.decode_retries,
        "write_retries": int(reg.counter(
            "retry_attempts_total", seam="serve_write").value),
        "chaos_injected": int(reg.total("chaos_injected_total")),
        "batch_occupancy_mean": round(occ, 4) if occ is not None else None,
        "padded_images": loop.padded_images,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
