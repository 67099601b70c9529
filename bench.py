"""Benchmark: training throughput (img/sec/chip) vs the north star
(BASELINE.json: >= 2000 img/s/chip @ 256^2 pix2pix on TPU).

Runs on a TPU only: without ``--dry-run`` a backend that is not a TPU is
an error (exit 2), never a shrunken CPU run printed under a device
metric's name.

Headline metric: the full jitted pix2pix train step (U-Net G + 70x70
PatchGAN D + L1, the 'facades'/'edges2shoes' preset family) on 256x256
synthetic pairs. BENCH_PRESET selects any other preset (e.g. 'reference'
for the heavy ExpandNetwork + multiscale-D + VGG workload).

Timing methodology: K train steps run inside ONE jitted ``lax.scan``
dispatch (build_multi_train_step) so per-call host dispatch overhead
amortizes away; calls are CHAINED (each consumes the previous state) and a
single host fetch of the final loss fences the whole chain, so the host
never syncs per step. The cost of that one trivial dispatch + fetch is
measured separately (``measure_rtt``) and subtracted. The mechanics live
in ``p2p_tpu.obs.timing`` (``StepTimer.chain`` + ``measure_rtt``), so this
file, the train loop, and the metrics stream all share ONE fenced
img/sec/chip definition.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

Env knobs: BENCH_PRESET, BENCH_BS (per-chip batch), BENCH_STEPS, BENCH_IMG;
BENCH_JSONL=<path> additionally appends the record (kind="bench") to that
metrics stream through the obs registry.

``--sweep`` runs the ten contract rows (headline, bs=1,
edges2shoes int8-delayed, cityscapes, pix2pixhd, vid2vid, the round-6
int8-multiscale-D and pallas-fusion rows, and the round-7 open-loop
serving row) and diffs each against the
last-recorded band, exiting nonzero on a >3% regression below the band
floor — the standing perf-regression gate (VERDICT r5 #7). New rows carry
``band: None`` until their first on-TPU recording lands in SWEEP_ROWS.
``--sweep --dry-run`` shrinks every row to toy dims and skips the band
check: a CPU-able plumbing test that each contract config still builds,
steps, and reports (CI runs it).

Every image-preset record additionally carries a fenced per-net ``phases``
breakdown (``_phase_breakdown``: G/D/C fwd+bwd ms via ``StepTimer.chain``,
one dispatch per net, outside the headline timing) so a lever's win — or
the remaining gap to the 2000 img/s north star — is attributable to its
net rather than only the headline number. ``BENCH_BREAKDOWN=0`` skips it.

``--infer`` is the standing INFERENCE headline row: the serving engine
(p2p_tpu.serve — AOT bucket-batched generator inference with pipelined
PNG output) on synthetic data, reported with the fenced breakdown
(end-to-end img/s, device img/s, encode overlap, compiles-per-bucket).
``--infer --dry-run`` is its CPU-able CI plumbing row.

``--chaos [SPEC]`` arms the fault-injection layer
(p2p_tpu.resilience.chaos) for the run. With ``--infer`` (default spec
``serve_write:1.0x2``) the first two output writes fail (then the seam
goes quiet), so the row measures throughput WITH the retry/recovery
machinery firing; ``chaos_injected``/``retries`` land in the record. The
resilience contract this mode stands guard over: injected faults at the
wrapped seams must cost retries, never correctness — the row must still
satisfy the bucket-compile contract and stay in band. (Probabilistic
specs like ``serve_write:0.2`` measure sustained-fault throughput but CAN
legitimately exhaust the 3-attempt retry budget on an unlucky streak —
that's the give-up-eventually contract, not a bug.)

``--chaos`` WITHOUT ``--infer`` (default spec ``nan@3x2``) is the
standing SENTINEL row: the train headline with the divergence sentinel
(p2p_tpu.resilience.health) classifying every step inside the timed
region at the trainer's exact delayed-read cost model, and the ``nan``
seam poisoning the targeted observations. The contract: the sentinel's
healthy-path overhead stays within the headline row's band (<1%) —
``sentinel`` {steps, spikes, nonfinite} lands in the record as proof the
path actually ran.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys


def _phase_breakdown(cfg, state, host_batch, dtype, scan_k, rtt) -> dict:
    """Fenced per-net (G/D/C) fwd+bwd timings — the attribution layer the
    sweep records carry so a lever's win (int8-D, Pallas fusion, ...) shows
    up against ITS net, not just the headline number.

    Each net gets its own jitted ``lax.scan`` of ``scan_k`` value_and_grad
    iterations (chained through the carry so XLA cannot hoist the loop
    body), timed with the same ``StepTimer.chain`` + RTT methodology as the
    headline — one fenced dispatch per net. Numbers are ms per iteration:
    ONE forward+backward of that net alone (the D figure is one D pass;
    the train step runs two — fake and real). They are attribution
    weights, not an additive decomposition of the step (the real step
    fuses cross-net work the isolated programs cannot)."""
    import jax
    import jax.numpy as jnp

    from p2p_tpu.obs import StepTimer, span
    from p2p_tpu.train.state import build_models
    from p2p_tpu.utils.images import ingest

    g, d, c = build_models(cfg, dtype)
    real_a = ingest(jnp.asarray(host_batch["input"]), dtype)
    real_b = ingest(jnp.asarray(host_batch["target"]), dtype)
    use_quant = cfg.model.int8_delayed

    g_vars = {"params": 0, "batch_stats": state.batch_stats_g}
    if use_quant:
        g_vars["quant"] = state.quant_g

    def g_loss(params, x):
        vars_ = dict(g_vars, params=params)
        out = g.apply(vars_, x, False)
        return jnp.mean(jnp.square(out.astype(jnp.float32)))

    d_vars = {"spectral": state.spectral_d}
    if use_quant:
        d_vars["quant"] = state.quant_d
    if cfg.model.split_d_pairs:
        pair = (real_a, real_b)
    else:
        pair = jnp.concatenate([real_a, real_b], axis=-1)

    def d_loss(params, x):
        preds = d.apply({"params": params, **d_vars}, x)
        return sum(jnp.mean(jnp.square(p.astype(jnp.float32)))
                   for p in jax.tree_util.tree_leaves(preds))

    c_vars = {"batch_stats": state.batch_stats_c}
    if use_quant and state.quant_c is not None:
        # net_c on the delayed-int8 path (int8_compression) reads its
        # stored scales like G/D do
        c_vars["quant"] = state.quant_c

    def c_loss(params, x):
        out = c.apply({"params": params, **c_vars}, x, False)
        return jnp.mean(jnp.square(out.astype(jnp.float32)))

    def perturb(x, eps):
        # thread the scan carry into the input so the loop body genuinely
        # depends on the previous iteration (XLA would hoist an invariant
        # body out of the while loop and time nothing)
        if isinstance(x, tuple):
            return (x[0] + eps.astype(x[0].dtype), x[1])
        return x + eps.astype(x.dtype)

    def timed_ms(name, loss_fn, params, x):
        # params/x enter as jit ARGUMENTS (not closure constants): the
        # program is value-independent, so it can hit the persistent XLA
        # cache across runs and never embeds weight blobs in the HLO
        def prog_fn(p, xx):
            def body(carry, _):
                val, grads = jax.value_and_grad(loss_fn)(
                    p, perturb(xx, carry * 1e-30))
                leaf = jax.tree_util.tree_leaves(grads)[0]
                return (val + leaf.reshape(-1)[0].astype(jnp.float32) * 0.0,
                        None)

            return jax.lax.scan(body, jnp.zeros((), jnp.float32), None,
                                length=scan_k)

        prog = jax.jit(prog_fn)
        with span(f"bench_phase_{name}_warmup"):
            out, _ = prog(params, x)
            float(out)                      # compile + fence
        t = StepTimer(batch_size=1)
        with span(f"bench_phase_{name}"), t.chain(steps=scan_k,
                                                  rtt=rtt) as ch:
            out, _ = prog(params, x)
            ch.fence(out)
        return round(t.elapsed / scan_k * 1000.0, 3)

    phases = {"g_ms": timed_ms("g", g_loss, state.params_g, real_a),
              "d_ms": timed_ms("d", d_loss, state.params_d, pair)}
    if cfg.model.use_compression_net:
        phases["c_ms"] = timed_ms("c", c_loss, state.params_c, real_b)
    return phases


def run_single(tiny: bool = False, with_sentinel: bool = False) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from p2p_tpu.core.config import get_preset
    from p2p_tpu.data.synthetic import synthetic_batch
    from p2p_tpu.models.vgg import load_vgg19_params
    from p2p_tpu.train.state import create_train_state
    from p2p_tpu.train.step import build_multi_train_step

    platform = jax.devices()[0].platform
    # Default headline: the int8-discriminator QAT step with DELAYED
    # (stored-scale) activation quantization — identical architecture/
    # losses to 'facades' (the bf16 number is one BENCH_PRESET=facades
    # away); trained-quality evidence for THIS path is the decayed
    # 40-epoch real-photo run metrics_facades_int8_decay.jsonl (README
    # "Round 3": final 22.21 dB / 0.769 SSIM / 0.63 VFID, best-in-decay
    # 23.75 / 0.794 / 0.398 — at the dynamic-path peak level).
    preset = os.environ.get("BENCH_PRESET", "facades_int8")
    cfg = get_preset(preset)
    facades_like = preset in ("facades", "facades_int8",
                              "facades_int8_full")
    # BENCH_IMG overrides to a square size; otherwise non-default presets
    # bench at their NATIVE dims (e.g. pix2pixhd 1024×512), facades at 256².
    if tiny:
        # --sweep --dry-run: toy dims proving the config builds and steps
        # (keep a rectangular extent when the preset has one — the HD
        # generators assume W > H)
        img, wid = 32, (64 if cfg.data.image_width else None)
    elif "BENCH_IMG" in os.environ or facades_like:
        img = int(os.environ.get("BENCH_IMG", "256"))
        wid = None
    else:
        img, wid = cfg.data.image_size, cfg.data.image_width
    bs = int(os.environ.get("BENCH_BS", "128" if facades_like else
                            str(cfg.data.batch_size)))
    scan_k = int(os.environ.get("BENCH_SCAN", "8"))
    n_calls = int(os.environ.get("BENCH_STEPS", "64")) // scan_k
    n_calls = max(n_calls, 2)
    if tiny:
        bs, scan_k, n_calls = 1, 2, 2
        cfg = cfg.replace(
            model=dataclasses.replace(
                cfg.model, ngf=8, ndf=8, num_D=min(cfg.model.num_D, 2),
                n_layers_D=2, n_blocks=min(cfg.model.n_blocks, 2)),
            data=dataclasses.replace(
                cfg.data, n_frames=min(cfg.data.n_frames, 2)),
            loss=dataclasses.replace(cfg.loss, lambda_vgg=0.0),
        )

    cfg = cfg.replace(
        data=dataclasses.replace(
            cfg.data, batch_size=bs, image_size=img, image_width=wid
        )
    )
    bench_int8 = os.environ.get("BENCH_INT8", "").lower()
    if bench_int8 in ("1", "d", "true", "on", "g"):
        # int8 discriminator on any preset; BENCH_INT8=g also quantizes
        # the generator trunk (ResNet families / U-Net encoder)
        both = bench_int8 == "g"
        cfg = cfg.replace(model=dataclasses.replace(
            cfg.model, int8=True, int8_generator=both))
        preset = preset + ("_i8gd" if both else "_i8d")
    if (os.environ.get("BENCH_DELAYED", "") == "1"
            and not cfg.model.int8_delayed):
        # delayed (stored-scale) activation quantization, ops/int8.py
        # (no-op suffix-skip when the preset already ships delayed)
        cfg = cfg.replace(model=dataclasses.replace(
            cfg.model, int8_delayed=True))
        preset = preset + "_ds"
    if os.environ.get("BENCH_THIN", "") == "1":
        # U-Net image head as the subpixel form (ModelConfig.thin_head)
        cfg = cfg.replace(model=dataclasses.replace(
            cfg.model, thin_head=True))
        preset = preset + "_th"
    if os.environ.get("BENCH_STEM", "") == "1":
        # U-Net k4-s2 stem as strided patches (ModelConfig.thin_stem)
        cfg = cfg.replace(model=dataclasses.replace(
            cfg.model, thin_stem=True))
        preset = preset + "_st"
    if os.environ.get("BENCH_HPAL", "") == "1":
        # thin head through the Pallas fused kernel (bypass the Mosaic
        # gate so runtime upgrades get re-probed — ops/conv.py)
        os.environ["P2P_HPAL_FORCE"] = "1"
        cfg = cfg.replace(model=dataclasses.replace(
            cfg.model, thin_head=True, head_pallas=True))
        preset = preset.removesuffix("_th") + "_hp"
    if os.environ.get("BENCH_SPLITD", ""):
        # feed D unconcatenated (a,b) pairs (ModelConfig.split_d_pairs) —
        # BENCH_SPLITD=0 forces concat on presets that default split
        split_on = os.environ["BENCH_SPLITD"] == "1"
        cfg = cfg.replace(model=dataclasses.replace(
            cfg.model, split_d_pairs=split_on))
        preset = preset + ("_splitd" if split_on else "_concatd")
    if os.environ.get("BENCH_MOM", ""):
        # low-precision Adam moment storage (OptimConfig.moment_dtype),
        # e.g. BENCH_MOM=bfloat16 — the bs=1 parameter-traffic lever
        cfg = cfg.replace(optim=dataclasses.replace(
            cfg.optim, moment_dtype=os.environ["BENCH_MOM"]))
        preset = preset + "_mom16"
    if os.environ.get("BENCH_UPSAMPLE", ""):
        # override the U-Net decoder upsample family (deconv|subpixel|resize)
        cfg = cfg.replace(model=dataclasses.replace(
            cfg.model, upsample_mode=os.environ["BENCH_UPSAMPLE"]))
        preset = preset + "_" + os.environ["BENCH_UPSAMPLE"]
    if os.environ.get("BENCH_I8DEC", "") == "1":
        # quantized subpixel decoder for the U-Net (QuantSubpixelDeconv)
        cfg = cfg.replace(model=dataclasses.replace(
            cfg.model, int8=True, int8_generator=True, int8_decoder=True))
        preset = preset + "_i8dec"
    if os.environ.get("BENCH_NORM", ""):
        # generator norm override — BENCH_NORM=pallas_instance routes the
        # norm→act(→residual) chains through the fused Pallas epilogue
        # (ops/pallas/norm_act.py; lax fallback off-TPU)
        val = os.environ["BENCH_NORM"]
        cfg = cfg.replace(model=dataclasses.replace(cfg.model, norm=val))
        preset = preset + {"pallas_instance": "_pnorm",
                           "instance": "_inorm"}.get(val, "_" + val)
    if os.environ.get("BENCH_NORMD", ""):
        # discriminator-side norm (ModelConfig.norm_d — pix2pixHD-paper D
        # layout; pallas_instance = fused norm+LeakyReLU epilogue)
        val = os.environ["BENCH_NORMD"]
        cfg = cfg.replace(model=dataclasses.replace(cfg.model, norm_d=val))
        preset = preset + {"pallas_instance": "_pnormd",
                           "instance": "_inormd"}.get(val, "_" + val + "d")
    dtype = jnp.bfloat16 if cfg.train.mixed_precision else None

    n_frames = cfg.data.n_frames
    # BENCH_U8=0 opts out of the uint8 batch contract (default ON — the
    # real pipeline ships uint8 and the steps normalize on device, so the
    # HBM-resident scan batches are uint8 too: 4× less input read traffic
    # per step; numerics pinned identical in tests/test_train.py)
    bench_u8 = os.environ.get("BENCH_U8", "1") == "1"
    host = synthetic_batch(batch_size=bs * max(n_frames, 1), size=img,
                           bits=cfg.model.quant_bits, width=wid,
                           dtype="uint8" if bench_u8 else "float32")
    if n_frames > 1:
        # video presets: NTHWC clips through the video step (the img/s
        # figure counts FRAMES — the per-chip pixel-throughput analogue)
        host = {k: v.reshape(bs, n_frames, *v.shape[1:])
                for k, v in host.items()}
    single = {k: jnp.asarray(v) for k, v in host.items()}
    batches = {
        k: jnp.asarray(np.broadcast_to(v, (scan_k,) + v.shape).copy())
        for k, v in host.items()
    }

    vgg_params = None
    if cfg.loss.lambda_vgg > 0:
        vgg_params = load_vgg19_params(
            jnp.bfloat16 if dtype is not None else jnp.float32
        )
    if n_frames > 1:
        from p2p_tpu.train.video_step import (
            build_multi_video_train_step,
            create_video_train_state,
        )

        state = create_video_train_state(cfg, jax.random.key(0), single,
                                         train_dtype=dtype)
        step = build_multi_video_train_step(
            cfg, vgg_params, train_dtype=dtype,
            unroll=int(os.environ.get("BENCH_UNROLL", "1")))
    else:
        state = create_train_state(cfg, jax.random.key(0), single,
                                   train_dtype=dtype)
        # BENCH_UNROLL: lax.scan unroll factor (default 1); >1 trades
        # compile time/code size for cross-step scheduling freedom
        step = build_multi_train_step(
            cfg, vgg_params, train_dtype=dtype,
            unroll=int(os.environ.get("BENCH_UNROLL", "1")))

    from p2p_tpu.obs import StepTimer, measure_rtt, span

    # cost of one trivial dispatch + host fetch (the chain's fence)
    rtt = measure_rtt()

    # warmup (compile) + fence
    with span("bench_warmup"):
        state, metrics = step(state, batches)
        float(metrics["loss_g"][-1])

    # --chaos: exercise the divergence sentinel at the trainer's exact
    # cost model — the PREVIOUS dispatch's per-step metrics are fetched
    # and classified while the next one runs (train/loop.py's delayed
    # read), INSIDE the timed region, so the row measures the healthy-
    # path overhead the headline band check stands guard over. The
    # 'nan' chaos seam poisons observations here exactly like the loop.
    sentinel = None
    sentinel_stats = {"steps": 0, "spikes": 0, "nonfinite": 0}
    if with_sentinel:
        from p2p_tpu.resilience.health import (
            DivergenceSentinel,
            poison_nan_observation,
        )

        sentinel = DivergenceSentinel()

        def sentinel_feed(metrics_dev):
            host = jax.device_get(metrics_dev)
            for i in range(scan_k):
                sentinel_stats["steps"] += 1
                # step = OBSERVED step count (1-based, warmup excluded):
                # the default nan@3x2 spec targets the first fetched
                # dispatch at every scan_k, not a train-step number that
                # would shift past the range at BENCH_SCAN=8
                m = poison_nan_observation(
                    sentinel_stats["steps"],
                    {k: float(v[i]) for k, v in host.items()})
                status = sentinel.classify(m)
                if status != "healthy":
                    key = ("nonfinite" if status == "diverged" else "spikes")
                    sentinel_stats[key] += 1

    # the chained fenced interval, minus RTT — StepTimer.chain is the
    # same accumulator the per-step tick() path feeds, so this number and
    # the train loop's are the one img/sec/chip definition
    timer = StepTimer(batch_size=bs * max(n_frames, 1))
    with span("bench_timed"), timer.chain(
            steps=scan_k * n_calls, rtt=rtt) as ch:
        pend = None
        for _ in range(n_calls):
            state, metrics = step(state, batches)
            if sentinel is not None:
                if pend is not None:
                    sentinel_feed(pend)
                pend = metrics
        if sentinel is not None and pend is not None:
            sentinel_feed(pend)
        ch.fence(metrics["loss_g"][-1])  # forces the whole chained sequence

    # per-net attribution breakdown (OUTSIDE the timed headline chain, so
    # the headline number is untouched); BENCH_BREAKDOWN=0 skips it. Video
    # presets keep headline-only records (their nets differ per step).
    phases = None
    if os.environ.get("BENCH_BREAKDOWN", "1") == "1" and n_frames == 1:
        phases = _phase_breakdown(cfg, state, host, dtype, scan_k, rtt)
        phases["step_ms"] = round(
            timer.elapsed / max(timer.intervals, 1) * 1000.0, 3)

    img_per_sec = timer.images_per_sec
    baseline = 2000.0  # BASELINE.json north_star: img/s/chip @ 256^2 pix2pix
    comparable = platform == "tpu" and img == 256 and preset in (
        "facades", "facades_int8", "edges2shoes_dp",
        # suffix order as generated above: INT8 → DELAYED → THIN → I8DEC
        "facades_int8_ds", "facades_int8_i8gd", "facades_int8_i8gd_ds",
        "facades_int8_i8dec", "facades_int8_ds_i8dec",
        "facades_int8_ds_th", "facades_int8_th", "facades_int8_hp",
    )
    dims = f"{img}x{wid}" if wid else f"{img}px"
    record = {
        "metric": f"train_throughput_{preset}_{platform}_{dims}_bs{bs}",
        "value": round(img_per_sec, 2),
        "unit": "img/sec/chip",
        "vs_baseline": round(img_per_sec / baseline, 4) if comparable else 0.0,
    }
    if sentinel is not None:
        record["sentinel"] = dict(sentinel_stats)
    if phases is not None:
        record["phases"] = phases
    if comparable:
        record["chip"] = jax.devices()[0].device_kind
    if os.environ.get("BENCH_JSONL"):
        # mirror the result into a metrics stream (same record, kind-tagged)
        from p2p_tpu.obs import JSONLSink, MetricsRegistry

        reg = MetricsRegistry()
        sink = JSONLSink(os.environ["BENCH_JSONL"])
        reg.add_sink(sink)
        reg.record({"kind": "bench", "rtt_sec": round(rtt, 6), **record},
                   force=True)
        sink.close()
    return record


# ---------------------------------------------------------------------------
# --infer: the standing inference headline row (docs/SERVING.md)
# ---------------------------------------------------------------------------

def run_infer(tiny: bool = False) -> dict:
    """Serving-engine throughput: AOT bucket-batched generator inference
    with pipelined PNG output (p2p_tpu.serve.InferenceEngine), reported
    with the fenced StepTimer breakdown — img/s end-to-end, device-only
    img/s, encode overlap, and compiles-per-bucket (must equal the bucket
    count: the bucketing contract this row stands guard over).

    Env knobs: BENCH_PRESET (default facades_int8 — same generator as the
    train headline), BENCH_BS (default 64 on TPU), BENCH_IMG, BENCH_STEPS
    (number of full batches; a half-size tail batch is always appended to
    exercise the bucket router), BENCH_INFER_DTYPE (bf16|f32, default
    bf16), BENCH_INFER_SAVE=0 to skip PNG output (pure device number).
    """
    import tempfile

    import jax

    from p2p_tpu.core.config import get_preset
    from p2p_tpu.data.synthetic import synthetic_batch
    from p2p_tpu.serve import InferenceEngine
    from p2p_tpu.train.state import create_infer_state

    platform = jax.devices()[0].platform
    preset = os.environ.get("BENCH_PRESET", "facades_int8")
    cfg = get_preset(preset)
    facades_like = preset in ("facades", "facades_int8",
                              "facades_int8_full")
    if tiny:
        img, wid = 32, (64 if cfg.data.image_width else None)
        bs, n_batches = 2, 2
        cfg = cfg.replace(model=dataclasses.replace(
            cfg.model, ngf=8, ndf=8, num_D=min(cfg.model.num_D, 2),
            n_layers_D=2, n_blocks=min(cfg.model.n_blocks, 2)))
    else:
        # same shape rule as run_single: BENCH_IMG forces square,
        # otherwise non-default presets serve at their NATIVE dims
        # (pix2pixhd 1024×512 — the HD generators assume W > H)
        if "BENCH_IMG" in os.environ or facades_like:
            img = int(os.environ.get("BENCH_IMG", "256"))
            wid = None
        else:
            img, wid = cfg.data.image_size, cfg.data.image_width
        bs = int(os.environ.get("BENCH_BS", "64"))
        n_batches = int(os.environ.get("BENCH_STEPS", "32"))
    dtype = os.environ.get("BENCH_INFER_DTYPE", "bf16")
    save = os.environ.get("BENCH_INFER_SAVE", "1") == "1"
    cfg = cfg.replace(data=dataclasses.replace(
        cfg.data, test_batch_size=bs, image_size=img, image_width=wid))

    tail = max(1, bs // 2)
    buckets = tuple(sorted({tail, bs}))
    u8 = cfg.data.uint8_pipeline
    host = synthetic_batch(batch_size=bs, size=img,
                           bits=cfg.model.quant_bits, width=wid,
                           dtype="uint8" if u8 else "float32")
    state = create_infer_state(cfg, jax.random.key(0), host)
    engine = InferenceEngine(cfg, state, buckets=buckets, dtype=dtype,
                             with_metrics=False)

    def batches():
        for _ in range(n_batches):
            yield host
        # the tail batch: routes to the smaller bucket, never a recompile
        yield {k: v[:tail] for k, v in host.items()}

    out_dir = tempfile.mkdtemp(prefix="bench_infer_") if save else None
    from p2p_tpu.obs import span

    with span("bench_infer"):
        stats, _ = engine.run(batches(), out_dir=out_dir)
    dims = f"{img}x{wid}" if wid else f"{img}px"
    record = {
        "metric": f"infer_throughput_{preset}_{dtype}_{platform}_{dims}_bs{bs}",
        "value": round(stats.img_per_sec, 2),
        "unit": "img/sec/chip",
        **stats.as_dict(),
    }
    # contract gate BEFORE the metrics mirror: a run that recompiled
    # mid-serve must not append its (broken) row to the standing stream —
    # and must fail under `python -O` too, so no bare assert
    if stats.n_compiles != len(buckets):
        raise RuntimeError(
            f"bucket contract broken: {stats.n_compiles} compiles for "
            f"{len(buckets)} buckets")
    if os.environ.get("BENCH_JSONL"):
        from p2p_tpu.obs import JSONLSink, MetricsRegistry

        reg = MetricsRegistry()
        sink = JSONLSink(os.environ["BENCH_JSONL"])
        reg.add_sink(sink)
        reg.record({"kind": "bench_infer", **record}, force=True)
        sink.close()
    return record


# ---------------------------------------------------------------------------
# --serve: the open-loop serving-latency row (docs/SERVING.md "HTTP API")
# ---------------------------------------------------------------------------

def run_serve(tiny: bool = False) -> dict:
    """Open-loop serving latency: synthetic clients submit requests on a
    FIXED arrival schedule (independent of completions — the open-loop
    discipline that exposes queueing delay closed-loop benchmarks hide)
    against the continuous batcher + shared dispatch loop + AOT bucket
    engine (p2p_tpu.serve.batcher/frontend — the exact serving stack
    behind the HTTP frontend, minus the socket so the row measures
    batching + inference, not urllib). Reports p50/p99 request latency
    (admission → response bytes ready), served img/sec, and the bucket
    occupancy the continuous batcher achieved — plus the standing
    compile contract (n_compiles == len(buckets), zero mid-serve).

    Env knobs: BENCH_PRESET (default facades_int8), BENCH_BS (largest
    bucket / group cap), BENCH_IMG, BENCH_SERVE_N (total requests),
    BENCH_SERVE_RATE (arrivals/sec; 0 = as-fast-as-possible burst),
    BENCH_INFER_DTYPE (bf16|f32).
    """
    import threading
    import time

    import jax
    import numpy as np

    from p2p_tpu.core.config import get_preset
    from p2p_tpu.data.synthetic import synthetic_batch
    from p2p_tpu.obs import MetricsRegistry
    from p2p_tpu.resilience.queue import BoundedRequestQueue
    from p2p_tpu.serve import (
        ContinuousBatcher,
        DispatchLoop,
        InferenceEngine,
        default_buckets,
    )
    from p2p_tpu.train.state import create_infer_state

    platform = jax.devices()[0].platform
    preset = os.environ.get("BENCH_PRESET", "facades_int8")
    cfg = get_preset(preset)
    if tiny:
        img, bs, n_req, rate = 32, 4, 24, 0.0
        cfg = cfg.replace(model=dataclasses.replace(
            cfg.model, ngf=8, ndf=8, num_D=min(cfg.model.num_D, 2),
            n_layers_D=2, n_blocks=min(cfg.model.n_blocks, 2)))
    else:
        img = int(os.environ.get("BENCH_IMG", "256"))
        bs = int(os.environ.get("BENCH_BS", "64"))
        n_req = int(os.environ.get("BENCH_SERVE_N", "1024"))
        rate = float(os.environ.get("BENCH_SERVE_RATE", "0"))
    dtype = os.environ.get("BENCH_INFER_DTYPE", "bf16")
    cfg = cfg.replace(data=dataclasses.replace(
        cfg.data, test_batch_size=bs, image_size=img, image_width=None))
    buckets = default_buckets(bs)
    u8 = cfg.data.uint8_pipeline
    host = synthetic_batch(batch_size=1, size=img,
                           bits=cfg.model.quant_bits,
                           dtype="uint8" if u8 else "float32")
    state = create_infer_state(cfg, jax.random.key(0), host)
    engine = InferenceEngine(cfg, state, buckets=buckets, dtype=dtype,
                             with_metrics=False)
    engine.warmup()

    reg = MetricsRegistry()
    queue = BoundedRequestQueue(max_depth=max(4 * bs, n_req),
                                registry=reg, tenant="bench")
    batcher = ContinuousBatcher(queue, buckets, group_cap=bs,
                                linger_s=0.002)
    payload = host["input"][0]
    latencies = []
    done = threading.Event()

    def deliver(reqs, pred, n_real):
        # the response isn't served until the bytes are host-side: one
        # batch D2H here makes the latency honest, like the HTTP
        # responder's fetch (PNG encode excluded — that's --infer's
        # encode_sec story)
        np.asarray(pred)
        now = time.monotonic()
        for r in reqs:
            latencies.append(now - r.enqueued_at)
        if len(latencies) >= n_req:
            done.set()

    loop = DispatchLoop(
        engine, batcher, decode=lambda req: req.payload, deliver=deliver,
        on_poison=lambda req, exc: None, registry=reg, tenant="bench",
        group_cap=bs)

    consumer_exc = []

    def consume():
        try:
            while not done.is_set():
                ready, _ = batcher.next_group(timeout=0.05)
                if ready:
                    loop.dispatch(ready)
        except BaseException as e:  # surface, don't stall done.wait(600)
            consumer_exc.append(e)
            done.set()

    consumer = threading.Thread(target=consume, name="bench-serve",
                                daemon=True)
    consumer.start()
    t0 = time.monotonic()
    for i in range(n_req):
        if rate > 0:
            target = t0 + i / rate
            while True:
                lag = target - time.monotonic()
                if lag <= 0:
                    break
                time.sleep(min(lag, 0.002))
        while batcher.submit(f"r{i}", payload=payload) is None:
            time.sleep(0.001)  # queue sized for n_req; near-unreachable
    if not done.wait(600):
        raise RuntimeError(
            f"serve bench stalled: {len(latencies)}/{n_req} completed")
    wall = max(time.monotonic() - t0, 1e-9)
    batcher.close()
    consumer.join(timeout=5.0)
    if consumer_exc:
        raise consumer_exc[0]

    if engine.n_compiles != len(buckets):
        raise RuntimeError(
            f"bucket contract broken: {engine.n_compiles} compiles for "
            f"{len(buckets)} buckets")
    lat_ms = np.asarray(latencies) * 1e3
    record = {
        "metric": f"serve_openloop_{preset}_{dtype}_{platform}_"
                  f"{img}px_bs{bs}",
        "value": round(n_req / wall, 2),
        "unit": "img/sec/chip",
        "p50_ms": round(float(np.percentile(lat_ms, 50)), 2),
        "p99_ms": round(float(np.percentile(lat_ms, 99)), 2),
        "n_requests": n_req,
        "rate": rate,
        "wall_sec": round(wall, 4),
        "occupancy_mean": round(loop.occupancy_mean, 4),
        "padded_images": loop.padded_images,
        "n_compiles": engine.n_compiles,
        "buckets": list(buckets),
    }
    if os.environ.get("BENCH_JSONL"):
        from p2p_tpu.obs import JSONLSink

        sink = JSONLSink(os.environ["BENCH_JSONL"])
        reg.add_sink(sink)
        reg.record({"kind": "bench_serve", **record}, force=True)
        sink.close()
    return record


# ---------------------------------------------------------------------------
# --sweep: the standing perf-regression gate (VERDICT r5 #7)
# ---------------------------------------------------------------------------

# The contract rows with their last-recorded bands (img/s/chip; pre-round
# chip sessions whose logs were deleted in PR 21 — the `benchmark` PR
# replaces them). A row regresses when it lands >3% below its band FLOOR —
# the band width itself is session-to-session drift, not regression.
# ``band: None`` = a new row whose band is pending its first on-TPU
# recording: the row runs and reports, the regression gate arms once the
# measured band is written here.
SWEEP_ROWS = [
    {"name": "headline_facades_int8_bs128", "env": {},
     "band": (1684.4, 1717.2)},
    {"name": "facades_int8_bs1", "env": {"BENCH_BS": "1"},
     "band": (217.0, 228.7)},
    {"name": "edges2shoes_int8_delayed",
     "env": {"BENCH_PRESET": "edges2shoes_dp", "BENCH_INT8": "1",
             "BENCH_DELAYED": "1"},
     "band": (1364.7, 1371.6)},
    {"name": "cityscapes_spatial",
     "env": {"BENCH_PRESET": "cityscapes_spatial"}, "band": (37.5, 37.9)},
    {"name": "pix2pixhd", "env": {"BENCH_PRESET": "pix2pixhd"},
     "band": (8.77, 8.81)},
    {"name": "vid2vid_temporal",
     "env": {"BENCH_PRESET": "vid2vid_temporal"}, "band": (200.3, 203.5)},
    # round-6 rows (ISSUE 6): int8 over the FULL 3-scale spectral-norm
    # multiscale D (the reference workload's D, delayed scales), and the
    # fused Pallas norm+act chains on the instance-norm ResNet family
    {"name": "reference_int8_multiD",
     "env": {"BENCH_PRESET": "reference", "BENCH_INT8": "1",
             "BENCH_DELAYED": "1"},
     "band": None},
    {"name": "cityscapes_pallas_fused",
     "env": {"BENCH_PRESET": "cityscapes_spatial",
             "BENCH_NORM": "pallas_instance"},
     "band": None},
    # round-8 row (ISSUE 14): FULL-model delayed int8 on the headline
    # facades config — the drained-worklist coverage set, now a FIRST-
    # CLASS preset (ISSUE 15: the former BENCH_INT8_FULL opt-out env
    # gate is gone, the measurement of record for the ROADMAP item-2
    # band decision rides every default sweep). Band-pending until
    # measured on-chip; the lint's train_step[facades_int8_full]
    # roofline row is its static twin.
    {"name": "facades_int8_full",
     "env": {"BENCH_PRESET": "facades_int8_full"}, "band": None},
    # round-7 row (ISSUE 12): the open-loop serving-latency row — the
    # continuous-batching stack behind the HTTP frontend (run_serve);
    # value is served img/sec, the record carries p50/p99 request latency
    {"name": "serve_openloop_continuous_batch", "env": {},
     "mode": "serve", "band": None},
]

REGRESSION_TOLERANCE = 0.03


def run_sweep(dry_run: bool = False) -> int:
    """Run every contract row; return a nonzero exit code naming each row
    that lands >3% under its band floor. ``dry_run`` shrinks the rows to
    toy dims (CPU-able) and checks plumbing only."""
    import jax

    check_bands = not dry_run   # main() refused a non-TPU real run
    # the sweep owns these knobs; a stray env override would silently
    # bench a different contract than the bands record
    owned = ("BENCH_PRESET", "BENCH_BS", "BENCH_INT8", "BENCH_DELAYED",
             "BENCH_IMG", "BENCH_NORM", "BENCH_NORMD", "BENCH_BREAKDOWN")
    saved = {k: os.environ.pop(k) for k in owned if k in os.environ}
    if saved:
        print(f"note: ignoring {sorted(saved)} for --sweep",
              file=sys.stderr)
    from p2p_tpu.analysis.hlo_cost import roofline_row_for

    def sweep_roofline(row):
        """The perf_budget.json row statically modeling this sweep row's
        program, None when the traced set doesn't cover it. Keys on the
        FULL row env, not just the preset: BENCH_INT8 switches the U-Net
        family to the delayed-int8 program, and the plain cityscapes row
        runs the reference norm — only its BENCH_NORM=pallas_instance
        variant matches the fused traced row."""
        if row.get("mode") == "serve":
            return None          # the traced set models train/eval steps
        env = row["env"]
        preset = env.get("BENCH_PRESET", "facades_int8")
        if env.get("BENCH_INT8"):
            return (roofline_row_for("facades_int8")
                    if preset in ("facades", "edges2shoes_dp") else None)
        if preset == "cityscapes_spatial" and not env.get("BENCH_NORM"):
            return None          # reference-norm program, not the fused one
        return roofline_row_for(preset)

    regressions = []
    results = []
    try:
        for row in SWEEP_ROWS:
            os.environ.update(row["env"])
            runner = (run_serve if row.get("mode") == "serve"
                      else run_single)
            try:
                rec = runner(tiny=dry_run)
            finally:
                for k in row["env"]:
                    os.environ.pop(k, None)
            band = row["band"]
            status = "ok" if band is not None else "ok (band pending)"
            if not (rec["value"] > 0):
                status = "failed"
                regressions.append((row["name"], rec["value"],
                                    band[0] if band else 0.0))
            elif check_bands and band is not None:
                lo = band[0]
                floor = lo * (1 - REGRESSION_TOLERANCE)
                if rec["value"] < floor:
                    status = f"REGRESSION (<{floor:.1f})"
                    regressions.append((row["name"], rec["value"], lo))
            entry = {"row": row["name"], "value": rec["value"],
                     "band": list(band) if band is not None else None,
                     "status": status, "metric": rec["metric"],
                     # the perf_budget.json row statically modeling this
                     # config's program family (ISSUE 13): the measured
                     # number and its cost-model bound travel together
                     "roofline": sweep_roofline(row)}
            if "p50_ms" in rec:
                # the serving row's latency tail rides the sweep record
                entry["latency_ms"] = {"p50": rec["p50_ms"],
                                       "p99": rec["p99_ms"]}
            if "phases" in rec:
                # the per-net attribution breakdown rides every sweep row
                # (ISSUE 6 satellite — see _phase_breakdown)
                entry["phases"] = rec["phases"]
            results.append(entry)
            print(json.dumps(results[-1]), flush=True)
    finally:
        os.environ.update(saved)
    print(json.dumps({
        "kind": "bench_sweep", "dry_run": dry_run,
        "bands_checked": check_bands, "rows": len(results),
        "regressions": [r[0] for r in regressions],
    }))
    if regressions:
        for name, val, lo in regressions:
            print(f"REGRESSION: {name} = {val} vs band floor {lo} "
                  f"(-{(1 - val / lo) * 100:.1f}%)", file=sys.stderr)
        return 1
    return 0


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--sweep", action="store_true",
                    help="run all ten contract rows and fail "
                         "on >3% regression below the recorded band "
                         "(band-less rows report without gating)")
    ap.add_argument("--infer", action="store_true",
                    help="bench the serving engine instead of the train "
                         "step: AOT bucket-batched inference + pipelined "
                         "PNG output, fenced breakdown (docs/SERVING.md)")
    ap.add_argument("--serve", action="store_true",
                    help="bench the SERVING STACK open-loop: continuous "
                         "batcher + dispatch loop + engine under a fixed "
                         "arrival schedule; reports p50/p99 request "
                         "latency + served img/sec (docs/SERVING.md)")
    ap.add_argument("--chaos", nargs="?", const="__default__",
                    default=None, metavar="SPEC",
                    help="arm fault injection for the run. With --infer "
                         "(default spec 'serve_write:1.0x2') the row "
                         "measures throughput with retries firing; alone "
                         "(default spec 'nan@3x2') it runs the TRAIN "
                         "headline with the divergence sentinel classifying "
                         "every step at the trainer's delayed-read cost "
                         "model — the standing sentinel-overhead row "
                         "(docs/RESILIENCE.md)")
    ap.add_argument("--dry-run", action="store_true",
                    help="with --sweep/--infer/--chaos: toy dims, plumbing "
                         "check only (CPU-able; no band comparison)")
    args = ap.parse_args(argv)
    import jax

    from p2p_tpu.core.cache import enable_compilation_cache

    platform = jax.devices()[0].platform
    if not args.dry_run and platform != "tpu":
        print(f"bench.py measures a TPU; the backend here is {platform!r}. "
              "Use --dry-run for the CPU plumbing check.", file=sys.stderr)
        return 2
    enable_compilation_cache()
    chaos_counts = None
    if args.chaos:
        from p2p_tpu.resilience import ChaosMonkey, install_chaos

        spec = args.chaos
        if spec == "__default__":
            spec = "serve_write:1.0x2" if args.infer else "nan@3x2"
        monkey = ChaosMonkey.from_spec(spec)
        install_chaos(monkey)
        chaos_counts = monkey.counts
    if args.serve:
        rec = run_serve(tiny=args.dry_run)
        if chaos_counts is not None:
            rec["chaos_injected"] = chaos_counts()
        print(json.dumps(rec))
        return 0
    if args.infer:
        rec = run_infer(tiny=args.dry_run)
        if chaos_counts is not None:
            from p2p_tpu.obs import get_registry

            rec["chaos_injected"] = chaos_counts()
            rec["retries"] = int(
                get_registry().total("retry_attempts_total"))
        print(json.dumps(rec))
        return 0
    if args.sweep:
        return run_sweep(dry_run=args.dry_run)
    # plain train row; --chaos additionally runs the sentinel at the
    # trainer's cost model and reports what it classified/injected
    rec = run_single(tiny=args.dry_run,
                     with_sentinel=chaos_counts is not None)
    if chaos_counts is not None:
        rec["chaos_injected"] = chaos_counts()
    print(json.dumps(rec))
    return 0


if __name__ == "__main__":
    sys.exit(main())
