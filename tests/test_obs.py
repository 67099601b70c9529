"""The telemetry subsystem (p2p_tpu.obs): registry aggregation math, JSONL
crash-safety, span nesting + Perfetto export, in-jit NaN sentinels on CPU,
retrace-watchdog compile counting, check_finite event emission, chained
StepTimer math, manifest provenance, and the Trainer wiring."""

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from p2p_tpu import obs
from p2p_tpu.core.config import list_presets
from p2p_tpu.obs.registry import combine_host_snapshots


# ---------------------------------------------------------------- registry
def test_registry_metric_factories_are_idempotent():
    r = obs.MetricsRegistry()
    c1 = r.counter("images", split="train")
    c1.inc(5)
    r.counter("images", split="train").inc(3)
    assert r.counter("images", split="train").value == 8
    # different tags → different metric
    assert r.counter("images", split="eval").value == 0


def test_histogram_math():
    r = obs.MetricsRegistry()
    h = r.histogram("lat")
    for v in (0.001, 0.002, 0.004, 1.0):
        h.observe(v)
    assert h.count == 4
    assert h.sum == pytest.approx(1.007)
    assert h.min == pytest.approx(0.001)
    assert h.max == pytest.approx(1.0)
    assert h.mean == pytest.approx(1.007 / 4)
    # p50 of {1,2,4,1000} ms sits in the couple-of-ms buckets, far from max
    assert h.quantile(0.5) < 0.02


def test_ewma_rate_tracks_event_rate():
    t = [0.0]
    e = obs.registry.EWMARate("r", halflife_s=1.0, clock=lambda: t[0])
    e.mark(10)            # first mark only sets the epoch
    for _ in range(50):   # 10 events per 0.1 s → 100/s
        t[0] += 0.1
        e.mark(10)
    assert e.rate == pytest.approx(100.0, rel=0.05)


def test_cross_host_combine_math():
    kinds = {"n": "counter", "g": "gauge", "h": "histogram", "e": "ewma"}
    rows = [
        {"n": {"value": 3}, "g": {"value": 1.0},
         "h": {"count": 2, "sum": 4.0, "min": 1.0, "max": 3.0},
         "e": {"rate": 50.0}},
        {"n": {"value": 4}, "g": {"value": 3.0},
         "h": {"count": 1, "sum": 9.0, "min": 9.0, "max": 9.0},
         "e": {"rate": 70.0}},
    ]
    out = combine_host_snapshots(rows, kinds)
    assert out["n"]["value"] == 7                      # counters sum
    assert out["g"]["value_mean"] == pytest.approx(2.0)  # gauges mean+max
    assert out["g"]["value_max"] == pytest.approx(3.0)
    assert out["h"] == {"count": 3, "sum": 13.0, "min": 1.0, "max": 9.0,
                        "mean": pytest.approx(13.0 / 3)}
    assert out["e"]["rate"] == pytest.approx(120.0)    # rates add
    # a metric present on one host only still combines
    out2 = combine_host_snapshots(
        [{"n": {"value": 1}}, {}], {"n": "counter"})
    assert out2["n"]["value"] == 1


def test_aggregate_single_process_matches_combine_fields():
    r = obs.MetricsRegistry()
    r.counter("c").inc(2)
    r.gauge("g").set(5.0)
    agg = r.aggregate()
    assert agg["c"]["value"] == 2
    assert agg["g"]["value_mean"] == 5.0 and agg["g"]["value_max"] == 5.0


# ------------------------------------------------------------------- sinks
def test_jsonl_sink_round_trip_and_force_flush(tmp_path):
    path = str(tmp_path / "m.jsonl")
    r = obs.MetricsRegistry()
    sink = obs.JSONLSink(path, flush_every=1000)   # large buffer on purpose
    r.add_sink(sink)
    r.record({"kind": "train", "step": 1, "loss": np.float32(0.5)})
    r.record({"kind": "epoch", "epoch": 1, "lr": 2e-4}, force=True)
    # crash-safety: WITHOUT close(), the force=True record (and everything
    # before it) must already be on disk — a SIGKILLed run keeps them
    lines = [json.loads(x) for x in open(path)]
    assert [x["kind"] for x in lines] == ["train", "epoch"]
    assert lines[0]["loss"] == 0.5                 # device scalar coerced
    assert lines[1]["lr"] == pytest.approx(2e-4)
    # buffered (non-force) records appear after close; close is idempotent
    r.record({"kind": "train", "step": 2})
    sink.close()
    sink.close()
    assert len(open(path).readlines()) == 3
    sink.write({"kind": "late"}, force=True)       # post-close write: no-op
    assert len(open(path).readlines()) == 3


def test_metrics_logger_facade_matches_seed_api(tmp_path, capsys):
    path = str(tmp_path / "m.jsonl")
    lg = obs.MetricsLogger(path, print_every=50)
    lg.log({"kind": "train", "step": 50, "loss_g": 1.25})
    lg.log({"kind": "train", "step": 51, "loss_g": 1.0})
    out = capsys.readouterr().out
    assert "loss_g=1.2500" in out          # heartbeat at step%50==0
    assert "loss_g=1.0000" not in out      # silent off-heartbeat
    recs = [json.loads(x) for x in open(path)]
    assert [r["step"] for r in recs] == [50, 51]   # JSONL carries every record


def test_prometheus_textfile_export(tmp_path):
    r = obs.MetricsRegistry()
    r.counter("images_total").inc(7)
    r.gauge("hbm_bytes", device=0).set(123.0)
    path = str(tmp_path / "p2p.prom")
    sink = obs.PrometheusTextfileSink(path, r)
    r.add_sink(sink)
    r.record({"kind": "x"}, force=True)
    text = open(path).read()
    assert "# TYPE images_total counter" in text
    assert "images_total 7.0" in text
    # label values must be quoted — one bare value makes node_exporter's
    # textfile collector reject the entire file
    assert 'hbm_bytes{device="0"} 123.0' in text


# ------------------------------------------------------------------- spans
def test_span_nesting_and_perfetto_export(tmp_path):
    rec = obs.SpanRecorder()
    reg = obs.MetricsRegistry()
    events = []
    reg.add_sink(type("S", (obs.Sink,), {
        "write": lambda self, r, force=False: events.append(r)})())
    with rec.span("epoch", registry=reg, epoch=1):
        with rec.span("dispatch"):
            pass
        with rec.span("dispatch"):
            pass
    # children finish first; depths recorded relative to the stack
    names = [(s["name"], s["depth"]) for s in rec.spans]
    assert names == [("dispatch", 1), ("dispatch", 1), ("epoch", 0)]
    assert events and events[0]["kind"] == "span" and events[0]["epoch"] == 1
    path = rec.export_perfetto(str(tmp_path / "t.json"))
    doc = json.load(open(path))
    xs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert len(xs) == 3
    epoch = next(e for e in xs if e["name"] == "epoch")
    for d in (e for e in xs if e["name"] == "dispatch"):
        # nesting falls out of interval containment
        assert epoch["ts"] <= d["ts"]
        assert d["ts"] + d["dur"] <= epoch["ts"] + epoch["dur"] + 1


def test_span_ring_drops_oldest_and_timed_annotation_feeds_histogram():
    rec = obs.SpanRecorder(max_spans=3)
    for i in range(5):
        with rec.span(f"s{i}"):
            pass
    # drop-OLDEST: the exported window is the run's most recent spans
    assert [s["name"] for s in rec.spans] == ["s2", "s3", "s4"]
    assert rec.dropped == 2
    h = obs.MetricsRegistry().histogram("d")
    with obs.timed_annotation("hot", h) as hot:
        pass
    assert h.count == 1 and h.sum == hot.secs >= 0


# ----------------------------------------------------------------- sentinel
def test_nan_sentinel_fires_in_jit_on_cpu():
    fired = []
    handler = fired.append
    obs.add_sentinel_handler(handler)
    try:
        @jax.jit
        def step(x):
            m = {"loss_g": jnp.sum(x), "loss_d": jnp.ones(())}
            obs.nan_sentinel(m, tag="train_step")
            return m

        step(jnp.ones((4,)))
        jax.effects_barrier()
        assert fired == []                       # happy path: silent
        step(jnp.asarray([1.0, np.nan, np.inf, np.inf]))
        jax.effects_barrier()
        assert len(fired) == 1
        ev = fired[0]
        assert ev["kind"] == "sentinel" and ev["tag"] == "train_step"
        assert ev["leaves"]["loss_g"] == {"nan": 1, "inf": 0}
        # the process-default registry counted the event
        assert obs.get_registry().counter(
            "nonfinite_events", tag="train_step").value >= 1
    finally:
        obs.remove_sentinel_handler(handler)


def test_nan_sentinel_under_scan():
    fired = []
    obs.add_sentinel_handler(fired.append)
    try:
        @jax.jit
        def multi(xs):
            def body(c, x):
                obs.nan_sentinel({"v": jnp.sum(x)}, tag="scan")
                return c, jnp.sum(x)

            return jax.lax.scan(body, 0.0, xs)

        xs = np.ones((3, 2), np.float32)
        xs[1, 0] = np.nan
        multi(jnp.asarray(xs))
        jax.effects_barrier()
        assert len(fired) == 1 and fired[0]["tag"] == "scan"
    finally:
        # bound-method equality makes this remove the handler added above
        obs.remove_sentinel_handler(fired.append)


def test_grad_norm_taps():
    m = obs.grad_norm_taps({}, g={"w": jnp.asarray([3.0, 4.0])}, d=None)
    assert float(m["grad_norm_g"]) == pytest.approx(5.0)
    assert "grad_norm_d" not in m


# -------------------------------------------------------------- check_finite
def test_check_finite_names_the_leaf_and_emits_event():
    reg = obs.MetricsRegistry()
    events = []
    reg.add_sink(type("S", (obs.Sink,), {
        "write": lambda self, r, force=False: events.append((r, force))})())
    from p2p_tpu.core.debug import check_finite

    good = {"a": jnp.ones((2,))}
    assert check_finite(good, registry=reg) == []
    bad = {"a": jnp.ones((2,)), "b": {"c": jnp.asarray([1.0, np.nan, np.inf])}}
    with pytest.raises(FloatingPointError, match="b/c"):
        check_finite(bad, "state", registry=reg)
    assert len(events) == 1
    rec, force = events[0]
    assert force and rec["kind"] == "nonfinite" and rec["name"] == "state"
    assert rec["leaves"] == [{"leaf": "b/c", "nan": 1, "inf": 1}]
    # degrade mode: report, don't raise
    assert check_finite(bad, raise_=False)[0]["leaf"] == "b/c"


# ------------------------------------------------------------------ watchdogs
def test_retrace_watchdog_counts_forced_recompile():
    reg = obs.MetricsRegistry()
    w = obs.RetraceWatchdog(registry=reg)
    try:
        f = jax.jit(lambda x: x * 3 + 1)
        f(jnp.ones((2,)))                    # warmup compile
        warm = w.compiles
        w.arm()
        f(jnp.ones((2,)))                    # cache hit: no compile
        assert w.compiles == warm and w.unexpected == 0
        f(jnp.ones((5,)))                    # shape wobble → recompile
        assert w.unexpected >= 1
        assert reg.counter("unexpected_recompiles").value >= 1
        assert reg.histogram("xla_compile_secs").count >= 1
    finally:
        w.close()


def test_memory_watchdog_cpu_is_quiet():
    # CPU devices expose no memory_stats — sample() must return {} and
    # write nothing rather than raise
    w = obs.MemoryWatchdog(registry=obs.MetricsRegistry())
    assert w.sample() == {}


# -------------------------------------------------------------------- timing
def test_step_timer_chain_math(monkeypatch):
    from p2p_tpu.obs import timing

    t = [0.0]
    monkeypatch.setattr(timing.time, "perf_counter", lambda: t[0])
    timer = obs.StepTimer(batch_size=10)
    with timer.chain(steps=8, rtt=1.0) as ch:
        t[0] += 5.0                          # 8 steps in 5s incl. 1s RTT
        ch.fence(jnp.ones(()))
    assert timer.intervals == 8
    assert timer.elapsed == pytest.approx(4.0)
    assert timer.images_per_sec == pytest.approx(10 * 8 / 4.0)
    # loop-style ticks feed the same accumulator
    timer2 = obs.StepTimer(batch_size=10, skip_first=1)
    for _ in range(4):
        timer2.tick()
        t[0] += 1.0
    timer2.tick()
    assert timer2.intervals == 3
    assert timer2.images_per_sec == pytest.approx(10.0)


# ------------------------------------------------------------------ manifest
def test_manifest_hash_and_write(tmp_path):
    import dataclasses

    from p2p_tpu.core.config import get_preset

    cfg = get_preset("facades")
    assert obs.config_hash(cfg) == obs.config_hash(get_preset("facades"))
    cfg2 = cfg.replace(data=dataclasses.replace(cfg.data, batch_size=7))
    assert obs.config_hash(cfg) != obs.config_hash(cfg2)
    path = str(tmp_path / "manifest.json")
    man = obs.write_manifest(path, cfg)
    on_disk = json.load(open(path))
    assert on_disk["config_hash"] == man["config_hash"]
    assert on_disk["dtype_policy"]["compute"] == "bfloat16"
    assert on_disk["config"]["data"]["batch_size"] == 1
    assert on_disk["jax_version"] == jax.__version__


# ------------------------------------------------------- trainer integration
def test_trainer_obs_wiring(tmp_path, monkeypatch):
    """The migrated Trainer produces, through obs: a manifest file, a
    provenance + epoch record in the metrics JSONL, and a Perfetto span
    trace at fit() end — with fake step fns, so no step compile cost."""
    import dataclasses

    from p2p_tpu.core.config import get_preset
    from p2p_tpu.data.synthetic import make_synthetic_dataset
    from p2p_tpu.train.loop import Trainer

    root = str(tmp_path / "ds")
    make_synthetic_dataset(root, n_train=4, n_test=2, size=16)
    cfg = get_preset("facades")
    cfg = cfg.replace(
        name="obswire",
        model=dataclasses.replace(cfg.model, ngf=4, ndf=4),
        data=dataclasses.replace(cfg.data, batch_size=2, image_size=16,
                                 threads=0),
        train=dataclasses.replace(cfg.train, mixed_precision=False,
                                  nepoch=1, epoch_save=1, log_every=1,
                                  eval_every_epoch=False),
    )
    tr = Trainer(cfg, data_root=root, workdir=str(tmp_path))
    try:
        assert tr.logger.registry is tr.obs

        def train_step(state, batch):
            return state.replace(step=state.step + 1), {
                "loss_g": jnp.float32(1.0), "loss_d": jnp.float32(2.0)}

        tr.train_step = train_step
        tr.multi_step = None
        # a program of this run traces the perceptual loss on bf16 images
        from p2p_tpu.losses import vgg_loss
        from p2p_tpu.models.vgg import load_vgg19_params

        jax.eval_shape(
            lambda p, a: vgg_loss(p, a, a), jax.eval_shape(load_vgg19_params),
            jax.ShapeDtypeStruct((1, 16, 16, 3), jnp.bfloat16))
        # ... and a x2-upsample k3 convolution in the subpixel form
        from p2p_tpu.ops.conv import UpsampleConvLayer

        jax.eval_shape(
            lambda a: UpsampleConvLayer(4, kernel_size=3, upsample=2).init(
                jax.random.key(0), a),
            jax.ShapeDtypeStruct((1, 256, 256, 4), jnp.float32))
        # ... and a reflect-padded k3 layer
        from p2p_tpu.ops.conv import ConvLayer

        jax.eval_shape(
            lambda a: ConvLayer(4, kernel_size=3).init(jax.random.key(0), a),
            jax.ShapeDtypeStruct((1, 16, 16, 4), jnp.float32))
        tr.fit()

        manifest = json.load(open(tmp_path / "manifest_obswire.json"))
        assert manifest["config_hash"] == obs.config_hash(cfg)
        assert manifest["mesh_shape"] == {"data": 1, "fsdp": 1,
                                          "spatial": 1, "time": 1,
                                          "model": 1, "pipe": 1}

        recs = [json.loads(x) for x in open(tmp_path / "metrics_obswire.jsonl")]
        kinds = [r["kind"] for r in recs]
        assert kinds[0] == "manifest"
        assert "train" in kinds and "epoch" in kinds
        epoch = next(r for r in recs if r["kind"] == "epoch")
        assert epoch["epoch"] == 1 and math.isfinite(epoch["loss_g"])
        # ... and the run's stream says which forms its convolutions took
        forms = [r for r in recs if r["kind"] == "conv_forms"][-1]
        assert forms["conv_form_sites_total.nearest_up2"] >= 1
        assert set(forms) >= {"conv_form_sites_total.blocked"}
        # ... how its reflect pads' backward was built
        pads = [r for r in recs if r["kind"] == "reflect_pad"][-1]
        assert pads["reflect_pad_sites_total.one_pass"] >= 1
        assert set(pads) >= {"reflect_pad_sites_total.one_pass_w",
                             "reflect_pad_sites_total.autodiff"}
        # ... and which dtype VGG19 stored
        (vgg,) = [r for r in recs if r["kind"] == "vgg_loss"]
        assert vgg["vgg_loss_traces_total.bfloat16"] >= 1
        assert set(vgg) >= {"vgg_loss_traces_total.float32"}

        trace_doc = json.load(open(tmp_path / "trace_obswire.json"))
        names = {e["name"] for e in trace_doc["traceEvents"]
                 if e.get("ph") == "X"}
        # the ring holds epochs, not steps: one train_epoch record whose
        # per-step phases were counted once per dispatch
        assert {"epoch", "train_epoch", "checkpoint_save"} <= names
        assert "train_dispatch" not in names
        (span,) = [r for r in recs if r["kind"] == "span"]
        assert span["span"] == "train_epoch" and span["steps"] == 2
        assert 0 < span["epoch_start_s"] <= span["sec"]
        assert tr.obs.histogram("feed_next_secs").count == 2
        assert tr.obs.histogram("step_bookkeeping_secs").count == 2
        # the step clock saw the epoch's dispatches FINISH (one delayed
        # read each; the two stamps give one interval, and the record the
        # rate steps complete at, not the rate the host enqueues them), and
        # every dispatch fed the duration histogram
        assert tr.obs.histogram("device_wait_secs").count == 2
        assert tr.obs.histogram("step_interval_secs").count == 1
        assert span["step_interval_median_s"] == pytest.approx(
            tr.obs.histogram("step_interval_secs").sum, abs=1e-5)
        assert span["step_interval_median_s"] > 0
        assert tr.obs.histogram("dispatch_secs").count == 2
        assert tr.retrace.armed
    finally:
        tr.close()
    # close() unhooked the process-global compile listener (a later
    # trainer in this process must not pollute this run's stream)
    from jax._src import monitoring as _mon

    assert tr.retrace._on_event not in _mon.get_event_duration_listeners()
    tr.close()  # idempotent


def test_trainer_check_finite_flag_emits_and_raises(tmp_path):
    import dataclasses

    from p2p_tpu.core.config import DebugConfig, get_preset
    from p2p_tpu.data.synthetic import make_synthetic_dataset
    from p2p_tpu.train.loop import Trainer

    root = str(tmp_path / "ds")
    make_synthetic_dataset(root, n_train=4, n_test=2, size=16)
    cfg = get_preset("facades")
    cfg = cfg.replace(
        name="cf",
        model=dataclasses.replace(cfg.model, ngf=4, ndf=4),
        data=dataclasses.replace(cfg.data, batch_size=2, image_size=16,
                                 threads=0),
        train=dataclasses.replace(cfg.train, mixed_precision=False,
                                  log_every=1000, scan_steps=2),
        debug=DebugConfig(check_finite=True),
    )
    tr = Trainer(cfg, data_root=root, workdir=str(tmp_path))
    try:
        def nan_multi_step(state, batches):
            k = next(iter(batches.values())).shape[0]
            # NaN in an INTERMEDIATE scanned step, finite in the last —
            # the guard checks the scan-axis sum, so it must still fire
            v = np.ones((k,), np.float32)
            v[0] = np.nan
            return state.replace(step=state.step + k), {
                "loss_g": jnp.asarray(v)}

        tr.train_step = lambda s, b: (s.replace(step=s.step + 1),
                                      {"loss_g": jnp.float32(np.nan)})
        tr.multi_step = nan_multi_step
        with pytest.raises(FloatingPointError, match="loss_g"):
            tr.train_epoch()
        recs = [json.loads(x) for x in open(tmp_path / "metrics_cf.jsonl")]
        bad = [r for r in recs if r["kind"] == "nonfinite"]
        # the evidence reached the (force-flushed) stream BEFORE the raise
        assert bad and bad[0]["leaves"][0]["leaf"] == "loss_g"
    finally:
        tr.close()


def test_trainer_sentinel_handler_routes_to_run_registry(tmp_path):
    """cfg.debug.nan_sentinel: sentinel events land in THIS run's metrics
    stream and tick nonfinite_events on the trainer's registry (the one
    exporters snapshot), and close() unregisters the handler."""
    import dataclasses

    from p2p_tpu.core.config import DebugConfig, get_preset
    from p2p_tpu.data.synthetic import make_synthetic_dataset
    from p2p_tpu.obs import taps
    from p2p_tpu.train.loop import Trainer

    root = str(tmp_path / "ds")
    make_synthetic_dataset(root, n_train=4, n_test=2, size=16)
    cfg = get_preset("facades")
    cfg = cfg.replace(
        name="sent",
        model=dataclasses.replace(cfg.model, ngf=4, ndf=4),
        data=dataclasses.replace(cfg.data, batch_size=2, image_size=16,
                                 threads=0),
        train=dataclasses.replace(cfg.train, mixed_precision=False),
        debug=DebugConfig(nan_sentinel=True),
    )
    tr = Trainer(cfg, data_root=root, workdir=str(tmp_path))
    try:
        assert tr._sentinel_handler in taps._handlers
        tr._sentinel_handler(
            {"kind": "sentinel", "tag": "train_step", "nan": 1, "inf": 0})
        assert tr.obs.counter(
            "nonfinite_events", tag="train_step").value == 1
        recs = [json.loads(x)
                for x in open(tmp_path / "metrics_sent.jsonl")]
        assert any(r["kind"] == "sentinel" for r in recs)
    finally:
        tr.close()
    assert tr._sentinel_handler is None
    assert all(getattr(h, "__name__", "") != "_handler"
               for h in taps._handlers)


def test_retrace_watchdog_persistent_cache_hit_on_identical_compile(
        tmp_path):
    """ISSUE 6 satellite: the persistent-XLA-cache hit/miss counters on
    RetraceWatchdog, asserted end-to-end — a second identical backend
    compile (in-memory executable cache dropped, so the request really
    reaches the backend) is served from the on-disk cache and lands in
    ``cache_hits`` AND the ``persistent_cache_hits`` registry counter,
    with the first compile counted as a miss."""
    from p2p_tpu.core import cache as cache_mod
    from p2p_tpu.core.cache import enable_compilation_cache

    prev_dir = jax.config.jax_compilation_cache_dir
    prev_enabled = cache_mod._enabled_dir
    reg = obs.MetricsRegistry()
    w = obs.RetraceWatchdog(registry=reg)
    try:
        enable_compilation_cache(str(tmp_path / "xla_cache"))
        f = jax.jit(lambda x: x * 2.0 + 1.0)
        f(jnp.ones((3,)))                     # first compile: cache MISS
        assert w.cache_misses >= 1
        assert reg.counter("persistent_cache_misses").value >= 1
        assert os.listdir(str(tmp_path / "xla_cache")), \
            "first compile wrote no cache entry"

        hits_before = w.cache_hits
        jax.clear_caches()                    # drop in-memory executables
        f(jnp.ones((3,)))                     # identical compile: HIT
        assert w.cache_hits > hits_before
        assert reg.counter("persistent_cache_hits").value >= 1
    finally:
        w.close()
        cache_mod._enabled_dir = prev_enabled
        jax.config.update("jax_compilation_cache_dir", prev_dir)


def test_budget_drift_pure_comparison():
    from p2p_tpu.obs import budget_drift

    drift, bad = budget_drift(110, 100)
    assert abs(drift - 0.10) < 1e-9 and not bad
    drift, bad = budget_drift(125, 100)
    assert bad and abs(drift - 0.25) < 1e-9
    assert budget_drift(0, 0) == (0.0, False)   # no static row → no claim


def test_crosscheck_hbm_budget_record_and_warn(capsys):
    """ISSUE 15 satellite: the startup cross-check compares the live
    per-host HBM fill against the static memory_budget.json state law,
    publishes gauges + a kind="hbm_budget" record, and warns past 10%
    drift. Driven with injected samples (CPU devices report no memory
    stats)."""
    import dataclasses

    from p2p_tpu.analysis.memory_audit import state_budget
    from p2p_tpu.core.config import get_preset
    from p2p_tpu.obs import MetricsRegistry, crosscheck_hbm_budget

    cfg = get_preset("facades")
    cfg = dataclasses.replace(
        cfg, model=dataclasses.replace(cfg.model, ngf=8, ndf=8),
        data=dataclasses.replace(cfg.data, image_size=16))
    static = state_budget(cfg, {})["state_total"]

    class _Log:
        def __init__(self):
            self.recs = []

        def log(self, rec, force=False):
            self.recs.append(rec)

    # no samples at all (CPU backend): a no-op returning None
    assert crosscheck_hbm_budget(cfg, None, samples={}) is None

    reg, log = MetricsRegistry(), _Log()
    rec = crosscheck_hbm_budget(
        cfg, None, registry=reg, logger=log,
        samples={"0": {"bytes_in_use": int(static * 1.02)}})
    assert rec is not None and not rec["out_of_band"]
    assert rec["static_state_bytes"] == static
    assert log.recs and log.recs[0]["kind"] == "hbm_budget"
    assert log.recs[0]["static_state_bytes"] == static
    assert "WARNING" not in capsys.readouterr().out

    rec = crosscheck_hbm_budget(
        cfg, None, registry=reg, logger=log,
        samples={"0": {"bytes_in_use": int(static * 1.5)}})
    assert rec["out_of_band"] and rec["drift"] > 0.10
    assert reg.counter("hbm_budget_drift_total").value == 1
    assert "static memory model" in capsys.readouterr().out


def test_conv_layer_trace_reads_device_time_by_layer_and_direction():
    """``scripts/conv_layer_trace.py`` on the benchmark's recorded trace
    and the text its step compiles to (a convolution under the scope
    ``net_a``, a tanh under ``net_b``; three executions): an instruction
    is keyed by the first ``op_name`` component that matches, and the
    keys' milliseconds a step add up to what ``scope_time.by_scope`` sums
    under the same names."""
    import importlib.util

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "conv_layer_trace", os.path.join(root, "scripts",
                                         "conv_layer_trace.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    from benchmark import scope_time

    data = os.path.join(root, "benchmark", "tests", "data")
    trace = os.path.join(data, "small_trace.xplane.pb")
    with open(os.path.join(data, "small_trace.hlo.txt")) as f:
        text = f.read()

    keys = tool.layer_keys(text, ("G",), "net_a|net_b")
    assert keys["fusion.3"] == ("unscoped", "net_a", "fwd", None)
    assert keys["copy.9"] == ("unscoped", "net_b", "fwd", None)
    # an instruction under a form's scope of ops/conv.py is marked with it
    scoped = text.replace("net_a/", "net_a/nearest_up2/")
    assert tool.layer_keys(scoped, ("G",), "net_a|net_b")["fusion.3"] == (
        "unscoped", "net_a", "fwd", "nearest_up2")
    assert "copy.6" not in keys       # named after a parameter: no layer
    got = tool.by_layer(trace, text, "net_a|net_b", top=2)
    want = scope_time.by_scope(trace, text, ("net_a", "net_b"))
    assert got["steps"] == want["executions"] == 3
    assert got["blocked_conv_ms"] == got["nearest_up2_ms"] == 0.0
    assert got["reflect_pad_ms"] == {"all": 0.0}
    # the reflect pad's scope is read whichever layers were asked for
    padded = tool.by_layer(trace, text.replace("net_b/", "net_b/reflect_pad/"),
                           "net_a", top=2)
    assert list(padded["layer_ms"]) == ["unscoped|net_a|fwd"]
    assert padded["reflect_pad_ms"]["all"] == pytest.approx(
        got["layer_ms"]["unscoped|net_b|fwd"]["ms"], rel=1e-9)
    assert padded["reflect_pad_ms"]["unscoped|fwd"] == \
        padded["reflect_pad_ms"]["all"]
    up2 = tool.by_layer(trace, scoped, "net_a|net_b", top=2)
    assert up2["blocked_conv_ms"] == 0.0
    assert up2["nearest_up2_ms"] == pytest.approx(
        up2["layer_ms"]["unscoped|net_a|fwd"]["ms"], rel=1e-9)
    for net in ("net_a", "net_b"):
        row = got["layer_ms"][f"unscoped|{net}|fwd"]
        assert row["ms"] == pytest.approx(
            1000.0 * want["scope_s"][net] / 3, rel=1e-9)
        assert len(row["ops"]) == 2 and row["ops"][0][1] >= row["ops"][1][1]


def test_conv_layer_trace_finds_the_trainer_of_a_frozen_collector():
    """The Trainer freezes the collector after its first epoch
    (``train.loop.settle_collector``) and ``gc.get_objects()`` lists no
    frozen object: ``epoch_records.live_trainer()`` then finds none, which
    is how ``trace_phases.py``'s join failed on the chip (PR 43). The
    script's own lookup thaws first."""
    import gc
    import importlib.util
    import types

    from benchmark import epoch_records
    from p2p_tpu.train.loop import Trainer

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "conv_layer_trace", os.path.join(root, "scripts",
                                         "conv_layer_trace.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)

    class Built(Trainer):
        def __init__(self):     # what live_trainer reads, nothing else
            self.spans = types.SimpleNamespace(
                spans=[{"name": "train_epoch", "ts": 9e18}])

    trainer = Built()
    assert epoch_records.live_trainer() is trainer
    gc.freeze()
    try:
        assert epoch_records.live_trainer() is not trainer
        assert tool.thawed(epoch_records.live_trainer)() is trainer
    finally:
        gc.unfreeze()


# preset -> the reflect-padded sites of G (and C where the preset has one),
# traced at the preset's own extent: ExpandNetwork 1 stem + 2 stride-2 + 18
# in the residual blocks + 1 head and the compression net's 3; pix2pixHD's
# enhancer and G1; the ResNet generator of cityscapes_spatial; the U-Nets
# pad with zeros (facades_int8_full's 3 are its compression net's), and so
# does SPADE; the LaMa generator's stem, 3 stride-2 convolutions and head,
# and in each of its 36 fast Fourier convolutions one pad a branch (the
# local and the global one: each serves the two convolutions that read it).
REFLECT_PAD_SITES = {"reference": 25, "pix2pixhd": 35,
                     "cityscapes_spatial": 23, "facades_int8_full": 3,
                     "big_lama": 77}


@pytest.mark.parametrize("spatial", [1, 2], ids=["one_device", "spatial2"])
@pytest.mark.parametrize("preset", list_presets())
def test_every_preset_builds_its_reflect_pads_backward_in_one_pass(
        preset, spatial, devices8):
    """G and C traced abstractly at the preset's own extent: every
    ``reflect_pad_2d`` call site ticks
    ``reflect_pad_sites_total{backward=...}`` once, ``one_pass`` on one
    device and ``one_pass_w`` inside a mesh that shards H, none keeps
    autodiff's four chained passes, and a preset that pads with zeros
    ticks nothing (PR 33: the gain cannot be lost silently)."""
    import contextlib

    from p2p_tpu.core.config import get_preset
    from p2p_tpu.core.mesh import MeshSpec, make_mesh, mesh_context
    from p2p_tpu.ops.conv import reflect_pad_sites
    from p2p_tpu.train.state import build_models

    cfg = get_preset(preset)
    g, _, c = build_models(cfg, jnp.bfloat16)
    h = cfg.data.image_size
    x = jax.ShapeDtypeStruct(
        (2, h, cfg.data.image_width or h, cfg.model.input_nc), jnp.bfloat16)
    inside = contextlib.nullcontext() if spatial == 1 else mesh_context(
        make_mesh(MeshSpec(data=1, spatial=spatial),
                  devices=devices8[:spatial]))
    before = reflect_pad_sites()
    with inside:
        for net in (g, c):
            if net is not None:
                jax.eval_shape(
                    lambda x, net=net: net.init(jax.random.key(0), x, False),
                    x)
    ticks = {k: v - before[k] for k, v in reflect_pad_sites().items()}
    want = dict.fromkeys(ticks, 0)
    want["one_pass" if spatial == 1 else "one_pass_w"] = \
        REFLECT_PAD_SITES.get(preset, 0)
    assert ticks == want
