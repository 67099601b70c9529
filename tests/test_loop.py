import os
import time

import pytest

import numpy as np

from p2p_tpu.core.config import (
    Config,
    DataConfig,
    LossConfig,
    ModelConfig,
    OptimConfig,
    ParallelConfig,
    TrainConfig,
)
from p2p_tpu.core.mesh import MeshSpec
from p2p_tpu.data.synthetic import make_synthetic_dataset
from p2p_tpu.train.loop import Trainer


@pytest.mark.slow
def test_trainer_end_to_end(tmp_path):
    """SURVEY §4.4: tiny synthetic set, N steps, loss finite and decreasing,
    eval + sample dumps + checkpoint + resume all work."""
    root = make_synthetic_dataset(str(tmp_path / "data"), 4, 2, size=32)
    cfg = Config(
        name="e2e",
        model=ModelConfig(ngf=8, n_blocks=1, ndf=8, num_D=2),
        loss=LossConfig(lambda_feat=10.0, lambda_vgg=0.0, lambda_tv=1.0),
        optim=OptimConfig(niter=2, niter_decay=2),
        data=DataConfig(batch_size=2, image_size=32, threads=0),
        parallel=ParallelConfig(mesh=MeshSpec(data=1)),
        train=TrainConfig(
            nepoch=2, epoch_save=2, log_every=1, mixed_precision=False,
            seed=0,
        ),
    )
    tr = Trainer(cfg, data_root=root, workdir=str(tmp_path))
    history = tr.fit()
    assert len(history) == 2
    for rec in history:
        assert np.isfinite(rec["loss_g"]) and np.isfinite(rec["psnr_mean"])
        assert 0 < rec["psnr_mean"] <= 60
    # sample dumps exist
    result_dir = tmp_path / "result" / cfg.data.dataset
    assert any(f.endswith("_pred.png") for f in os.listdir(result_dir))
    # the compression net is active → the quantized intermediate is dumped
    # alongside input/target/pred, like the reference (train.py:469-473)
    assert any(f.endswith("_comp.png") for f in os.listdir(result_dir))
    # metrics log exists
    assert (tmp_path / "metrics_e2e.jsonl").exists()

    # resume: fresh trainer picks up the saved checkpoint at epoch 3
    tr2 = Trainer(cfg, data_root=root, workdir=str(tmp_path))
    assert tr2.maybe_resume()
    assert int(tr2.state.step) == int(tr.state.step)
    assert tr2.epoch == 3


@pytest.mark.slow
def test_resume_into_decay_window_continues_lr_curve(tmp_path):
    """Resume × decay regression (round-3 hd_r3 bug): the lambda schedule
    derived its epoch from the restored ABSOLUTE step and then added the
    compiled-in --epoch_count offset again, so a resume whose window
    overlapped the decay phase trained at LR=0. Fixed: maybe_resume treats
    the restored step as authoritative and rebuilds the schedule with
    epoch_count normalized to 1. This trains into the decay window,
    resumes reference-style (--epoch_count 5), and asserts the next
    epochs' lr records continue the decay curve exactly."""
    root = make_synthetic_dataset(str(tmp_path / "data"), 4, 2, size=16)
    base_lr = 2e-4

    def mk(epoch_count, nepoch):
        return Config(
            name="resdec",
            model=ModelConfig(ngf=4, n_blocks=1, ndf=4, num_D=1),
            loss=LossConfig(lambda_feat=0.0, lambda_vgg=0.0, lambda_tv=0.0),
            optim=OptimConfig(lr=base_lr, niter=2, niter_decay=4),
            data=DataConfig(batch_size=2, image_size=16, threads=0),
            parallel=ParallelConfig(mesh=MeshSpec(data=1)),
            train=TrainConfig(
                nepoch=nepoch, epoch_count=epoch_count, epoch_save=2,
                log_every=100, mixed_precision=False, seed=0,
                eval_every_epoch=False,
            ),
        )

    # fresh run INTO the decay window (decay begins after epoch niter=2)
    tr = Trainer(mk(1, 4), data_root=root, workdir=str(tmp_path))
    hist = tr.fit()
    spe = tr.steps_per_epoch
    assert spe == 2

    def expect(E):
        # lr recorded after 1-based epoch E = schedule at the epoch's last
        # update (count spe*E - 1): mult = 1 - max(0, e+1-niter)/(decay+1)
        e = (spe * E - 1) // spe
        return base_lr * max(0.0, 1.0 - max(0, e + 1 - 2) / 5.0)

    assert hist[-1]["lr"] == pytest.approx(expect(4), rel=1e-5)
    assert expect(4) < base_lr  # we really are inside the decay window

    # resume reference-style with --epoch_count 5 (the trigger in the
    # reference, train.py:253-255) and train two more epochs
    tr2 = Trainer(mk(5, 6), data_root=root, workdir=str(tmp_path))
    assert tr2.maybe_resume()
    assert tr2.epoch == 5
    import jax

    before = jax.tree_util.tree_map(np.asarray, tr2.state.params_g)
    hist2 = tr2.fit()
    lrs = [r["lr"] for r in hist2]
    assert lrs == pytest.approx([expect(5), expect(6)], rel=1e-5)
    # the bug trained the continuation at exactly 0
    assert min(lrs) > 0.0
    # and params must actually move past the decay onset
    moved = any(
        not np.allclose(a, b)
        for a, b in zip(
            jax.tree_util.tree_leaves(before),
            jax.tree_util.tree_leaves(tr2.state.params_g),
        )
    )
    assert moved


@pytest.mark.slow
def test_evaluate_scores_every_test_image(tmp_path):
    """drop_remainder=False + tail padding: a 5-image test split at
    test_batch_size=2 scores exactly 5 images."""
    import dataclasses

    from p2p_tpu.core.config import get_preset
    from p2p_tpu.data.synthetic import make_synthetic_dataset
    from p2p_tpu.train.loop import Trainer

    root = str(tmp_path / "ds")
    make_synthetic_dataset(root, n_train=2, n_test=5, size=16)
    cfg = get_preset("reference")
    cfg = cfg.replace(
        model=dataclasses.replace(cfg.model, ngf=4, n_blocks=1),
        data=dataclasses.replace(cfg.data, batch_size=2, image_size=16,
                                 test_batch_size=2),
        train=dataclasses.replace(cfg.train, mixed_precision=False),
        loss=dataclasses.replace(cfg.loss, lambda_vgg=0.0),
    )
    tr = Trainer(cfg, data_root=root, workdir=str(tmp_path))
    result = tr.evaluate()
    assert np.isfinite(result["psnr_mean"])
    assert result["n_images"] == 5  # tail batch scored, padding trimmed


@pytest.mark.slow
def test_trainer_scan_steps_covers_every_batch(tmp_path):
    """scan_steps=2 over 5 batches/epoch: 2 scanned dispatches + 1
    single-step remainder — state.step advances by 5 and metric averages
    cover all steps."""
    import dataclasses

    from p2p_tpu.core.config import get_preset
    from p2p_tpu.data.synthetic import make_synthetic_dataset
    from p2p_tpu.train.loop import Trainer

    root = str(tmp_path / "ds")
    make_synthetic_dataset(root, n_train=10, n_test=2, size=16)
    cfg = get_preset("reference")
    cfg = cfg.replace(
        model=dataclasses.replace(cfg.model, ngf=4, n_blocks=1, ndf=4,
                                  num_D=2, n_layers_D=2),
        data=dataclasses.replace(cfg.data, batch_size=2, image_size=16,
                                 threads=0),
        train=dataclasses.replace(cfg.train, mixed_precision=False,
                                  scan_steps=2),
        loss=dataclasses.replace(cfg.loss, lambda_vgg=0.0),
    )
    tr = Trainer(cfg, data_root=root, workdir=str(tmp_path))
    metrics = tr.train_epoch()
    assert int(tr.state.step) == 5
    assert np.isfinite(metrics["loss_g"])


# --------------------------------------------------- accounting fixtures
class _FakeClock:
    """Deterministic perf_counter: +1.0 per call. Makes train_epoch's
    throughput math hand-computable (VERDICT r2 item 6: a miscount here
    silently corrupts the headline img/s figure)."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1.0
        return self.t


def _fake_steps():
    """(train_step, multi_step) fakes: advance state.step, constant
    metrics, zero wall time (the fake clock owns time entirely)."""
    import jax.numpy as jnp

    def train_step(state, batch):
        return state.replace(step=state.step + 1), {
            "loss_g": jnp.float32(1.0), "loss_d": jnp.float32(2.0)}

    def multi_step(state, batches):
        k = next(iter(batches.values())).shape[0]
        return state.replace(step=state.step + k), {
            "loss_g": jnp.ones((k,), jnp.float32),
            "loss_d": jnp.full((k,), 2.0, jnp.float32)}

    return train_step, multi_step


def _tiny_trainer(tmp_path, n_train, batch_size, scan_steps):
    """A 16x16 facades Trainer over a synthetic split, its step fns
    replaced by the fakes: the loop, loader and prefetch are real."""
    import dataclasses

    from p2p_tpu.core.config import get_preset

    root = str(tmp_path / "ds")
    make_synthetic_dataset(root, n_train=n_train, n_test=2, size=16)
    cfg = get_preset("facades")
    cfg = cfg.replace(
        model=dataclasses.replace(cfg.model, ngf=4, ndf=4),
        data=dataclasses.replace(cfg.data, batch_size=batch_size,
                                 image_size=16, threads=0),
        train=dataclasses.replace(cfg.train, mixed_precision=False,
                                  scan_steps=scan_steps, log_every=1000),
    )
    tr = Trainer(cfg, data_root=root, workdir=str(tmp_path))
    train_step, multi_step = _fake_steps()
    tr.train_step = train_step
    tr.multi_step = multi_step if scan_steps > 1 else None
    return tr


def _accounting_trainer(tmp_path, n_train, batch_size, scan_steps,
                        monkeypatch):
    from p2p_tpu.train import loop as loop_mod

    tr = _tiny_trainer(tmp_path, n_train, batch_size, scan_steps)
    monkeypatch.setattr(loop_mod.time, "perf_counter", _FakeClock())
    return tr


def test_train_epoch_throughput_math_scan_with_remainder(
        tmp_path, monkeypatch):
    """K=2 over 5 batches: 2 scanned dispatches + 1 single-step remainder.

    Fake-clock trace (+1 per perf_counter call):
      t0=1 | d1: call=2, first -> t0=3 | d2: call=4 | d3 (k=1, new
      dispatch shape): call=5, skew=6-5=1 | end=7.
    elapsed = 7 - 3 - 1(skew) = 3; steps counted = 5 - first_k(2) = 3
    -> img_per_sec = 3*bs/3 = bs exactly. The remainder dispatch's
    compile block lands in compile_skew, NOT in throughput."""
    tr = _accounting_trainer(tmp_path, n_train=10, batch_size=2,
                             scan_steps=2, monkeypatch=monkeypatch)
    out = tr.train_epoch()
    assert int(tr.state.step) == 5
    assert out["img_per_sec"] == pytest.approx(2.0)
    # metric averages cover every step
    assert out["loss_g"] == pytest.approx(1.0)
    assert out["loss_d"] == pytest.approx(2.0)


def test_train_epoch_throughput_math_single_step(tmp_path, monkeypatch):
    """K=1 over 3 batches: first dispatch excluded (compile), no skew.
      t0=1 | d1: call=2, first -> t0=3 | d2: call=4 | d3: call=5 | end=6
    elapsed = 6-3 = 3; counted steps = 3-1 = 2 -> 2*bs/3."""
    tr = _accounting_trainer(tmp_path, n_train=6, batch_size=2,
                             scan_steps=1, monkeypatch=monkeypatch)
    out = tr.train_epoch()
    assert int(tr.state.step) == 3
    assert out["img_per_sec"] == pytest.approx(2 * 2 / 3.0)


def test_train_epoch_all_scanned_no_remainder(tmp_path, monkeypatch):
    """K=2 over exactly 4 batches: no remainder path, skew must stay 0.
      t0=1 | d1: call=2, first -> t0=3 | d2: call=4 | end=5
    elapsed = 5-3 = 2; counted = 4-2 = 2 -> 2*bs/2 = bs."""
    tr = _accounting_trainer(tmp_path, n_train=8, batch_size=2,
                             scan_steps=2, monkeypatch=monkeypatch)
    out = tr.train_epoch()
    assert int(tr.state.step) == 4
    assert out["img_per_sec"] == pytest.approx(2.0)


@pytest.mark.slow
def test_evaluate_pad_and_trim_across_data_shards(tmp_path):
    """5 test images, test_batch_size=2, data=2 mesh: the odd tail batch
    is edge-padded to split across shards, and the padded duplicate must
    NOT be scored — exactly 5 per-image metrics come back."""
    import dataclasses

    from p2p_tpu.core.config import get_preset

    root = str(tmp_path / "ds")
    make_synthetic_dataset(root, n_train=2, n_test=5, size=16)
    cfg = get_preset("facades")
    cfg = cfg.replace(
        model=dataclasses.replace(cfg.model, ngf=4, ndf=4),
        data=dataclasses.replace(cfg.data, batch_size=2, image_size=16,
                                 test_batch_size=2, threads=0),
        parallel=dataclasses.replace(
            cfg.parallel, mesh=MeshSpec(data=2)),
        train=dataclasses.replace(cfg.train, mixed_precision=False),
    )
    tr = Trainer(cfg, data_root=root, workdir=str(tmp_path))
    result = tr.evaluate()
    assert result["n_images"] == 5
    assert np.isfinite(result["psnr_mean"])
    # padding by edge-repeat then trimming means the mean over 5 equals
    # the mean of the 5 individual scores — recompute via a second pass
    # with test_batch_size=5 (no padding needed) and compare.
    cfg2 = cfg.replace(
        data=dataclasses.replace(cfg.data, test_batch_size=6),
        parallel=dataclasses.replace(cfg.parallel, mesh=MeshSpec(data=1)),
    )
    tr2 = Trainer(cfg2, data_root=root, workdir=str(tmp_path))
    # cross-mesh handoff: tr's state is replicated over ITS (data=2) mesh;
    # re-place onto tr2's single-device mesh
    import jax

    from p2p_tpu.core.mesh import replicated

    tr2.state = jax.device_put(tr.state, replicated(tr2.mesh))
    result2 = tr2.evaluate()
    assert result2["n_images"] == 5
    assert result["psnr_mean"] == pytest.approx(result2["psnr_mean"],
                                                rel=1e-4)


# ------------------------------------------------------------ CLI tensor
# parallelism (the round-6 tentpole: Trainer builds the TP sharding tree
# itself when mesh.model > 1 — no more "decorative axis" warning)


def _cli_tp_harness(cfg_tp, cfg_single, root, tmp_path, probes, tol=5e-4):
    """Train ONE epoch with the TP Trainer and the single-device Trainer
    on identical data order; epoch-mean losses must agree to fp tolerance
    and the probe kernels must really be model-axis-sharded. ``tol`` is
    an EPOCH-level bound — reduction-order deltas compound across the
    epoch's steps (the one-step pins at 3e-4 live in test_parallel.py)."""
    tr_tp = Trainer(cfg_tp, data_root=root, workdir=str(tmp_path / "tp"))
    try:
        assert tr_tp.state_sharding is not None  # CLI-TP wired
        for path in probes:
            leaf = tr_tp.state.params_g
            for k in path:
                leaf = leaf[k]
            assert "model" in str(leaf.sharding.spec), (path, leaf.sharding)
        tp_metrics = tr_tp.train_epoch(seed=0)
    finally:
        tr_tp.close()
    tr_1 = Trainer(cfg_single, data_root=root,
                   workdir=str(tmp_path / "single"))
    try:
        ref_metrics = tr_1.train_epoch(seed=0)
    finally:
        tr_1.close()
    for k, v in ref_metrics.items():
        if k == "img_per_sec":
            continue
        assert tp_metrics[k] == pytest.approx(v, rel=tol, abs=tol), k
    return tp_metrics


@pytest.mark.slow
def test_cli_tp_trainer_matches_single_device_facades(tmp_path, devices8):
    """facades preset through the CLI-TP path: --mesh 2,1,1,2 with the
    Trainer-built tp_sharding_tree == the data=1 Trainer, same data."""
    import dataclasses

    from p2p_tpu.core.config import get_preset

    root = make_synthetic_dataset(str(tmp_path / "data"), 4, 2, size=64)
    cfg = get_preset("facades")
    cfg = cfg.replace(
        name="clitp_facades",
        model=dataclasses.replace(cfg.model, ngf=8, ndf=8),
        data=dataclasses.replace(cfg.data, batch_size=2, image_size=64,
                                 test_batch_size=2, threads=0),
        parallel=dataclasses.replace(
            cfg.parallel, mesh=MeshSpec(data=2, model=2), tp_min_ch=16),
        train=dataclasses.replace(cfg.train, mixed_precision=False,
                                  seed=0),
    )
    cfg_single = cfg.replace(parallel=dataclasses.replace(
        cfg.parallel, mesh=MeshSpec(data=1)))
    # ngf=8 U-Net: down3..5/up5 are 64-channel Megatron pairs at min_ch=16
    _cli_tp_harness(cfg, cfg_single, root, tmp_path, probes=[
        ("down3", "kernel"), ("down4", "kernel"), ("up5", "kernel"),
    ])


@pytest.mark.slow
def test_cli_tp_trainer_matches_single_device_pix2pixhd(tmp_path, devices8):
    """pix2pixhd preset through the CLI-TP path (norm='instance' — the
    XLA norm partitions natively under channel shards, tp.py docstring):
    TP Trainer == single-device Trainer on identical data."""
    import dataclasses

    from p2p_tpu.core.config import get_preset

    root = make_synthetic_dataset(str(tmp_path / "data"), 4, 2, size=32)
    cfg = get_preset("pix2pixhd")
    cfg = cfg.replace(
        name="clitp_hd",
        model=dataclasses.replace(cfg.model, ngf=8, ndf=8, n_blocks=1,
                                  num_D=2, n_layers_D=2, norm="instance"),
        loss=dataclasses.replace(cfg.loss, lambda_vgg=0.0),
        data=dataclasses.replace(cfg.data, batch_size=2, image_size=32,
                                 image_width=64, test_batch_size=2,
                                 threads=0),
        parallel=dataclasses.replace(
            cfg.parallel, mesh=MeshSpec(data=2, model=2), tp_min_ch=16),
        train=dataclasses.replace(cfg.train, mixed_precision=False,
                                  seed=0),
    )
    cfg_single = cfg.replace(parallel=dataclasses.replace(
        cfg.parallel, mesh=MeshSpec(data=1)))
    # 5e-3: the spectral-norm u/v iteration feeds the feature-matching
    # loss, so the per-step ~3e-4 reduction-order delta compounds over
    # the epoch (observed ~1.7e-3 on g_feat after 2 steps)
    _cli_tp_harness(cfg, cfg_single, root, tmp_path, probes=[
        ("global", "ConvLayer_3", "Conv_0", "kernel"),
        ("global", "ConvLayer_4", "Conv_0", "kernel"),
    ], tol=5e-3)


# ------------------------------------------------------- the epoch record
def _phase_trainer(tmp_path, monkeypatch, scan_steps=1):
    """The tiny Trainer on the real clock with dispatches that take a
    while, so that an epoch is long beside the microseconds between two
    phases; on the plain loader, whose batches are assembled when asked
    for (Grain reads ahead in threads, which would hide a slow record in
    the prefetch fill)."""
    monkeypatch.setenv("P2P_TPU_NO_GRAIN", "1")
    tr = _tiny_trainer(tmp_path, n_train=12, batch_size=2,
                       scan_steps=scan_steps)

    def lasting(step):
        def slept(*a):
            time.sleep(0.03)
            return step(*a)

        return slept

    tr.train_step = lasting(tr.train_step)
    tr.multi_step = tr.multi_step and lasting(tr.multi_step)
    return tr


def _epoch_records(tr):
    return [s for s in tr.spans.spans if s["name"] == "train_epoch"]


@pytest.mark.parametrize("scan_steps, dispatches", [(1, 6), (2, 3)])
def test_train_epoch_leaves_one_record_whose_phases_tile_it(
        tmp_path, monkeypatch, scan_steps, dispatches):
    """Every second of train_epoch falls into one phase: the record's
    direct children sum to its duration (to the few microseconds between
    two ``with`` blocks), the per-step phases are counted once a dispatch,
    and the same record is in the metrics stream."""
    import json

    tr = _phase_trainer(tmp_path, monkeypatch, scan_steps)
    try:
        tr.train_epoch()          # decodes; the memo serves the second
        tr.epoch += 1
        tr.train_epoch()
        first, rec = _epoch_records(tr)
        assert (first["epoch"], rec["epoch"]) == (1, 2)
        assert rec["steps"] == 6 and rec["depth"] == 0
        children = sum(rec[f"{p}_s"] for p in (
            "epoch_setup", "feed_next", "train_dispatch",
            "step_bookkeeping", "epoch_drain"))
        assert 0.95 * rec["dur_s"] <= children <= rec["dur_s"]
        # loader_next and h2d_put nest inside feed_next
        assert rec["loader_next_s"] + rec["h2d_put_s"] <= rec["feed_next_s"]
        assert rec["first_feed_next_s"] <= rec["feed_next_s"]
        assert rec["epoch_setup_s"] + rec["first_feed_next_s"] <= \
            rec["epoch_start_s"] <= rec["dur_s"]
        for name in ("feed_next_secs", "dispatch_secs",
                     "step_bookkeeping_secs"):
            assert tr.obs.histogram(name).count == 2 * dispatches, name
        for name in ("train_epoch_secs", "epoch_setup_secs",
                     "epoch_drain_secs"):
            assert tr.obs.histogram(name).count == 2, name
        assert tr.obs.histogram("h2d_put_secs").count == 2 * dispatches
        tr.logger.registry.flush()
        stream = [json.loads(x) for x in open(
            os.path.join(str(tmp_path), f"metrics_{tr.cfg.name}.jsonl"))]
        spans = [r for r in stream if r["kind"] == "span"]
        assert [r["span"] for r in spans] == ["train_epoch"] * 2
        assert spans[1]["sec"] == pytest.approx(rec["dur_s"], abs=1e-5)
        assert spans[1]["slowest_train_dispatch_step"] in range(0, 6, scan_steps)
    finally:
        tr.close()


def test_slow_record_is_the_epochs_slowest_feed_next(tmp_path, monkeypatch):
    """A dataset whose ``__getitem__`` stalls on one index: the epoch
    record names the step that waited for it, and the wait lies in the
    loader's ``next()``, not in the transfer."""
    from p2p_tpu.data.pipeline import PairedImageDataset

    tr = _phase_trainer(tmp_path, monkeypatch)
    order, slow = [], []
    getitem = PairedImageDataset.__getitem__

    def recording(self, idx):
        order.append(int(idx))
        if int(idx) in slow:
            time.sleep(0.25)
        return getitem(self, idx)

    monkeypatch.setattr(PairedImageDataset, "__getitem__", recording)
    try:
        tr.train_epoch(seed=5)
        # the same seed shuffles the same way: stall the first record of
        # the fourth batch. The prefetch keeps two batches in flight, so
        # batch 3 is assembled while the loop waits before step 2
        slow.append(order[3 * 2])
        del order[:]
        tr.train_epoch(seed=5)
        rec = _epoch_records(tr)[-1]
        assert order.index(slow[0]) == 6
        assert rec["slowest_feed_next_step"] == 2
        assert 0.25 <= rec["slowest_feed_next_s"] <= rec["feed_next_s"]
        assert rec["loader_next_s"] >= 0.25 > rec["h2d_put_s"]
        assert rec["first_feed_next_s"] < 0.25
    finally:
        tr.close()
