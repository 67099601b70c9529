import numpy as np
import pytest

import jax
import jax.numpy as jnp

from p2p_tpu.core.config import (
    Config,
    DataConfig,
    LossConfig,
    ModelConfig,
    OptimConfig,
    ParallelConfig,
    TrainConfig,
    get_preset,
)
from p2p_tpu.core.mesh import MeshSpec
from p2p_tpu.data.synthetic import synthetic_batch
from p2p_tpu.train.schedules import PlateauController, lambda_rule, make_schedule
from p2p_tpu.train.state import create_train_state
from p2p_tpu.train.step import build_eval_step, build_train_step


def tiny_config(**model_kw):
    """Small reference-style config: all losses live, 2 res blocks, ndf=8."""
    return Config(
        name="tiny",
        model=ModelConfig(ngf=8, n_blocks=2, ndf=8, num_D=2, **model_kw),
        loss=LossConfig(lambda_feat=10.0, lambda_vgg=0.0, lambda_tv=1.0),
        optim=OptimConfig(niter=2, niter_decay=2),
        data=DataConfig(batch_size=2, image_size=32),
        parallel=ParallelConfig(mesh=MeshSpec(data=1)),
        train=TrainConfig(seed=0, mixed_precision=False),
    )


@pytest.fixture(scope="module")
def batch():
    return {k: jnp.asarray(v) for k, v in synthetic_batch(2, 32).items()}


# ------------------------------------------------------------- schedules
def test_lambda_rule_exact_values():
    # niter=100, niter_decay=100, epoch_count=1: flat until epoch 99,
    # then linear to ~0 (networks.py:106-109)
    assert float(lambda_rule(0, 1, 100, 100)) == 1.0
    assert float(lambda_rule(99, 1, 100, 100)) == 1.0
    np.testing.assert_allclose(
        float(lambda_rule(100, 1, 100, 100)), 1 - 1 / 101, rtol=1e-6
    )
    np.testing.assert_allclose(
        float(lambda_rule(199, 1, 100, 100)), 1 - 100 / 101, rtol=1e-5
    )


def test_schedules_per_policy():
    cfg = OptimConfig(lr=2e-4, niter=10, niter_decay=10, lr_decay_iters=5)
    lam = make_schedule(cfg, steps_per_epoch=4)
    assert float(lam(0)) == pytest.approx(2e-4)
    assert float(lam(4 * 12)) == pytest.approx(2e-4 * (1 - 3 / 11))
    step = make_schedule(
        OptimConfig(lr=1.0, lr_policy="step", lr_decay_iters=5), 1
    )
    assert float(step(4)) == pytest.approx(1.0)
    assert float(step(5)) == pytest.approx(0.1)
    assert float(step(10)) == pytest.approx(0.01, rel=1e-5)
    cos = make_schedule(OptimConfig(lr=1.0, lr_policy="cosine", niter=10), 1)
    assert float(cos(0)) == pytest.approx(1.0)
    assert float(cos(5)) == pytest.approx(0.5)
    assert float(cos(10)) == pytest.approx(0.0, abs=1e-7)


def test_schedule_fresh_epoch_count_matches_reference_formula():
    """A FRESH run with --epoch_count N starts the lambda curve at epoch N,
    exactly the reference formula 1 - max(0, e + epoch_count - niter) /
    (niter_decay + 1) with the scheduler's local 0-based epoch e
    (networks.py:106-109)."""
    cfg = OptimConfig(lr=1.0, niter=2, niter_decay=4)
    sched = make_schedule(cfg, steps_per_epoch=2, epoch_count=5)
    for step, local_e in [(0, 0), (1, 0), (2, 1), (5, 2)]:
        ref = max(0.0, 1.0 - max(0, local_e + 5 - 2) / 5.0)
        assert float(sched(step)) == pytest.approx(ref), (step, local_e)


def test_schedule_resume_normalized_continues_curve():
    """The resume contract (Trainer.maybe_resume rebuilds with
    epoch_count=1): the schedule of the ABSOLUTE restored step must equal
    the hand-computed decay curve — with niter=2, niter_decay=4, spe=2,
    epoch e (0-based) has mult = 1 - max(0, e-1)/5. The buggy round-3
    wiring (absolute step AND the epoch_count offset) clamps to LR=0
    instead (hd_r3 bug). The end-to-end contract is pinned by
    tests/test_loop.py::test_resume_into_decay_window_continues_lr_curve."""
    cfg = OptimConfig(lr=1.0, niter=2, niter_decay=4)
    resumed = make_schedule(cfg, steps_per_epoch=2, epoch_count=1)
    # steps 8..11 are epochs 5-6 (0-based 4-5), inside the decay window
    for step in range(8, 12):
        e = step // 2
        expect = 1.0 - max(0, e + 1 - 2) / 5.0
        assert float(resumed(step)) == pytest.approx(expect)
        assert float(resumed(step)) > 0.0
    # the buggy wiring (restored absolute step AND epoch_count=5 offset)
    # would clamp to zero here:
    buggy = make_schedule(cfg, steps_per_epoch=2, epoch_count=5)
    assert float(buggy(8)) == 0.0


def test_plateau_controller():
    pc = PlateauController(patience=2)
    scales = [pc.update(1.0) for _ in range(10)]
    # best=1.0 at first update; 3 bad epochs → one reduction within 4 updates
    assert scales[0] == 1.0
    assert scales[-1] < 1.0


# ------------------------------------------------------------ train step
@pytest.mark.slow
def test_train_step_runs_and_updates_everything(batch):
    cfg = tiny_config()
    state = create_train_state(cfg, jax.random.key(0), batch, 1)
    step_fn = build_train_step(cfg, None, 1, None, jit=True)
    state1, metrics = step_fn(state, batch)

    assert int(state1.step) == 1
    for key in ("loss_d", "loss_g", "loss_c", "g_gan", "g_feat", "g_tv"):
        v = float(metrics[key])
        assert np.isfinite(v), key

    # G, D and C params all moved
    def moved(a, b):
        return any(
            not np.allclose(x, y)
            for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b))
        )

    # state was donated; compare against a freshly created identical state
    state0 = create_train_state(cfg, jax.random.key(0), batch, 1)
    assert moved(state0.params_g, state1.params_g)
    assert moved(state0.params_d, state1.params_d)
    assert moved(state0.params_c, state1.params_c)  # STE makes C trainable (Q1/Q2 fixed)
    assert moved(state0.batch_stats_g, state1.batch_stats_g)
    assert moved(state0.spectral_d, state1.spectral_d)


@pytest.mark.slow
def test_train_step_uint8_batch_matches_f32():
    """The uint8 batch contract (device-side ingest at step entry) matches
    the f32 pipeline: the normalized INPUT is bit-exact (same canonical
    f32 expression), and one full train step agrees at the 1-ulp level —
    the residual comes from XLA fusing the convert chain differently in
    the two compiled programs (measured: two reduced scalar metrics off by
    6e-8, params by 2e-8), not from the normalize. Eval is bit-exact."""
    from p2p_tpu.train.step import build_eval_step
    from p2p_tpu.utils.images import ingest

    rng = np.random.default_rng(42)
    u8 = {k: rng.integers(0, 256, (2, 32, 32, 3)).astype(np.uint8)
          for k in ("input", "target")}
    # the canonical normalize expression — (x − 127.5)·(1/127.5), what
    # load_image, fastimage.cpp and ingest all compute (FMA-proof form)
    f32 = {k: (v.astype(np.float32) - np.float32(127.5))
           * np.float32(1.0 / 127.5) for k, v in u8.items()}
    for k in u8:  # the ingest contract itself is bit-exact, jit or not
        np.testing.assert_array_equal(
            np.asarray(jax.jit(ingest)(jnp.asarray(u8[k]))), f32[k])

    cfg = tiny_config()
    step_fn = build_train_step(cfg, None, 1, None, jit=True)
    out = {}
    for tag, b in (("u8", u8), ("f32", f32)):
        state = create_train_state(cfg, jax.random.key(0), b, 1)
        s1, m = step_fn(state, {k: jnp.asarray(v) for k, v in b.items()})
        out[tag] = (s1, m)
    for k in out["f32"][1]:
        np.testing.assert_allclose(
            np.asarray(out["u8"][1][k]), np.asarray(out["f32"][1][k]),
            rtol=0, atol=1e-6, err_msg=k)
    for a, b in zip(jax.tree_util.tree_leaves(out["u8"][0].params_g),
                    jax.tree_util.tree_leaves(out["f32"][0].params_g)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=0, atol=1e-6)

    eval_fn = build_eval_step(cfg, None)
    state = create_train_state(cfg, jax.random.key(0), u8, 1)
    p8, m8 = eval_fn(state, {k: jnp.asarray(v) for k, v in u8.items()})
    pf, mf = eval_fn(state, {k: jnp.asarray(v) for k, v in f32.items()})
    np.testing.assert_array_equal(np.asarray(p8), np.asarray(pf))
    np.testing.assert_array_equal(np.asarray(m8["psnr"]),
                                  np.asarray(mf["psnr"]))


def test_train_step_split_d_pairs_matches_concat(batch):
    """ModelConfig.split_d_pairs (D fed the unconcatenated (a,b) pair,
    the HD-extent form) matches the concat step to fp tolerance: same
    losses, same updated G and D params."""
    import dataclasses

    cfg_c = tiny_config()
    cfg_s = cfg_c.replace(model=dataclasses.replace(
        cfg_c.model, split_d_pairs=True))
    out = {}
    for tag, cfg in (("concat", cfg_c), ("split", cfg_s)):
        state = create_train_state(cfg, jax.random.key(0), batch, 1)
        s1, m = build_train_step(cfg, None, 1, None)(state, dict(batch))
        out[tag] = (s1, m)
    for k in out["concat"][1]:
        np.testing.assert_allclose(
            float(out["split"][1][k]), float(out["concat"][1][k]),
            rtol=2e-4, atol=2e-4, err_msg=k)
    for tree in ("params_g", "params_d"):
        for a, b in zip(
            jax.tree_util.tree_leaves(getattr(out["split"][0], tree)),
            jax.tree_util.tree_leaves(getattr(out["concat"][0], tree)),
        ):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-4, atol=5e-4)


def test_scale_by_adam_lp_matches_f32_adam():
    """scale_by_adam_lp (bf16-stored moments, OptimConfig.moment_dtype):
    with float32 storage it reproduces optax.adam's trajectory exactly
    (same math, storage cast is a no-op); with bfloat16 storage it tracks
    within bf16 rounding over multiple steps."""
    import optax

    from p2p_tpu.train.state import scale_by_adam_lp

    params = {"w": jnp.asarray(np.random.default_rng(0)
                               .standard_normal((16, 16)), jnp.float32)}
    g_rng = np.random.default_rng(1)

    def run(opt):
        p = params
        st = opt.init(p)
        for _ in range(5):
            g = {"w": jnp.asarray(g_rng.standard_normal((16, 16)) * 0.1,
                                  jnp.float32)}
            up, st = opt.update(g, st, p)
            p = optax.apply_updates(p, up)
        return p

    lr = 1e-3
    ref = run(optax.adam(lr, b1=0.5, b2=0.999))
    g_rng = np.random.default_rng(1)
    lp32 = run(optax.chain(scale_by_adam_lp(0.5, 0.999, 1e-8, "float32"),
                           optax.scale_by_learning_rate(lr)))
    np.testing.assert_allclose(np.asarray(lp32["w"]), np.asarray(ref["w"]),
                               rtol=1e-6, atol=1e-8)
    g_rng = np.random.default_rng(1)
    lp16 = run(optax.chain(scale_by_adam_lp(0.5, 0.999, 1e-8, "bfloat16"),
                           optax.scale_by_learning_rate(lr)))
    # moments round to bf16 between steps: trajectories agree to ~2⁻⁸
    np.testing.assert_allclose(np.asarray(lp16["w"]), np.asarray(ref["w"]),
                               rtol=0, atol=2e-4)


def test_train_step_no_compression_pix2pix(batch):
    cfg = tiny_config(use_compression_net=False, use_spectral_norm=False)
    cfg = Config(
        name=cfg.name, model=cfg.model,
        loss=LossConfig(lambda_feat=0.0, lambda_vgg=0.0, lambda_tv=0.0,
                        lambda_l1=100.0),
        optim=cfg.optim, data=cfg.data, parallel=cfg.parallel, train=cfg.train,
    )
    state = create_train_state(cfg, jax.random.key(0), batch, 1)
    step_fn = build_train_step(cfg, None, 1, None)
    state1, metrics = step_fn(state, batch)
    assert float(metrics["loss_c"]) == 0.0
    assert "g_l1" in metrics and float(metrics["g_l1"]) > 0
    assert state1.params_c is None


@pytest.mark.slow
def test_loss_decreases_over_steps(batch):
    cfg = tiny_config()
    state = create_train_state(cfg, jax.random.key(0), batch, 1)
    step_fn = build_train_step(cfg, None, 1, None)
    losses = []
    for _ in range(8):
        state, metrics = step_fn(state, batch)
        losses.append(float(metrics["loss_g"]))
    # overfitting one batch: generator loss should drop substantially
    assert losses[-1] < losses[0]


@pytest.mark.slow
def test_lr_scale_gates_updates(batch):
    """lr_scale=0 (plateau floor) must freeze all params; the schedules'
    PlateauController drives this field host-side."""
    cfg = tiny_config()
    state = create_train_state(cfg, jax.random.key(0), batch, 1)
    state = state.replace(lr_scale=jnp.zeros((), jnp.float32))
    before = jax.tree_util.tree_map(np.asarray, state.params_g)
    step_fn = build_train_step(cfg, None, 1, None)
    state1, _ = step_fn(state, batch)
    for a, b in zip(jax.tree_util.tree_leaves(before),
                    jax.tree_util.tree_leaves(state1.params_g)):
        np.testing.assert_allclose(a, b, atol=0)


@pytest.mark.slow
def test_bug_compatible_quantizer_freezes_c(batch):
    cfg = tiny_config(quant_ste=False)
    state0 = create_train_state(cfg, jax.random.key(0), batch, 1)
    params_c_before = jax.tree_util.tree_map(np.asarray, state0.params_c)
    step_fn = build_train_step(cfg, None, 1, None)
    state1, _ = step_fn(state0, batch)
    for a, b in zip(
        jax.tree_util.tree_leaves(params_c_before),
        jax.tree_util.tree_leaves(state1.params_c),
    ):
        np.testing.assert_allclose(a, b, atol=1e-7)  # round() blocks grads (Q2)


def test_eval_step(batch):
    cfg = tiny_config()
    state = create_train_state(cfg, jax.random.key(0), batch, 1)
    eval_fn = build_eval_step(cfg)
    pred, metrics = eval_fn(state, batch)
    assert pred.shape == batch["target"].shape
    # per-image metric vectors (one entry per batch element)
    assert metrics["psnr"].shape == (batch["target"].shape[0],)
    assert np.all((0 < np.asarray(metrics["psnr"]))
                  & (np.asarray(metrics["psnr"]) <= 60.0))
    assert np.all((-1.0 <= np.asarray(metrics["ssim"]))
                  & (np.asarray(metrics["ssim"]) <= 1.0))


# ------------------------------------------------------------ checkpoint
@pytest.mark.slow
def test_checkpoint_roundtrip(tmp_path, batch):
    from p2p_tpu.train.checkpoint import CheckpointManager

    cfg = tiny_config()
    state = create_train_state(cfg, jax.random.key(0), batch, 1)
    step_fn = build_train_step(cfg, None, 1, None)
    state, _ = step_fn(state, batch)

    mgr = CheckpointManager(str(tmp_path / "ckpt"))
    mgr.save(1, state, wait=True)
    template = create_train_state(cfg, jax.random.key(1), batch, 1)
    restored = mgr.restore(template)
    for a, b in zip(
        jax.tree_util.tree_leaves(state), jax.tree_util.tree_leaves(restored)
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # training continues bitwise-identically from the restored state
    s1, m1 = step_fn(state, batch)
    s2, m2 = step_fn(restored, batch)
    np.testing.assert_allclose(
        float(m1["loss_g"]), float(m2["loss_g"]), rtol=1e-6
    )
    mgr.close()


def test_checkpoint_f32_moments_restore_into_bf16_template(tmp_path, batch):
    """Backward compat for the round-5 facades_int8 preset flip: an OLD
    checkpoint (f32 Adam moments) restores into the NEW template (bf16
    moments, OptimConfig.moment_dtype) — Orbax casts to the template
    dtype, preserving the moment VALUES to bf16 rounding rather than
    leaving template zeros or raising."""
    import dataclasses

    from p2p_tpu.train.checkpoint import CheckpointManager

    cfg16 = tiny_config()
    cfg16 = cfg16.replace(optim=dataclasses.replace(
        cfg16.optim, moment_dtype="bfloat16"))
    cfg32 = tiny_config()

    old = create_train_state(cfg32, jax.random.key(0), batch, 1)
    old, _ = build_train_step(cfg32, None, 1, None)(old, dict(batch))

    mgr = CheckpointManager(str(tmp_path / "ckpt"))
    mgr.save(1, old, wait=True)
    template = create_train_state(cfg16, jax.random.key(1), batch, 1)
    restored = mgr.restore(template)
    mgr.close()

    checked = 0
    for a, b in zip(jax.tree_util.tree_leaves(old.opt_g),
                    jax.tree_util.tree_leaves(restored.opt_g)):
        a32 = np.asarray(a, np.float32)
        if a32.size <= 10 or np.abs(a32).max() == 0:
            continue
        assert np.asarray(b).dtype == jnp.bfloat16
        rel = (np.abs(a32 - np.asarray(b, np.float32)).max()
               / np.abs(a32).max())
        assert rel < 1e-2, rel   # bf16 rounding, not zeros
        checked += 1
    assert checked > 0


@pytest.mark.slow
def test_multi_step_scan_matches_sequential():
    """build_multi_train_step(K) == K sequential build_train_step calls."""
    import dataclasses

    from p2p_tpu.core.config import get_preset
    from p2p_tpu.train.state import create_train_state
    from p2p_tpu.train.step import build_multi_train_step, build_train_step

    cfg = get_preset("reference")
    cfg = cfg.replace(
        model=dataclasses.replace(cfg.model, ngf=4, n_blocks=1, ndf=4,
                                  num_D=2, n_layers_D=2),
        data=dataclasses.replace(cfg.data, batch_size=2, image_size=16),
        loss=dataclasses.replace(cfg.loss, lambda_vgg=0.0),
    )
    rng = np.random.default_rng(0)
    K = 3
    stacked = {
        k: jnp.asarray(rng.uniform(-1, 1, (K, 2, 16, 16, 3)), jnp.float32)
        for k in ("input", "target")
    }
    single0 = {k: v[0] for k, v in stacked.items()}

    state_a = create_train_state(cfg, jax.random.key(0), single0)
    step = build_train_step(cfg)
    seq_losses = []
    for i in range(K):
        state_a, m = step(state_a, {k: v[i] for k, v in stacked.items()})
        seq_losses.append(float(m["loss_g"]))

    state_b = create_train_state(cfg, jax.random.key(0), single0)
    mstep = build_multi_train_step(cfg)
    state_b, ms = mstep(state_b, stacked)
    np.testing.assert_allclose(
        np.asarray(ms["loss_g"]), np.asarray(seq_losses), rtol=2e-4, atol=2e-4
    )
    assert int(state_b.step) == K
    # Adam updates are ~lr-sized regardless of gradient magnitude, so fp
    # reassociation between scan and unrolled execution can move any
    # near-zero-gradient parameter by O(lr) per step — compare at 3*lr.
    for la, lb in zip(jax.tree_util.tree_leaves(state_a.params_g),
                      jax.tree_util.tree_leaves(state_b.params_g)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   rtol=1e-3, atol=8 * 2e-4)


def test_device_pool_semantics():
    """device_pool_query matches reference ImagePool behavior: fill phase
    passes through and stores; once full, outputs are either the incoming
    pair or a previously stored one, swaps happen with p≈0.5, and the
    buffer only ever contains previously-seen pairs."""
    from p2p_tpu.utils.pool import device_pool_query

    P, n_steps = 4, 64
    pool = jnp.zeros((P, 2, 2, 1), jnp.float32)
    pool_n = jnp.zeros((), jnp.int32)
    stored = set()
    swaps = 0
    q = jax.jit(device_pool_query)
    for i in range(n_steps):
        incoming = float(i + 1)
        pair = jnp.full((1, 2, 2, 1), incoming)
        out, pool, pool_n = q(pool, pool_n, pair, jax.random.key(i))
        val = float(out[0, 0, 0, 0])
        if i < P:
            assert val == incoming       # fill phase: passthrough + store
            assert int(pool_n) == i + 1
            stored.add(incoming)
        else:
            assert int(pool_n) == P
            if val != incoming:          # swap: returned pair must have
                assert val in stored     # been stored earlier; buffer now
                stored.discard(val)      # holds the incoming pair instead
                stored.add(incoming)
                swaps += 1
            # else passthrough: buffer untouched
    assert 0.25 < swaps / (n_steps - P) < 0.75  # p≈0.5 swap rate


@pytest.mark.slow
def test_train_step_with_pool_enabled(tmp_path):
    """pool_size > 0 threads the ring buffer through the jitted step, the
    Orbax checkpoint round-trip, and a restore into a template rebuilt the
    way cli.infer does (preset + pool_size flag)."""
    import dataclasses

    cfg = get_preset("facades")
    cfg = cfg.replace(
        data=dataclasses.replace(cfg.data, batch_size=2, image_size=32),
        train=dataclasses.replace(cfg.train, pool_size=8),
    )
    batch = {
        "input": jnp.asarray(
            np.random.default_rng(0).uniform(-1, 1, (2, 32, 32, 3)),
            jnp.float32),
        "target": jnp.asarray(
            np.random.default_rng(1).uniform(-1, 1, (2, 32, 32, 3)),
            jnp.float32),
    }
    state = create_train_state(cfg, jax.random.key(0), batch)
    assert state.pool.shape == (8, 32, 32, 6)
    step = build_train_step(cfg)
    state, _ = step(state, batch)
    state, _ = step(state, batch)
    assert int(state.pool_n) == 4  # two steps x bs2 fill four slots
    assert float(jnp.abs(state.pool[:4]).sum()) > 0

    from p2p_tpu.train.checkpoint import CheckpointManager

    ckpt = CheckpointManager(str(tmp_path))
    ckpt.save(2, state, wait=True)
    template = create_train_state(cfg, jax.random.key(1), batch)
    restored = ckpt.restore(template, 2)
    np.testing.assert_array_equal(np.asarray(restored.pool),
                                  np.asarray(state.pool))
    assert int(restored.pool_n) == 4


def test_device_pool_boundary_batch_never_returns_zeros():
    """ADVICE r1: a batch crossing the fill boundary must never hand D an
    uninitialized all-zeros pair — swap draws address only slots filled in
    the PRE-update pool (pool_n), not slots being filled by earlier samples
    of the same batch."""
    from p2p_tpu.utils.pool import device_pool_query

    P, bs = 4, 2
    q = jax.jit(device_pool_query)
    for key in range(200):
        # pool_n=3 of 4 filled with nonzero sentinels; batch of 2 crosses
        # the boundary (one fills slot 3, one is past the boundary).
        pool = jnp.concatenate([
            jnp.full((3, 2, 2, 1), 7.0), jnp.zeros((1, 2, 2, 1))])
        pool_n = jnp.asarray(3, jnp.int32)
        pairs = jnp.stack([jnp.full((2, 2, 1), 11.0),
                           jnp.full((2, 2, 1), 12.0)])
        out, new_pool, new_n = q(pool, pool_n, pairs, jax.random.key(key))
        vals = np.asarray(out).reshape(bs, -1)[:, 0]
        assert (vals != 0.0).all(), (key, vals)
        assert set(np.round(vals, 3)).issubset({7.0, 11.0, 12.0})
        assert int(new_n) == 4
    # empty-pool edge: first batch larger than the whole pool passes through
    pool = jnp.zeros((2, 2, 2, 1))
    pairs = jnp.stack([jnp.full((2, 2, 1), float(v)) for v in (1, 2, 3, 4)])
    for key in range(50):
        out, _, _ = q(pool, jnp.asarray(0, jnp.int32), pairs,
                      jax.random.key(key))
        np.testing.assert_array_equal(np.asarray(out), np.asarray(pairs))


@pytest.mark.slow
def test_coarse_to_fine_graft_roundtrip(tmp_path):
    """VERDICT r1 #7: phase-1 (pix2pixhd_global) params transfer into the
    full Pix2PixHDGenerator — checkpoint restore + graft + forward, with
    the embedded-G1 leaves bitwise equal to phase 1 and only the image
    head dropped."""
    import dataclasses

    from p2p_tpu.data.synthetic import synthetic_batch
    from p2p_tpu.train.checkpoint import CheckpointManager
    from p2p_tpu.train.graft import g1_phase_config, load_and_graft_g1

    cfg = get_preset("pix2pixhd")
    cfg = cfg.replace(
        name="hdtest",
        model=dataclasses.replace(cfg.model, ngf=8, ndf=8, n_blocks=2,
                                  num_D=2, n_layers_D=2),
        loss=dataclasses.replace(cfg.loss, lambda_vgg=0.0),
        data=dataclasses.replace(cfg.data, batch_size=1, image_size=32,
                                 image_width=64),
        parallel=dataclasses.replace(cfg.parallel,
                                     mesh=MeshSpec(data=1)),
        train=dataclasses.replace(cfg.train, mixed_precision=False,
                                  checkpoint_dir=str(tmp_path / "ckpt")),
    )
    g1_cfg = g1_phase_config(cfg)
    assert g1_cfg.model.generator == "pix2pixhd_global"
    assert g1_cfg.data.image_size == 16 and g1_cfg.data.image_width == 32
    assert g1_cfg.name == "hdtest_g1"

    # phase 1: one real step, then checkpoint
    rng = np.random.default_rng(0)
    b1 = {k: jnp.asarray(rng.uniform(-1, 1, (1, 16, 32, 3)), jnp.float32)
          for k in ("input", "target")}
    s1 = create_train_state(g1_cfg, jax.random.key(0), b1)
    step1 = build_train_step(g1_cfg)
    s1, _ = step1(s1, b1)
    g1_dir = str(tmp_path / "ckpt" / cfg.data.dataset / g1_cfg.name)
    mgr = CheckpointManager(g1_dir)
    mgr.save(1, s1, wait=True)

    # phase 2: fresh full state + graft
    b2 = {k: jnp.asarray(rng.uniform(-1, 1, (1, 32, 64, 3)), jnp.float32)
          for k in ("input", "target")}
    s2 = create_train_state(cfg, jax.random.key(1), b2)
    before = np.asarray(
        s2.params_g["global"]["ResnetBlock_0"]["ConvLayer_0"]["Conv_0"]["kernel"])
    s2 = load_and_graft_g1(s2, cfg, g1_dir=g1_dir)
    after = s2.params_g["global"]["ResnetBlock_0"]["ConvLayer_0"]["Conv_0"]["kernel"]
    want = s1.params_g["ResnetBlock_0"]["ConvLayer_0"]["Conv_0"]["kernel"]
    np.testing.assert_array_equal(np.asarray(after), np.asarray(want))
    assert not np.array_equal(np.asarray(after), before)

    # grafted full model trains
    step2 = build_train_step(cfg)
    s2b, metrics = step2(s2, b2)
    assert np.isfinite([float(v) for v in metrics.values()]).all()

    # missing phase-1 checkpoint raises cleanly
    with pytest.raises(FileNotFoundError):
        load_and_graft_g1(create_train_state(cfg, jax.random.key(2), b2),
                          cfg, g1_dir=str(tmp_path / "nope"))


def test_lambda_rule_clamped_at_zero():
    """Past niter+niter_decay the reference formula goes negative (gradient
    ASCENT); the framework clamps at 0."""
    assert float(lambda_rule(400, 1, 100, 100)) == 0.0
    assert float(lambda_rule(199, 1, 100, 100)) > 0.0


def test_sobel_loss_term_and_warmup():
    """lambda_sobel adds a g_sobel term; sobel_warmup_epochs ramps it
    with the epoch index (reference train.py:445-448 shape)."""
    import dataclasses

    cfg = tiny_config()
    cfg = cfg.replace(loss=dataclasses.replace(
        cfg.loss, lambda_sobel=5.0, sobel_warmup_epochs=4))
    b = {k: jnp.asarray(v) for k, v in synthetic_batch(2, 32).items()}
    # steps_per_epoch=1 → epoch index == step+1; weight = 5·min(e/4, 1).
    # The raw edge-L1 changes as G trains, so compare the FIRST step of a
    # warmup run against a no-warmup twin from the same init: the ratio
    # must be the epoch-1 ramp value (1/4).
    state = create_train_state(cfg, jax.random.key(0), b, 1)
    step_fn = build_train_step(cfg, None, 1, None, jit=True)
    state, mw = step_fn(state, b)
    assert np.isfinite(float(mw["g_sobel"]))
    cfg0 = cfg.replace(loss=dataclasses.replace(
        cfg.loss, sobel_warmup_epochs=0))
    state0 = create_train_state(cfg0, jax.random.key(0), b, 1)
    step0 = build_train_step(cfg0, None, 1, None, jit=True)
    _, m0 = step0(state0, b)
    assert float(mw["g_sobel"]) == pytest.approx(
        0.25 * float(m0["g_sobel"]), rel=1e-5)


def test_angular_loss_uses_illumination_quotients():
    """The reference's commented angular experiment (train.py:356-360)
    compares real_a/max(real_b,eps) vs real_a/max(fake_b,eps) — NOT raw
    images. With the compression net active, fake_b is a function of
    real_b only, so changing real_a must change g_angular (the raw-image
    form ignored real_a entirely)."""
    import dataclasses

    cfg = tiny_config()
    assert cfg.model.use_compression_net
    cfg = cfg.replace(loss=dataclasses.replace(cfg.loss, lambda_angular=2.0))
    b1 = {k: jnp.asarray(v) for k, v in synthetic_batch(2, 32).items()}
    # second batch: same target (→ identical fake_b), different input
    b2 = dict(b1)
    b2["input"] = jnp.roll(b1["input"], 7, axis=1) * 0.5 + 0.1
    step_fn = build_train_step(cfg, None, 1, None, jit=True)
    state = create_train_state(cfg, jax.random.key(0), b1, 1)
    _, m1 = step_fn(state, b1)
    state = create_train_state(cfg, jax.random.key(0), b1, 1)
    _, m2 = step_fn(state, b2)
    a1, a2 = float(m1["g_angular"]), float(m2["g_angular"])
    assert np.isfinite(a1) and np.isfinite(a2) and a1 > 0
    assert a1 != pytest.approx(a2, rel=1e-4)


def test_nonfinite_grad_counter_surfaces_in_metrics():
    """grad_clip>0 activates the zero-nonfinite guard; the step must
    surface how many entries it dropped (ADVICE r2: silent masking)."""
    import dataclasses

    cfg = tiny_config()
    cfg = cfg.replace(optim=dataclasses.replace(cfg.optim, grad_clip=1.0))
    b = {k: jnp.asarray(v) for k, v in synthetic_batch(2, 32).items()}
    state = create_train_state(cfg, jax.random.key(0), b, 1)
    step_fn = build_train_step(cfg, None, 1, None, jit=True)
    _, m = step_fn(state, b)
    assert m["nonfinite_g"].shape == () and m["nonfinite_d"].shape == ()
    assert float(m["nonfinite_g"]) == 0.0  # healthy step drops nothing
    assert float(m["nonfinite_d"]) == 0.0
    # a clip=0 step must NOT pay for the counter
    cfg0 = cfg.replace(optim=dataclasses.replace(cfg.optim, grad_clip=0.0))
    state0 = create_train_state(cfg0, jax.random.key(0), b, 1)
    _, m0 = build_train_step(cfg0, None, 1, None, jit=True)(state0, b)
    assert "nonfinite_g" not in m0


def test_count_nonfinite_counts_exactly():
    from p2p_tpu.train.state import count_nonfinite

    tree = {
        "a": jnp.array([1.0, jnp.inf, -jnp.inf]),
        "b": jnp.array([[jnp.nan, 0.0], [2.0, jnp.nan]]),
    }
    assert int(count_nonfinite(tree)) == 4
    assert int(count_nonfinite({})) == 0


# ------------------------------------------------ named scopes of the step
@pytest.fixture(scope="module", params=[None, jnp.bfloat16],
                ids=["f32", "bf16"])
def scoped_step(batch, request):
    """The toy step with every kind of loss live (the seeded VGG19 too),
    lowered once a train dtype for the scope tests: under bf16 the
    perceptual loss takes its stored-dtype convolutions, whose
    hand-written backward has to carry the scope's name like autodiff's."""
    import dataclasses

    from p2p_tpu.models.vgg import load_vgg19_params

    cfg = tiny_config()
    cfg = cfg.replace(loss=dataclasses.replace(
        cfg.loss, lambda_vgg=10.0, lambda_l1=1.0))
    state = create_train_state(cfg, jax.random.key(0), batch, 1)
    return build_train_step(
        cfg, load_vgg19_params(None), train_dtype=request.param
    ).lower(state, batch)


def test_compiled_step_names_every_scope(scoped_step):
    """Scopes survive into the COMPILED text, where a device trace's
    instruction names can be joined with them (benchmark/scope_time.py):
    every scope of the shared tuple owns instructions there."""
    from benchmark import scope_time
    from p2p_tpu.train.step import STEP_SCOPES

    owners = scope_time.instruction_scopes(
        scoped_step.compile().as_text(), STEP_SCOPES)
    # (the three a learned-quantizer preset alone enters are held in its
    # own compiled step, tests/test_vqgan.py; the inpainting preset's
    # perceptual term in tests/test_lama.py)
    assert set(STEP_SCOPES) - {"loss_lpips", "loss_adaptive",
                               "loss_codebook", "loss_hrf"} <= set(
        owners.values())
    assert scope_time.program_scopes() == STEP_SCOPES


def test_no_convolution_or_dot_of_the_step_is_unscoped(scoped_step):
    """As the program hands the step to the compiler, every convolution
    and matmul, forward and backward, lies under a net's or a loss's
    scope. (The compiler may still drop the name from an op it rewrites;
    what that leaves unscoped on the chip is ``step.unscoped_share``.)"""
    from benchmark import scope_time
    from jax._src.lib.mlir import ir
    from p2p_tpu.train.step import STEP_SCOPES

    heavy = []

    def visit(op):
        if op.name in ("stablehlo.convolution", "stablehlo.dot_general"):
            heavy.append(str(op.location).split('"')[1])
        return ir.WalkResult.ADVANCE

    scoped_step.compiler_ir().operation.walk(visit)
    owners = [scope_time.first_scope(name, STEP_SCOPES) for name in heavy]
    assert len(heavy) > 100
    assert [n for n, o in zip(heavy, owners) if o is None] == []
    assert {"G", "D_fake", "D_real", "loss_vgg", "compress",
            "C_branch"} <= set(owners)
    # backward ops are named through their transforms
    assert any(n.startswith("jit(step)/transpose(jvp(G))/") for n in heavy)


def test_scopes_change_location_info_only(batch, monkeypatch):
    """The program the compiler is given is the same text with the scopes
    as without them, location info apart."""
    import contextlib

    cfg = tiny_config()
    state = create_train_state(cfg, jax.random.key(0), batch, 1)
    scoped = build_train_step(cfg).lower(state, batch).as_text()

    @contextlib.contextmanager
    def no_scope(name):
        yield

    monkeypatch.setattr(jax, "named_scope", no_scope)
    assert build_train_step(cfg).lower(state, batch).as_text() == scoped
