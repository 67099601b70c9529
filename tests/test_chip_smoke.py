"""CPU rehearsal of chip_smoke.py and the compile-cache placement rule.

The smoke's phases are functions of ``chip_smoke.Sizes``; here they run at
toy size (ngf=4, 32 px, no VGG) on the CPU backend, with the Pallas
kernels in interpret mode — the TEST sets ``P2P_TPU_FORCE_PALLAS``, the
program has no option for it. What the chip run adds (the TPU gate, the
``tpu_custom_call`` assertion, device memory) cannot be rehearsed here.
"""

import json
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TOY = chip_smoke.Sizes(image_size=32, hd_hw=(32, 64), ngf=4, ndf=4,
                       n_blocks=1, lambda_vgg=0.0, train_steps=3,
                       n_test=2, serve_requests=3, hd_steps=2)


@pytest.fixture
def meter():
    m = chip_smoke.PhaseMeter()
    yield m
    m.close()


def _phase_line(capsys, name):
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith('{"phase"')]
    (line,) = [ln for ln in lines if ln["phase"] == name]
    return line


def test_smoke_refuses_to_start_off_tpu(tmp_path):
    """JAX_PLATFORMS=cpu python chip_smoke.py: non-zero, no result line,
    nothing written."""
    out = tmp_path / "out"
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"),
         "--out", str(out)],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "needs a TPU" in r.stderr
    assert not out.exists()


def test_rehearse_reference_train_infer_serve(tmp_path, meter, capsys):
    """native → train (3 steps, eval, save) → infer → HTTP serve, through
    the CLIs' main(argv), one process."""
    out = str(tmp_path)
    with meter.phase("native") as r:
        chip_smoke.phase_native(r)
    assert _phase_line(capsys, "native")["decoder"] == "native"
    with meter.phase("train") as r:
        run = chip_smoke.phase_train(r, out, 0, TOY)
    line = _phase_line(capsys, "train")
    # every program was compiled, or (warm .jax_cache) loaded from disk
    assert line["steps"] == 3
    assert line["n_compiles"] + line["persistent_cache_hits"] > 0
    assert line["psnr"] > 0
    with meter.phase("infer") as r:
        chip_smoke.phase_infer(r, run, TOY)
    assert _phase_line(capsys, "infer")["images"] == 2
    with meter.phase("serve") as r:
        chip_smoke.phase_serve(r, run, TOY)
    line = _phase_line(capsys, "serve")
    assert line["requests"] == 3 and line["shutdown"] == "clean"
    assert line["n_bucket_compiles"] == len(line["buckets"]) == 2


def test_broken_phase_raises(tmp_path):
    """A phase that cannot do its work raises (the script then exits
    non-zero at once): infer against a checkpoint dir that is not one."""
    run = {"workdir": str(tmp_path), "data_root": str(tmp_path),
           "name": "nope", "dataset": "synthetic"}
    with pytest.raises((FileNotFoundError, AssertionError, SystemExit)):
        chip_smoke.phase_infer({}, run, TOY)


def test_rehearse_pallas_phase_interpreted(tmp_path, meter, capsys,
                                           monkeypatch):
    """Two pix2pixhd steps on --mesh data=1 with the Pallas norm taken in
    interpret mode; the square seeded images resize to the 2:1 extent."""
    monkeypatch.setenv("P2P_TPU_FORCE_PALLAS", "1")
    with meter.phase("pallas") as r:
        chip_smoke.phase_pallas(r, str(tmp_path), 0, TOY,
                                kernel_marker=None)
    line = _phase_line(capsys, "pallas")
    assert line["steps"] == 2
    # interpret mode lowers the kernels to plain HLO: asking for the
    # marker on such a program must FAIL the phase
    with pytest.raises(AssertionError, match="tpu_custom_call"):
        chip_smoke.phase_pallas({}, str(tmp_path), 0, TOY)


@pytest.mark.slow
def test_rehearse_multichip_phase_on_virtual_devices(tmp_path, meter,
                                                     capsys, monkeypatch):
    """data=2 x spatial=2 vs one device on the CPU mesh: the phase's own
    sharding and parity assertions (no XLA dump, no device memory)."""
    import dataclasses

    monkeypatch.setenv("P2P_TPU_FORCE_PALLAS", "1")
    # 128x256, not the 32x64 toy: at 32 rows the trunk holds ONE row per
    # spatial shard and GSPMD's reflect-pad halo is wider than the shard
    # — the sharded step then differs from its twin by 14% in g_feat (the
    # trainer's own H/4-divisible rule does not catch an extent that
    # small). At 128 rows the two agree to 5 digits.
    sizes = dataclasses.replace(TOY, hd_hw=(128, 256), hd_steps=2)
    with meter.phase("multichip") as r:
        chip_smoke.phase_multichip(r, str(tmp_path), 0, sizes,
                                   xla_dump=None)
    line = _phase_line(capsys, "multichip")
    assert line["steps"] == 2
    assert line["parity_worst_fraction_of_tolerance"] <= 1.0


# ------------------------------------------------------ the cache rule


@pytest.fixture
def cache_module(monkeypatch):
    """core.cache with its idempotence latch cleared and every
    jax.config.update recorded; the jax cache config is restored after."""
    from p2p_tpu.core import cache

    prev_dir = jax.config.jax_compilation_cache_dir
    monkeypatch.setattr(cache, "_enabled_dir", None)
    calls = []
    real_update = jax.config.update

    def spy(name, value):
        calls.append((name, value))
        return real_update(name, value)

    monkeypatch.setattr(jax.config, "update", spy)
    yield cache, calls
    monkeypatch.undo()
    jax.config.update("jax_compilation_cache_dir", prev_dir)
    cache._jax_cc.reset_cache()


def test_cache_env_set_uses_that_dir_and_sets_none_in_code(
        tmp_path, monkeypatch, cache_module):
    cache, calls = cache_module
    env_dir = str(tmp_path / "from_env")
    monkeypatch.setenv(cache.ENV_VAR, env_dir)
    assert cache.enable_compilation_cache() == env_dir
    assert os.path.isdir(env_dir)
    assert not [c for c in calls if c[0] == "jax_compilation_cache_dir"]
    # an explicit directory that AGREES is fine; one that differs is an
    # error, never an override
    assert cache.enable_compilation_cache(env_dir) == env_dir
    with pytest.raises(ValueError, match="disagrees"):
        cache.enable_compilation_cache(str(tmp_path / "elsewhere"))


def test_cache_env_unset_uses_the_fixed_checkout_dir(
        monkeypatch, cache_module):
    cache, calls = cache_module
    monkeypatch.delenv(cache.ENV_VAR, raising=False)
    assert cache.DEFAULT_CACHE_DIR == os.path.join(REPO, ".jax_cache")
    assert cache.resolve_cache_dir() == cache.DEFAULT_CACHE_DIR
    assert cache.enable_compilation_cache() == cache.DEFAULT_CACHE_DIR
    assert ("jax_compilation_cache_dir", cache.DEFAULT_CACHE_DIR) in calls


def test_cache_flag_wins_only_when_env_is_unset(tmp_path, monkeypatch,
                                                cache_module):
    cache, _ = cache_module
    monkeypatch.delenv(cache.ENV_VAR, raising=False)
    flag = str(tmp_path / "flag")
    assert cache.enable_compilation_cache(flag) == flag
    assert cache.compilation_cache_dir() == flag


def test_no_cache_path_is_built_from_tempfile_pid_or_clock():
    """The path is part of the cache key: a directory that moves never
    hits. No source line may derive a cache dir from a moving value."""
    import re

    moving = re.compile(r"tempfile|mkdtemp|getpid|time\.time|perf_counter")
    offenders = []
    files = [os.path.join(REPO, "chip_smoke.py")]
    for root, _, names in os.walk(os.path.join(REPO, "p2p_tpu")):
        files += [os.path.join(root, n) for n in names if n.endswith(".py")]
    for path in files:
        with open(path) as f:
            for i, line in enumerate(f, 1):
                if "cache" in line.lower() and "dir" in line.lower() \
                        and moving.search(line):
                    offenders.append(f"{path}:{i}: {line.strip()}")
    assert not offenders, offenders
