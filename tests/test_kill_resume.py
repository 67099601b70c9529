"""Kill-and-resume integration test — SURVEY §5.3/§5.4 end-to-end.

Launches the REAL training CLI as a subprocess on a tiny synthetic set,
SIGKILLs it mid-run after at least one checkpoint landed (including,
possibly, mid-async-save — Orbax's commit markers must make incomplete
steps invisible to restore), relaunches with identical flags, and asserts
the continuation: the epoch counter resumes past the kill point, the step
counter never rewinds, and the per-epoch lr records follow ONE decay curve
across both processes (composing with the resume × decay fix in
Trainer.maybe_resume).
"""

import json
import os
import signal
import subprocess
import sys
import time

import pytest

from p2p_tpu.data.synthetic import make_synthetic_dataset

# The CLI child runs on the CPU backend through the inherited
# JAX_PLATFORMS=cpu (tests/conftest.py exports it before any spawn).
_CLI = [sys.executable, "-m", "p2p_tpu.cli.train"]


def _cli_args(root, wd, nepoch):
    return [
        "--preset", "facades", "--data_root", root, "--workdir", wd,
        "--name", "kr", "--dataset", "krsynth",
        "--image_size", "16", "--batch_size", "2", "--test_batch_size", "2",
        "--ngf", "4", "--ndf", "4", "--threads", "0",
        "--nepoch", str(nepoch), "--niter", "2", "--niter_decay", "4",
        "--epochsave", "1", "--seed", "0", "--lambda_vgg", "0",
    ]


def _epoch_records(path):
    out = []
    with open(path) as f:
        for line in f:
            rec = json.loads(line)
            if rec.get("kind") == "epoch":
                out.append(rec)
    return out


@pytest.mark.slow
def test_kill_mid_run_then_resume_continues(tmp_path):
    root = make_synthetic_dataset(str(tmp_path / "data"), 4, 2, size=16)
    wd = str(tmp_path / "w")
    os.makedirs(wd)
    env = dict(os.environ)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    metrics = os.path.join(wd, "metrics_kr.jsonl")

    # ---- run 1: start a 6-epoch run, SIGKILL once ≥2 epochs are logged
    log1 = os.path.join(wd, "run1.log")
    with open(log1, "w") as lf:
        p = subprocess.Popen(
            _CLI + _cli_args(root, wd, 6),
            env=env, stdout=lf, stderr=subprocess.STDOUT, text=True,
        )
    ckpt_dir = os.path.join(wd, "checkpoint", "krsynth", "kr")

    def finalized_steps():
        if not os.path.isdir(ckpt_dir):
            return []
        return [d for d in os.listdir(ckpt_dir)
                if d.isdigit()]  # orbax tmp dirs carry a suffix

    killed_after = None
    deadline = time.time() + 540
    try:
        while time.time() < deadline:
            if p.poll() is not None:
                with open(log1) as f:
                    tail = f.read()[-3000:]
                pytest.fail(
                    f"run 1 exited early ({p.returncode}) before the kill:"
                    f"\n{tail}")
            # kill only once a FINALIZED checkpoint exists (async Orbax
            # saves can lag epochs on a loaded host) and ≥2 epochs logged
            if os.path.exists(metrics) and finalized_steps():
                eps = _epoch_records(metrics)
                if len(eps) >= 2:
                    killed_after = eps[-1]["epoch"]
                    p.send_signal(signal.SIGKILL)  # no cleanup, no flush
                    break
            time.sleep(0.5)
    finally:
        if p.poll() is None and killed_after is None:
            p.kill()
    assert killed_after is not None, \
        "run 1 never produced a finalized checkpoint + 2 epoch records"
    p.wait(timeout=60)

    run1 = _epoch_records(metrics)
    assert run1 and run1[-1]["epoch"] == killed_after
    assert finalized_steps(), "no finalized checkpoint survived the kill"

    # ---- run 2: identical flags; must RESUME (not restart) and finish
    out2 = subprocess.run(
        _CLI + _cli_args(root, wd, 6),
        env=env, capture_output=True, text=True, timeout=540,
    )
    assert out2.returncode == 0, out2.stdout[-3000:] + out2.stderr[-2000:]
    assert "resumed at epoch" in out2.stdout

    recs = _epoch_records(metrics)
    run2 = recs[len(run1):]
    assert run2, "run 2 logged no epochs"
    # continuation, not restart: run 2 begins after a RESTORED epoch (>1).
    # The kill may have landed mid-epoch, mid-save, or with the async
    # save a step behind the log, so run 2's first epoch lies anywhere in
    # (1, killed_after + 1] — never back at 1.
    first2 = run2[0]["epoch"]
    assert 1 < first2 <= killed_after + 1
    assert run2[-1]["epoch"] == 6

    # ONE decay curve across both processes: with spe=2, niter=2,
    # niter_decay=4, the lr recorded after 1-based epoch E is
    # 2e-4 · (1 − max(0, E − 2)/5) — exact for EVERY record of both runs
    # (this also pins that the resumed step/schedule agree with the epoch
    # labels; a rewound or double-offset schedule breaks the curve)
    spe = 2
    for rec in recs:
        e_abs = int(rec["epoch"])
        count = spe * e_abs - 1   # optimizer count at the epoch's last update
        mult = 1.0 - max(0, (count // spe) + 1 - 2) / 5.0
        assert rec["lr"] == pytest.approx(2e-4 * max(0.0, mult), rel=1e-4), (
            f"epoch {e_abs}: lr {rec['lr']} != expected {2e-4 * mult}"
        )


def _train_steps(path):
    """Step numbers of every kind=train record, in file order."""
    out = []
    with open(path) as f:
        for line in f:
            rec = json.loads(line)
            if rec.get("kind") == "train":
                out.append(int(rec["step"]))
    return out


@pytest.mark.slow
def test_sigterm_mid_epoch_exact_resume(tmp_path):
    """Graceful-preemption path end-to-end (p2p_tpu.resilience): SIGTERM a
    REAL training CLI mid-epoch; it must save an exact-step checkpoint and
    exit with PREEMPTED_EXIT_CODE (75); the relaunch must resume INSIDE
    the interrupted epoch and finish, with per-step records (log_every=1,
    fallback loader) forming one gapless, repeat-free step sequence —
    exact sample accounting: nothing replayed, nothing skipped."""
    from p2p_tpu.resilience import PREEMPTED_EXIT_CODE

    # one long epoch (spe=300, bs=1) so the kill lands mid-epoch with
    # margin: post-compile CPU steps are ~10 ms, the poll sees the step
    # counter grow and fires around step ~30
    n_train = 300
    root = make_synthetic_dataset(str(tmp_path / "data"), n_train, 2, size=16)
    wd = str(tmp_path / "w")
    os.makedirs(wd)
    env = dict(os.environ)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    env["P2P_TPU_NO_GRAIN"] = "1"   # the fallback-loader accounting pin
    metrics = os.path.join(wd, "metrics_kr.jsonl")
    args = [
        "--preset", "facades", "--data_root", root, "--workdir", wd,
        "--name", "kr", "--dataset", "krsynth",
        "--image_size", "16", "--batch_size", "1", "--test_batch_size", "2",
        "--ngf", "4", "--ndf", "4", "--threads", "0",
        "--nepoch", "1", "--niter", "1", "--niter_decay", "0",
        "--epochsave", "1", "--seed", "0", "--lambda_vgg", "0",
        "--log_every", "1",
    ]

    # ---- run 1: SIGTERM once a handful of steps are logged
    log1 = os.path.join(wd, "run1.log")
    with open(log1, "w") as lf:
        p = subprocess.Popen(
            _CLI + args,
            env=env, stdout=lf, stderr=subprocess.STDOUT, text=True,
        )
    deadline = time.time() + 540
    sent = False
    while time.time() < deadline:
        if p.poll() is not None:
            break
        if not sent and os.path.exists(metrics) and \
                len(_train_steps(metrics)) >= 5:
            p.send_signal(signal.SIGTERM)   # the graceful-preemption path
            sent = True
        time.sleep(0.1)
    assert sent, "run 1 finished before any SIGTERM could be sent"
    rc = p.wait(timeout=120)
    with open(log1) as f:
        out1 = f.read()
    assert rc == PREEMPTED_EXIT_CODE, f"exit {rc}, log tail:\n{out1[-3000:]}"
    assert "preempted: checkpoint saved at step" in out1

    # the preempt record names the exact saved step — mid-epoch by design
    recs = [json.loads(line) for line in open(metrics)]
    pre = [r for r in recs if r.get("kind") == "preempt"]
    assert len(pre) == 1
    saved_step = int(pre[0]["step"])
    assert 0 < saved_step < n_train, \
        f"kill was not mid-epoch (step {saved_step} of {n_train})"
    ckpt_dir = os.path.join(wd, "checkpoint", "krsynth", "kr")
    assert os.path.isdir(os.path.join(ckpt_dir, str(saved_step)))
    steps1 = _train_steps(metrics)
    assert steps1 == list(range(1, saved_step + 1)), \
        "run 1's logged steps don't match its saved step"

    # ---- run 2: identical flags; resumes INSIDE the epoch and finishes
    out2 = subprocess.run(
        _CLI + args,
        env=env, capture_output=True, text=True, timeout=540,
    )
    assert out2.returncode == 0, out2.stdout[-3000:] + out2.stderr[-2000:]
    assert "resumed at epoch" in out2.stdout

    recs = [json.loads(line) for line in open(metrics)]
    resume = [r for r in recs if r.get("kind") == "resume"]
    assert resume and int(resume[0]["batches_done"]) == saved_step % n_train

    # exact sample accounting on the fallback loader: the union of both
    # runs' per-step records is 1..n_train, each exactly once — run 2
    # replayed none of run 1's samples and skipped none of its own
    steps = _train_steps(metrics)
    assert steps == list(range(1, n_train + 1)), (
        f"step sequence has gaps/repeats around the kill point: "
        f"{steps[max(0, saved_step - 3):saved_step + 3]}")
    epochs = [r for r in recs if r.get("kind") == "epoch"]
    assert len(epochs) == 1 and int(epochs[0]["epoch"]) == 1


def _all_train_steps(wd, name):
    """Union of per-step records across every process's metrics file
    (proc 0 writes metrics_<name>.jsonl, proc N a metrics_<name>.pN.jsonl
    sibling — train/loop.py metrics_path)."""
    return _all_train_records(wd, name, "step")


def _gloo_phase_a(tmp_path, wd, args, repo, extra_mesh, n_expect_steps=3):
    """Launch the 2-process gloo phase A (elastic@3 preemption), wait for
    both exits, assert rc=75 everywhere; returns the env used."""
    import socket

    from p2p_tpu.resilience import PREEMPTED_EXIT_CODE

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    env["P2P_TPU_NO_GRAIN"] = "1"          # fallback-loader accounting pin
    env["P2P_CHAOS"] = "elastic@3"         # deterministic mid-epoch preempt
    worker = os.path.join(os.path.dirname(__file__), "mp_elastic_worker.py")
    procs, logs = [], []
    for pid in range(2):
        log_path = str(tmp_path / f"elastic_worker_{pid}.log")
        logs.append(log_path)
        lf = open(log_path, "w")
        procs.append(subprocess.Popen(
            [sys.executable, worker, str(pid), "2", str(port),
             *args, extra_mesh],
            env=env, stdout=lf, stderr=subprocess.STDOUT, cwd=repo,
        ))
    rcs = [p.wait(timeout=540) for p in procs]
    for pid, rc in enumerate(rcs):
        if rc != PREEMPTED_EXIT_CODE:
            texts = []
            for lg in logs:
                with open(lg) as f:
                    texts.append(f.read())
            _skip_if_gloo_transport_broken(texts, wd)
            pytest.fail(f"phase-A worker {pid} exited {rc} "
                        f"(want 75):\n{texts[pid][-4000:]}")
    with open(logs[0]) as f:
        assert "preempted: checkpoint saved at step 3" in f.read()
    return env


def _skip_if_gloo_transport_broken(log_texts, wd):
    """Some hosts (observed: 1-vCPU CI boxes) cannot form the 2-process
    gloo CPU cluster at all — a worker dies inside the gloo TCP transport
    (EnforceNotMet size-mismatch / all-reduce read error) during plain
    trainer CONSTRUCTION, before any training or chaos fires. That is an
    environment limitation, not a regression in the elastic path — skip
    with the evidence named instead of failing the rehearsal.

    Anchored to "no training ever happened" (zero kind=train records in
    the shared workdir): a gloo error AFTER steps ran could be a real
    collective-divergence regression mid-rehearsal and must FAIL, not
    skip."""
    markers = ("gloo::EnforceNotMet", "Gloo all-reduce failed")
    hit = next((m for m in markers for t in log_texts if m in t), None)
    if hit is None:
        return
    trained = any(
        _train_steps(os.path.join(wd, fn))
        for fn in sorted(os.listdir(wd))
        if fn.startswith("metrics_") and fn.endswith(".jsonl"))
    if not trained:
        pytest.skip(
            f"gloo CPU collectives transport is broken on this host "
            f"({hit} during cluster formation, zero train steps logged) "
            "— the 2-process rehearsal cannot form; run on a multi-core "
            "host / CI for the real pin")


def _all_train_records(wd, name, key):
    """Values of ``key`` across every process's kind=train records."""
    out, seen = [], []
    for fn in sorted(os.listdir(wd)):
        if fn == f"metrics_{name}.jsonl" or (
                fn.startswith(f"metrics_{name}.p")
                and fn.endswith(".jsonl")):
            seen.append(fn)
            with open(os.path.join(wd, fn)) as f:
                for line in f:
                    rec = json.loads(line)
                    if rec.get("kind") == "train" and key in rec:
                        out.append(int(rec[key]))
    assert seen, f"no metrics files for {name} in {wd}"
    return out


@pytest.mark.slow
def test_elastic_kill_resume_batch_change_gapless_samples(tmp_path):
    """PR-11 chaos rehearsal, cross-BATCH: a 2-process bs=4 run killed at
    step 3 (12 samples consumed) relaunches single-process at bs=2. The
    relaunch must classify ``migrate`` (batch_rebase), re-base the step
    counter to the sample basis, finish rc=0, and the per-process
    SAMPLE-record union must tile the epoch exactly — old-batch prefix
    {4,8,12} ∪ new-batch suffix {14,...,24}, no gap, no overlap."""
    n_train = 24          # bs 4 → 6 steps/epoch; kill at step 3
    root = make_synthetic_dataset(str(tmp_path / "data"), n_train, 2, size=16)
    wd = str(tmp_path / "w")
    os.makedirs(wd)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    args = [
        "--preset", "facades", "--data_root", root, "--workdir", wd,
        "--name", "eb", "--dataset", "ebsynth",
        "--image_size", "16", "--batch_size", "4", "--test_batch_size", "2",
        "--ngf", "4", "--ndf", "4", "--threads", "0",
        "--nepoch", "1", "--niter", "1", "--niter_decay", "0",
        "--epochsave", "1", "--seed", "0", "--lambda_vgg", "0",
        "--log_every", "1",
    ]
    env = _gloo_phase_a(tmp_path, wd, args, repo, "--mesh=-1,1,1")
    samples_a = sorted(set(_all_train_records(wd, "eb", "samples")))
    assert samples_a == [4, 8, 12]

    # phase B: single process, data=2 mesh, HALF the global batch
    env_b = dict(env)
    env_b.pop("P2P_CHAOS", None)
    out2 = subprocess.run(
        [*_CLI, *args,
         "--mesh", "2,1,1", "--batch_size", "2"],
        env=env_b, capture_output=True, text=True, timeout=540, cwd=repo,
    )
    assert out2.returncode == 0, out2.stdout[-3000:] + out2.stderr[-2000:]
    assert "elastic resume" in out2.stdout
    assert "batch re-base" in out2.stdout

    recs = [json.loads(line)
            for line in open(os.path.join(wd, "metrics_eb.jsonl"))]
    el = [r for r in recs if r.get("kind") == "elastic_resume"]
    assert el and el[0]["decision"] == "migrate"
    assert "batch_rebase" in el[0]["chain"]
    rb = [r for r in recs if r.get("kind") == "batch_rebase"]
    # 12 samples / new bs 2 → rebased step 6 on the 12-step epoch grid
    assert rb and rb[0]["rebased_step"] == 6
    assert rb[0]["samples_seen"] == 12

    # THE pin: gapless per-SAMPLE accounting across the batch change —
    # phase A consumed flat samples (0,12] in strides of 4, phase B must
    # consume exactly (12,24] in strides of 2
    samples = sorted(set(_all_train_records(wd, "eb", "samples")))
    assert samples == [4, 8, 12] + list(range(14, 25, 2)), samples
    epochs = [r for r in recs if r.get("kind") == "epoch"]
    assert len(epochs) == 1 and int(epochs[0]["epoch"]) == 1


@pytest.mark.slow
def test_elastic_kill_resume_pipe_width_change(tmp_path):
    """PR-11 chaos rehearsal, cross-PIPE-WIDTH: a 2-process pipe=2 run
    killed mid-epoch relaunches single-process at pipe=1 (plus a
    data-axis change). The relaunch must classify ``migrate``
    (pp_restructure), finish rc=0, and the per-process step-record union
    stays gapless 1..steps_per_epoch."""
    n_train = 24
    root = make_synthetic_dataset(str(tmp_path / "data"), n_train, 2, size=16)
    wd = str(tmp_path / "w")
    os.makedirs(wd)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    args = [
        "--preset", "facades", "--data_root", root, "--workdir", wd,
        "--name", "ep", "--dataset", "epsynth",
        "--image_size", "16", "--batch_size", "4", "--test_batch_size", "2",
        "--ngf", "4", "--ndf", "4", "--threads", "0",
        "--nepoch", "1", "--niter", "1", "--niter_decay", "0",
        "--epochsave", "1", "--seed", "0", "--lambda_vgg", "0",
        "--log_every", "1",
    ]
    # data=-1 resolves to 2 across the 2x2-device cluster, pipe=2
    env = _gloo_phase_a(tmp_path, wd, args, repo, "--mesh=-1,1,1,1,2")
    ckpt_dir = os.path.join(wd, "checkpoint", "epsynth", "ep")
    with open(os.path.join(ckpt_dir + ".aux", "3.json")) as f:
        topo = json.load(f)["topology"]
    assert topo["mesh"]["pipe"] == 2

    env_b = dict(env)
    env_b.pop("P2P_CHAOS", None)
    out2 = subprocess.run(
        [*_CLI, *args, "--mesh", "2,1,1"],
        env=env_b, capture_output=True, text=True, timeout=540, cwd=repo,
    )
    assert out2.returncode == 0, out2.stdout[-3000:] + out2.stderr[-2000:]
    assert "elastic resume" in out2.stdout

    recs = [json.loads(line)
            for line in open(os.path.join(wd, "metrics_ep.jsonl"))]
    el = [r for r in recs if r.get("kind") == "elastic_resume"]
    assert el and el[0]["decision"] == "migrate"
    assert "pp_restructure" in el[0]["chain"]
    rs = [r for r in recs if r.get("kind") == "resharded_restore"]
    assert rs and rs[0]["resharded_restore_total"] >= 1
    steps = sorted(set(_all_train_steps(wd, "ep")))
    spe = n_train // 4
    assert steps == list(range(1, spe + 1)), (
        f"step gaps/repeats across the pipe-width relaunch: {steps}")
    epochs = [r for r in recs if r.get("kind") == "epoch"]
    assert len(epochs) == 1 and int(epochs[0]["epoch"]) == 1


@pytest.mark.slow
def test_elastic_kill_resume_across_process_count_and_mesh(tmp_path):
    """THE elastic acceptance pin, end-to-end over real processes: a
    2-process (4-device, data=4) CLI run is preempted mid-epoch by the
    ``elastic`` chaos seam (deterministic synthetic SIGTERM at host step
    3, cross-host agreed) and exits 75 on both processes; the relaunch is
    SINGLE-process on a data=2 mesh — a different process count, device
    count, and data-axis width — against the same workdir. It must
    reconcile the sidecar's recorded topology, reshard the restore, and
    finish with GAPLESS per-sample accounting: the union of both phases'
    per-step records is exactly 1..steps_per_epoch, nothing replayed,
    nothing skipped."""
    n_train = 24          # bs 4 → 6 steps/epoch; kill at step 3
    root = make_synthetic_dataset(str(tmp_path / "data"), n_train, 2, size=16)
    wd = str(tmp_path / "w")
    os.makedirs(wd)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    args = [
        "--preset", "facades", "--data_root", root, "--workdir", wd,
        "--name", "el", "--dataset", "elsynth",
        "--image_size", "16", "--batch_size", "4", "--test_batch_size", "2",
        "--ngf", "4", "--ndf", "4", "--threads", "0",
        "--nepoch", "1", "--niter", "1", "--niter_decay", "0",
        "--epochsave", "1", "--seed", "0", "--lambda_vgg", "0",
        "--log_every", "1",
    ]

    # ---- phase A: 2 processes x 2 local devices = data=4 mesh, killed
    # mid-epoch at host step 3 by the elastic chaos seam (shared helper;
    # skips on hosts whose gloo transport cannot form the cluster)
    env = _gloo_phase_a(tmp_path, wd, args, repo, "--mesh=-1,1,1")

    ckpt_dir = os.path.join(wd, "checkpoint", "elsynth", "el")
    assert os.path.isdir(os.path.join(ckpt_dir, "3"))
    with open(os.path.join(ckpt_dir + ".aux", "3.json")) as f:
        topo = json.load(f)["topology"]
    assert topo["process_count"] == 2 and topo["mesh"]["data"] == 4
    # BOTH processes' accounting evidence must exist (proc 1 writes the
    # .p1 sibling) and agree on the same gapless prefix
    assert os.path.exists(os.path.join(wd, "metrics_el.p1.jsonl"))
    steps_a = _all_train_steps(wd, "el")
    assert sorted(set(steps_a)) == [1, 2, 3]

    # ---- phase B: SINGLE process, data=2 mesh (different process count,
    # device count, and data width) — must reshard-resume and finish
    env_b = dict(env)
    env_b.pop("P2P_CHAOS", None)
    out2 = subprocess.run(
        [*_CLI, *args, "--mesh", "2,1,1"],
        env=env_b, capture_output=True, text=True, timeout=540, cwd=repo,
    )
    assert out2.returncode == 0, out2.stdout[-3000:] + out2.stderr[-2000:]
    assert "resumed at epoch" in out2.stdout
    assert "elastic resume" in out2.stdout

    recs = [json.loads(line)
            for line in open(os.path.join(wd, "metrics_el.jsonl"))]
    el = [r for r in recs if r.get("kind") == "elastic_resume"]
    assert el and el[0]["decision"] == "reshard"
    assert el[0]["saved"]["process_count"] == 2
    assert el[0]["current"]["process_count"] == 1
    rs = [r for r in recs if r.get("kind") == "resharded_restore"]
    assert rs and rs[0]["resharded_restore_total"] >= 1
    resume = [r for r in recs if r.get("kind") == "resume"]
    assert resume and int(resume[0]["batches_done"]) == 3

    # gapless per-sample accounting across the topology change: the union
    # of phase A's (per-process) and phase B's step records is exactly
    # 1..6, each once — the relaunch's hosts landed on the correct shard
    # offsets, zero duplicated, zero dropped
    steps = sorted(set(_all_train_steps(wd, "el")))
    spe = n_train // 4
    assert steps == list(range(1, spe + 1)), (
        f"step sequence has gaps/repeats across the elastic relaunch: "
        f"{steps}")
    epochs = [r for r in recs if r.get("kind") == "epoch"]
    assert len(epochs) == 1 and int(epochs[0]["epoch"]) == 1


@pytest.mark.slow
def test_elastic_kill_resume_fsdp_to_replicated(tmp_path):
    """ISSUE 15 chaos rehearsal, cross-LAYOUT: a 2-process data=2 x
    fsdp=2 run (ZeRO-sharded optimizer moments, the rule-driven
    partitioner live end-to-end under gloo) is preempted at step 3, then
    relaunched single-process on a plain data=2 mesh. The fsdp →
    replicated delta must classify as a plain ``reshard`` (layout-only —
    the Orbax load gathers the moment shards onto the replicated
    targets), finish rc=0, and the per-process step union stays gapless
    1..steps_per_epoch."""
    n_train = 24          # bs 4 → 6 steps/epoch; kill at step 3
    root = make_synthetic_dataset(str(tmp_path / "data"), n_train, 2, size=16)
    wd = str(tmp_path / "w")
    os.makedirs(wd)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    args = [
        "--preset", "facades", "--data_root", root, "--workdir", wd,
        "--name", "ef", "--dataset", "efsynth",
        "--image_size", "16", "--batch_size", "4", "--test_batch_size", "2",
        "--ngf", "4", "--ndf", "4", "--threads", "0",
        "--nepoch", "1", "--niter", "1", "--niter_decay", "0",
        "--epochsave", "1", "--seed", "0", "--lambda_vgg", "0",
        "--log_every", "1",
    ]
    # 2 procs × 2 devices → data=2 × fsdp=2 (the named --mesh grammar)
    env = _gloo_phase_a(tmp_path, wd, args, repo, "--mesh=data=-1,fsdp=2")
    ckpt_dir = os.path.join(wd, "checkpoint", "efsynth", "ef")
    with open(os.path.join(ckpt_dir + ".aux", "3.json")) as f:
        topo = json.load(f)["topology"]
    assert topo["mesh"]["fsdp"] == 2

    env_b = dict(env)
    env_b.pop("P2P_CHAOS", None)
    out2 = subprocess.run(
        [*_CLI, *args, "--mesh", "2,1,1"],
        env=env_b, capture_output=True, text=True, timeout=540, cwd=repo,
    )
    assert out2.returncode == 0, out2.stdout[-3000:] + out2.stderr[-2000:]
    assert "elastic resume" in out2.stdout

    recs = [json.loads(line)
            for line in open(os.path.join(wd, "metrics_ef.jsonl"))]
    el = [r for r in recs if r.get("kind") == "elastic_resume"]
    assert el and el[0]["decision"] == "reshard", el
    assert "mesh.fsdp" in el[0]["reason"]
    rs = [r for r in recs if r.get("kind") == "resharded_restore"]
    assert rs and rs[0]["resharded_restore_total"] >= 1
    steps = sorted(set(_all_train_steps(wd, "ef")))
    spe = n_train // 4
    assert steps == list(range(1, spe + 1)), (
        f"step gaps/repeats across the fsdp relaunch: {steps}")
    epochs = [r for r in recs if r.get("kind") == "epoch"]
    assert len(epochs) == 1 and int(epochs[0]["epoch"]) == 1
