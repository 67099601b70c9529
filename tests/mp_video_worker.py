"""Worker process for the 2-process data×time VIDEO run
(tests/test_multiprocess.py::test_two_process_video_data_time; VERDICT r4
#6). Not a test module — launched as a subprocess, one per JAX process.

Exercises the video trainer's multi-host branches end-to-end on a REAL
2-process gloo cluster with a data×time mesh (data across processes,
time across each process's 2 local devices — sequence parallelism):

- ``VideoClipDataset`` + per-process record sharding
- ``place_global`` clip assembly under ``P('data','time',...)``
- ``VideoTrainer.train_epoch`` + ``evaluate`` with the shared
  ``local_metric_rows`` dedup (the per-frame metric vector replicates
  over the time axis) and the allgather'd cross-process reduction.
"""

import json
import os
import sys


def main() -> int:
    pid = int(sys.argv[1])
    nproc = int(sys.argv[2])
    port = sys.argv[3]
    data_root = sys.argv[4]
    workdir = sys.argv[5]
    out_path = sys.argv[6]

    # the launching test exports JAX_PLATFORMS=cpu (see mp_worker.py)
    import jax

    jax.distributed.initialize(
        coordinator_address=f"127.0.0.1:{port}",
        num_processes=nproc,
        process_id=pid,
    )
    assert jax.process_count() == nproc, jax.process_count()

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
    from p2p_tpu.train.video_loop import VideoTrainer

    n_local = len(jax.local_devices())
    n_dev = len(jax.devices())
    n_frames = 4  # sharded 2×2 over the time axis
    cfg = Config(
        name="mpv",
        model=ModelConfig(ngf=4, n_blocks=1, ndf=4, num_D=1,
                          use_compression_net=False, norm="instance"),
        loss=LossConfig(lambda_feat=0.0, lambda_vgg=0.0, lambda_tv=0.0,
                        lambda_l1=10.0),
        optim=OptimConfig(),
        data=DataConfig(batch_size=nproc, test_batch_size=nproc,
                        image_size=16, threads=0, n_frames=n_frames),
        parallel=ParallelConfig(mesh=MeshSpec(data=nproc,
                                              time=n_dev // nproc)),
        train=TrainConfig(nepoch=1, epoch_save=10, log_every=1000,
                          mixed_precision=False, seed=0,
                          eval_every_epoch=False),
    )
    tr = VideoTrainer(cfg, data_root=data_root,
                      workdir=os.path.join(workdir, f"proc{pid}"))

    train_metrics = tr.train_epoch(seed=1)
    steps_run = int(tr.state.step)
    assert steps_run >= 1, steps_run
    assert np.isfinite(train_metrics["loss_g"])
    assert np.isfinite(train_metrics["loss_d"])

    eval_metrics = tr.evaluate()
    assert np.isfinite(eval_metrics["psnr_mean"])
    assert 0.0 < eval_metrics["ssim_max"] <= 1.0

    with open(out_path, "w") as f:
        json.dump(
            {
                "pid": pid,
                "process_count": jax.process_count(),
                "n_devices": n_dev,
                "n_local_devices": n_local,
                "steps_run": steps_run,
                "loss_g": float(train_metrics["loss_g"]),
                "psnr_mean": float(eval_metrics["psnr_mean"]),
                "ssim_mean": float(eval_metrics["ssim_mean"]),
                "n_frames_scored": int(eval_metrics["n_frames_scored"]),
            },
            f,
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
