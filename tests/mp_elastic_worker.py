"""Phase-A worker for the elastic kill-resume test (test_kill_resume.py).

Not a test module (no ``test_`` prefix): launched as a subprocess, one per
JAX process, by the parent test. Unlike mp_worker.py (which drives trainer
methods directly), this worker runs the REAL training CLI end-to-end under
a gloo cluster, so the whole preempt → exit-75 path — chaos ``elastic``
seam, cross-host agreed stop, coordinated multi-process Orbax save,
topology-recording sidecar — executes exactly as a production slice would
run it. The parent then relaunches the CLI single-process on a different
data-axis mesh against the SAME (shared) workdir and asserts gapless
accounting + a resharded restore.

argv: pid nproc port <cli args...>; exits with the CLI's return code
(75 = preempted, the phase-A success criterion).
"""

import sys


def main() -> int:
    pid = int(sys.argv[1])
    nproc = int(sys.argv[2])
    port = sys.argv[3]
    cli_args = sys.argv[4:]

    # the launching test exports JAX_PLATFORMS=cpu (see mp_worker.py)
    import jax

    jax.distributed.initialize(
        coordinator_address=f"127.0.0.1:{port}",
        num_processes=nproc,
        process_id=pid,
    )
    assert jax.process_count() == nproc, jax.process_count()

    from p2p_tpu.cli.train import main as train_main

    return train_main(cli_args)


if __name__ == "__main__":
    sys.exit(main())
