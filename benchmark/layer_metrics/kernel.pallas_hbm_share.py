"""kernel.pallas_hbm_share (%; layer: kernels; moves train_img_per_s).

The Pallas kernels' share of their roofline, which for these streaming
norm kernels is BANDWIDTH: least time / kernel time, where least time =
the bytes of every custom call's operands and results, read once each
from the LOWERED train step's text (benchmark/hlo_bytes.py), over the
chip's peak HBM bytes per second (benchmark/peaks.json).
"""

META = {"name": "kernel.pallas_hbm_share", "unit": "%", "layer": "kernels",
        "moves": "train_img_per_s"}


def read(run):
    import glob
    import os

    from benchmark import harness, hlo_bytes

    tr, n, ir = run.get("trace"), run.get("steps"), run.get("ir_dir")
    if not tr or not n or not ir or not tr["n_kernel_events"]:
        return None
    files = glob.glob(os.path.join(ir, "*_jit_step*_compile.mlir"))
    if len(files) != 1:
        # kernels ran, so there is something to read: a step that was
        # renamed must not make the metric vanish unseen
        raise RuntimeError(
            f"{len(files)} lowered train steps under {ir} (looked for "
            f"*_jit_step*_compile.mlir; there: {sorted(os.listdir(ir))[:8]})")
    with open(files[0]) as f:
        sites, nbytes = hlo_bytes.custom_call_bytes(f.read())
    if not sites:
        return None
    peak = harness.load_peaks()[run["device_kind"]]["hbm_bytes_per_s"]
    least_s = nbytes / peak
    return 100.0 * least_s / (tr["kernel_s"] / n)
