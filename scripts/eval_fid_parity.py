"""Compute VFID for torch-reference and JAX predictions with the IDENTICAL
feature extractor — the controlled FID-parity comparison (PARITY_fid.json).

Both runners dump test-set predictions as PNGs named after the ground-truth
files; this script embeds (ground truth, torch preds, jax preds) with the
SAME fixed-seed VGG19 tap features (p2p_tpu.losses.fid.make_vgg_feature_fn,
D=1472) and reports VFID(gt, preds) per framework plus the parity delta.
The extractor being shared is what makes the numbers comparable — the
north-star clause "FID within 1.0 of the CUDA baseline" is evaluated as
|VFID_jax − VFID_torch| with this extractor.

Usage:
    python scripts/eval_fid_parity.py --gt dataset/real256/test/a \
        --torch_preds result/torch_ref/preds_e2 \
        --jax_preds result/jax_ref/preds_e2 [--size 256] [--json out.json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def load_dir(path, names, size):
    from PIL import Image

    imgs = []
    for n in names:
        img = Image.open(os.path.join(path, n)).convert("RGB")
        if img.size != (size, size):
            img = img.resize((size, size), Image.BICUBIC)
        imgs.append(np.asarray(img, np.float32) / 127.5 - 1.0)
    return np.stack(imgs)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--gt", required=True)
    ap.add_argument("--torch_preds", required=True)
    ap.add_argument("--jax_preds", required=True)
    ap.add_argument("--size", type=int, default=256)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seeds", default="190",
                    help="comma-separated extractor seeds; >1 adds the "
                         "multi-seed robustness rows (mean±range over "
                         "independent random-VGG draws — shows the parity "
                         "RANKING is not an artifact of one draw)")
    ap.add_argument("--json", default=None)
    args = ap.parse_args(argv)

    import jax.numpy as jnp

    from p2p_tpu.losses.fid import RunningStats, frechet_distance, make_vgg_feature_fn
    from p2p_tpu.models.vgg import load_vgg19_params, vgg19_params_source

    names = sorted(
        set(os.listdir(args.torch_preds)) & set(os.listdir(args.jax_preds))
    )
    if not names:
        raise RuntimeError("no common prediction filenames")
    print(f"{len(names)} common test predictions")

    seeds = [int(s) for s in args.seeds.split(",")]
    if len(seeds) > 1 and vgg19_params_source() == "pretrained":
        raise SystemExit(
            "--seeds with >1 seed is meaningless with the pretrained VGG19 "
            "npz present: load_vgg19_params ignores the seed and every "
            "'draw' would be the same extractor. Drop --seeds (or unset "
            "P2P_TPU_VGG19_NPZ to test random-extractor robustness).")

    dirs = {"gt": args.gt, "torch": args.torch_preds,
            "jax": args.jax_preds}

    def iter_batches(tag):
        for i in range(0, len(names), args.batch):
            yield load_dir(dirs[tag], names[i:i + args.batch], args.size)

    # Multi-seed: decode each directory ONCE and reuse across seeds (only
    # the extractor changes). Single-seed: STREAM the decode — holding all
    # three directories in host RAM simultaneously can exhaust memory for
    # large test sets at --size 512+.
    if len(seeds) > 1:
        batches = {tag: list(iter_batches(tag)) for tag in dirs}
        get_batches = batches.__getitem__
    else:
        get_batches = iter_batches

    per_seed = {"torch": [], "jax": []}
    for seed in seeds:
        feature_fn = make_vgg_feature_fn(
            load_vgg19_params(jnp.float32, seed=seed))

        def stats(tag):
            rs = RunningStats(1472)
            for batch in get_batches(tag):
                rs.update(feature_fn(jnp.asarray(batch)))
            return rs.finalize()

        mu_g, cov_g = stats("gt")
        for tag in ("torch", "jax"):
            mu, cov = stats(tag)
            per_seed[tag].append(
                float(frechet_distance(mu_g, cov_g, mu, cov)))
        print(f"seed {seed}: torch {per_seed['torch'][-1]:.3f} "
              f"jax {per_seed['jax'][-1]:.3f}")

    results = {
        # seed[0] keeps the historical single-seed row comparable
        "vfid_torch": per_seed["torch"][0],
        "vfid_jax": per_seed["jax"][0],
    }
    results["parity_delta"] = abs(results["vfid_jax"] - results["vfid_torch"])
    if len(seeds) > 1:
        results["seeds"] = seeds
        for tag in ("torch", "jax"):
            v = per_seed[tag]
            results[f"vfid_{tag}_by_seed"] = [round(x, 4) for x in v]
            results[f"vfid_{tag}_mean"] = round(sum(v) / len(v), 4)
            results[f"vfid_{tag}_range"] = [round(min(v), 4),
                                            round(max(v), 4)]
        results["jax_lower_seeds"] = sum(
            j < t for j, t in zip(per_seed["jax"], per_seed["torch"]))
        results["parity_delta_by_seed"] = [
            round(abs(j - t), 4)
            for j, t in zip(per_seed["jax"], per_seed["torch"])]
    results["n_images"] = len(names)
    results["feature_source"] = vgg19_params_source()
    results["extractor"] = "shared fixed-seed VGG19 taps, pooled, D=1472"
    print(json.dumps(results, indent=2))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=2)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
