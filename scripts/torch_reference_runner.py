"""CPU torch runner reproducing the reference training loop for the
FID-parity baseline (the CUDA-side baseline "must be measured
during the build").

This is a from-spec reimplementation of /root/reference/train.py's live
loss surface — NOT an import of the reference (networks.py is CUDA-bound:
hard `torch.cuda.FloatTensor` in GANLoss, networks.py:810, and a
torchvision import this image cannot satisfy). Architecture and semantics
follow the spec with these documented choices:

- Generator = ExpandNetwork (networks.py:447-523), D = 3-scale PatchGAN
  with spectral norm + intermediate features (networks.py:716-806),
  losses = LSGAN + 10·feature-matching + 10·VGG + 1·TV (train.py:338-380),
  Adam(2e-4, β=(0.5, 0.999)) ×2, G step then D step (train.py:384-390).
- The compression net is OMITTED on BOTH sides of the comparison: in the
  reference it never trains (SURVEY Q1+Q2 — optimizer_c holds net_d's
  params and round() zeroes its grads) so it acts as a frozen RANDOM
  filter; sharing one would require cross-framework weight export and not
  sharing one would give each side a different task. G instead consumes
  the stored 3-bit-quantized input directly (the same pairs the offline
  datagen writes — generate_dataset.py:100-106). The dead C-step block
  (train.py:392-402, a compute-only no-op) is likewise skipped.
- VGG19 weights: the SHARED fixed-seed extractor exported from
  p2p_tpu.models.vgg (this environment has no torchvision weights); both
  frameworks train against numerically identical VGG features.
- Eval PSNR/SSIM in the CORRECT pixel space (Q8 fixed, like the JAX side).

Outputs: result/<name>/preds_e<E>/*.png (test-set predictions),
metrics_<name>.jsonl, checkpoint state_dict.

Usage:
    python scripts/torch_reference_runner.py --data dataset/real256 \
        --name torch_ref --epochs 2 --subset 320
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import torch  # noqa: E402
import torch.nn as tnn  # noqa: E402
import torch.nn.functional as F  # noqa: E402
from PIL import Image  # noqa: E402


# --------------------------------------------------------------- models
class ResidualBlock(tnn.Module):
    """networks.py:429-444."""

    def __init__(self, ch):
        super().__init__()
        self.c1 = tnn.Conv2d(ch, ch, 3)
        self.b1 = tnn.BatchNorm2d(ch)
        self.c2 = tnn.Conv2d(ch, ch, 3)
        self.b2 = tnn.BatchNorm2d(ch)

    def forward(self, x):
        y = F.relu(self.b1(self.c1(F.pad(x, (1,) * 4, mode="reflect"))))
        y = self.b2(self.c2(F.pad(y, (1,) * 4, mode="reflect")))
        return F.relu(y + x)


class ExpandNet(tnn.Module):
    """networks.py:447-523 (one shared PReLU scalar, networks.py:452)."""

    def __init__(self, ngf=32, n_blocks=9):
        super().__init__()
        self.act = tnn.PReLU()
        self.e1 = tnn.Conv2d(12, ngf, 9)
        self.n1 = tnn.BatchNorm2d(ngf)
        self.e2 = tnn.Conv2d(ngf, ngf * 2, 3, stride=2)
        self.n2 = tnn.BatchNorm2d(ngf * 2)
        self.e3 = tnn.Conv2d(ngf * 2, ngf * 4, 3, stride=2)
        self.n3 = tnn.BatchNorm2d(ngf * 4)
        self.blocks = tnn.ModuleList(
            [ResidualBlock(ngf * 4) for _ in range(n_blocks)])
        self.d1 = tnn.Conv2d(ngf * 4, ngf * 2, 3)
        self.dn1 = tnn.BatchNorm2d(ngf * 2)
        self.d2 = tnn.Conv2d(ngf * 2, ngf, 3)
        self.dn2 = tnn.BatchNorm2d(ngf)
        self.d3 = tnn.Conv2d(ngf, 3, 9)
        self.dn3 = tnn.BatchNorm2d(3)

    def forward(self, x):
        y = F.pixel_unshuffle(x, 2)
        y = F.interpolate(y, scale_factor=2, mode="nearest")
        y = self.act(self.n1(self.e1(F.pad(y, (4,) * 4, mode="reflect"))))
        y = self.act(self.n2(self.e2(F.pad(y, (1,) * 4, mode="reflect"))))
        y = self.act(self.n3(self.e3(F.pad(y, (1,) * 4, mode="reflect"))))
        res = y
        for blk in self.blocks:
            y = blk(y)
        y = F.leaky_relu(y + res, 0.2)
        y = F.interpolate(y, scale_factor=2, mode="nearest")
        y = self.act(self.dn1(self.d1(F.pad(y, (1,) * 4, mode="reflect"))))
        y = F.interpolate(y, scale_factor=2, mode="nearest")
        y = self.act(self.dn2(self.d2(F.pad(y, (1,) * 4, mode="reflect"))))
        y = self.dn3(self.d3(F.pad(y, (4,) * 4, mode="reflect")))
        return torch.tanh(y)


class UNet(tnn.Module):
    """pix2pix U-Net-256 (BASELINE configs[0]) mirroring
    p2p_tpu.models.unet.UNetGenerator's deconv mode: k4s2 encoder
    (LeakyReLU 0.2 pre-conv from level 1, BN on inner levels),
    ConvTranspose k4s2 decoder (ReLU pre-conv, BN + dropout on the three
    post-innermost levels, skip concat), tanh head."""

    def __init__(self, ngf=64, num_downs=8, out_ch=3):
        super().__init__()
        self.num_downs = num_downs
        feats = [min(ngf * 2 ** i, ngf * 8) for i in range(num_downs)]
        self.downs = tnn.ModuleList()
        self.dnorms = tnn.ModuleDict()
        in_ch = 3
        for i, f in enumerate(feats):
            self.downs.append(tnn.Conv2d(in_ch, f, 4, stride=2, padding=1))
            if 0 < i < num_downs - 1:
                self.dnorms[str(i)] = tnn.BatchNorm2d(f)
            in_ch = f
        self.ups = tnn.ModuleList()
        self.unorms = tnn.ModuleDict()
        for i in reversed(range(num_downs)):
            f = out_ch if i == 0 else feats[i - 1]
            src = feats[i] if i == num_downs - 1 else feats[i] * 2
            self.ups.append(
                tnn.ConvTranspose2d(src, f, 4, stride=2, padding=1))
            if i > 0:
                self.unorms[str(i)] = tnn.BatchNorm2d(f)

    def forward(self, x):
        skips = []
        y = x
        for i, conv in enumerate(self.downs):
            if i > 0:
                y = F.leaky_relu(y, 0.2)
            y = conv(y)
            if str(i) in self.dnorms:
                y = self.dnorms[str(i)](y)
            skips.append(y)
        nd = self.num_downs
        for j, conv in enumerate(self.ups):
            i = nd - 1 - j
            y = conv(F.relu(y))
            if i > 0:
                y = self.unorms[str(i)](y)
                if nd - 4 <= i < nd - 1:
                    y = F.dropout(y, 0.5, training=self.training)
                y = torch.cat([y, skips[i - 1]], 1)
        return torch.tanh(y)


class NLayerD(tnn.Module):
    """networks.py:758-806: 5 stages, SN on the 3 inner convs (optional —
    the facades PatchGAN is the no-SN corner), all intermediate
    activations returned."""

    def __init__(self, in_ch=6, ndf=64, n_layers=3, use_sn=True):
        super().__init__()
        sn = tnn.utils.spectral_norm if use_sn else (lambda m: m)
        seq = [tnn.Conv2d(in_ch, ndf, 4, stride=2, padding=2)]
        nf = ndf
        for _ in range(1, n_layers):
            nf2 = min(nf * 2, 512)
            seq.append(sn(tnn.Conv2d(nf, nf2, 4, stride=2, padding=2)))
            nf = nf2
        nf2 = min(nf * 2, 512)
        seq.append(sn(tnn.Conv2d(nf, nf2, 4, stride=1, padding=2)))
        seq.append(tnn.Conv2d(nf2, 1, 4, stride=1, padding=2))
        self.stages = tnn.ModuleList(seq)

    def forward(self, x):
        feats = []
        y = x
        for i, conv in enumerate(self.stages):
            y = conv(y)
            if i < len(self.stages) - 1:
                y = F.leaky_relu(y, 0.2)
            feats.append(y)
        return feats


class MultiscaleD(tnn.Module):
    """networks.py:716-755: finest scale first; AvgPool(3,2,1,
    count_include_pad=False) between scales."""

    def __init__(self, in_ch=6, ndf=64, n_layers=3, num_d=3):
        super().__init__()
        self.ds = tnn.ModuleList(
            [NLayerD(in_ch, ndf, n_layers) for _ in range(num_d)])

    def forward(self, x):
        out, cur = [], x
        for i, d in enumerate(self.ds):
            out.append(d(cur))
            if i != len(self.ds) - 1:
                cur = F.avg_pool2d(cur, 3, stride=2, padding=1,
                                   count_include_pad=False)
        return out


class VGG19Torch(tnn.Module):
    """torchvision-VGG19 trunk shape, taps at indices 2/7/12/21/30
    (networks.py:41-50), weights injected from the shared flax extractor."""

    CFG = [("conv1_1", 64), ("conv1_2", 64), ("M", 0),
           ("conv2_1", 128), ("conv2_2", 128), ("M", 0),
           ("conv3_1", 256), ("conv3_2", 256), ("conv3_3", 256),
           ("conv3_4", 256), ("M", 0),
           ("conv4_1", 512), ("conv4_2", 512), ("conv4_3", 512),
           ("conv4_4", 512), ("M", 0),
           ("conv5_1", 512)]
    TAPS = ("conv1_1", "conv2_1", "conv3_1", "conv4_1", "conv5_1")

    def __init__(self):
        super().__init__()
        self.convs = tnn.ModuleDict()
        in_ch = 3
        for name, ch in self.CFG:
            if name == "M":
                continue
            self.convs[name] = tnn.Conv2d(in_ch, ch, 3, padding=1)
            in_ch = ch

    def load_flax(self, flax_params):
        with torch.no_grad():
            for name, conv in self.convs.items():
                k = np.asarray(flax_params[name]["kernel"])   # (kh,kw,in,out)
                b = np.asarray(flax_params[name]["bias"])
                conv.weight.copy_(torch.from_numpy(
                    k.transpose(3, 2, 0, 1).copy()))
                conv.bias.copy_(torch.from_numpy(b.copy()))
        for p in self.parameters():
            p.requires_grad_(False)

    def forward(self, x):
        taps = []
        y = x
        for name, _ in self.CFG:
            if name == "M":
                y = F.max_pool2d(y, 2)
                continue
            y = F.relu(self.convs[name](y))
            if name in self.TAPS:
                taps.append(y)
        return taps


# --------------------------------------------------------------- losses
VGG_W = (1 / 32, 1 / 16, 1 / 8, 1 / 4, 1.0)


def vgg_loss(vgg, x, y):
    fx = vgg(x)
    with torch.no_grad():
        fy = vgg(y)
    return sum(w * F.l1_loss(a, b.detach())
               for w, a, b in zip(VGG_W, fx, fy))


def gan_loss(preds, target_real: bool):
    """LSGAN on the last map per scale, summed (networks.py:840-850)."""
    total = 0.0
    for scale in preds:
        p = scale[-1]
        t = torch.full_like(p, 1.0 if target_real else 0.0)
        total = total + F.mse_loss(p, t)
    return total


def feat_match(pred_fake, pred_real, n_layers=3, num_d=3, lam=10.0):
    """train.py:344-351 exact weighting."""
    fw = 4.0 / (n_layers + 1)
    dw = 1.0 / num_d
    loss = 0.0
    for i in range(num_d):
        for j in range(len(pred_fake[i]) - 1):
            loss = loss + dw * fw * lam * F.l1_loss(
                pred_fake[i][j], pred_real[i][j].detach())
    return loss


def tv_loss(x):
    """train.py:123-126."""
    return (torch.mean(torch.abs(x[..., :-1] - x[..., 1:]))
            + torch.mean(torch.abs(x[..., :-1, :] - x[..., 1:, :])))


def init_weights(module, gain=0.02):
    """networks.py:128-146: conv N(0,.02); BN γ~N(1,.02), β=0."""
    for m in module.modules():
        if isinstance(m, tnn.Conv2d):
            tnn.init.normal_(m.weight, 0.0, gain)
            if m.bias is not None:
                tnn.init.zeros_(m.bias)
        elif isinstance(m, tnn.BatchNorm2d):
            tnn.init.normal_(m.weight, 1.0, gain)
            tnn.init.zeros_(m.bias)


# --------------------------------------------------------------- data/eval
def load_pairs(root, split, size, limit=None):
    a_dir, b_dir = os.path.join(root, split, "a"), os.path.join(root, split, "b")
    names = sorted(os.listdir(a_dir))
    if limit:
        names = names[:limit]
    out = []
    for n in names:
        pa = np.asarray(Image.open(os.path.join(a_dir, n)).convert("RGB")
                        .resize((size, size), Image.BICUBIC), np.float32)
        pb = np.asarray(Image.open(os.path.join(b_dir, n)).convert("RGB")
                        .resize((size, size), Image.BICUBIC), np.float32)
        out.append((n, pa / 127.5 - 1, pb / 127.5 - 1))
    return out


def to_chw(x):
    return torch.from_numpy(np.ascontiguousarray(x.transpose(2, 0, 1)))[None]


def to_img(t):
    """[-1,1] CHW tensor -> uint8 HWC (correct space — Q8 fixed)."""
    x = t.detach().squeeze(0).permute(1, 2, 0).numpy()
    return np.clip((x + 1) * 127.5, 0, 255).astype(np.uint8)


def psnr_ssim(ref, img):
    a = ref.astype(np.float64)
    b = img.astype(np.float64)
    mse = np.mean((a - b) ** 2)
    psnr = min(10 * np.log10(255.0 ** 2 / mse), 60.0) if mse else 60.0
    # light SSIM (global statistics) — the shared-extractor VFID is the
    # parity metric; PSNR is the sanity check
    mu_a, mu_b = a.mean(), b.mean()
    va, vb = a.var(), b.var()
    cov = ((a - mu_a) * (b - mu_b)).mean()
    c1, c2 = (0.01 * 255) ** 2, (0.03 * 255) ** 2
    ssim = ((2 * mu_a * mu_b + c1) * (2 * cov + c2)) / (
        (mu_a ** 2 + mu_b ** 2 + c1) * (va + vb + c2))
    return psnr, float(ssim)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--data", default="dataset/real256")
    ap.add_argument("--name", default="torch_ref")
    ap.add_argument("--size", type=int, default=256)
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--subset", type=int, default=320,
                    help="train patches used (CPU budget)")
    ap.add_argument("--test_subset", type=int, default=128)
    ap.add_argument("--ngf", type=int, default=32)
    ap.add_argument("--n_blocks", type=int, default=9)
    ap.add_argument("--model", default="expand", choices=["expand", "unet"],
                    help="expand = reference recipe (3-scale SN D, "
                         "featmatch+VGG+TV); unet = facades pix2pix recipe "
                         "(70x70 PatchGAN, LSGAN + 100*L1, no VGG term)")
    ap.add_argument("--grad_clip", type=float, default=0.0,
                    help="stabilization guard matching the JAX side's "
                         "--grad_clip: zero non-finite gradient entries, "
                         "then clip_grad_norm_ to this bound (0 = off)")
    ap.add_argument("--seed", type=int, default=123)
    ap.add_argument("--threads", type=int, default=0)
    ap.add_argument("--out_dir", default="result")
    args = ap.parse_args(argv)

    if args.threads:
        torch.set_num_threads(args.threads)
    torch.manual_seed(args.seed)
    np.random.seed(args.seed)

    train = load_pairs(args.data, "train", args.size, args.subset)
    test = load_pairs(args.data, "test", args.size, args.test_subset)
    print(f"{len(train)} train / {len(test)} test pairs @ {args.size}px")

    facades = args.model == "unet"
    if facades:
        # clamp depth to the factor-of-2 content of the image size, like
        # p2p_tpu.models.unet (64px -> 6 levels, 256px -> 8)
        nd = 0
        s = args.size
        while s % 2 == 0 and s > 1 and nd < 8:
            s //= 2
            nd += 1
        g = UNet(ngf=64, num_downs=nd)
        d = NLayerD(use_sn=False)
    else:
        g = ExpandNet(args.ngf, args.n_blocks)
        d = MultiscaleD()
    init_weights(g)
    init_weights(d)

    # shared fixed-seed VGG from the JAX side (identical features); the
    # facades recipe uses NO VGG term in training (extractor is eval-only)
    from p2p_tpu.models.vgg import load_vgg19_params, vgg19_params_source
    vgg = None
    if not facades:
        vgg = VGG19Torch()
        vgg.load_flax(load_vgg19_params(np.float32))
    vgg_source = vgg19_params_source()

    opt_g = torch.optim.Adam(g.parameters(), lr=2e-4, betas=(0.5, 0.999))
    opt_d = torch.optim.Adam(d.parameters(), lr=2e-4, betas=(0.5, 0.999))

    out_root = os.path.join(args.out_dir, args.name)
    os.makedirs(out_root, exist_ok=True)
    log_path = f"metrics_{args.name}.jsonl"
    log = open(log_path, "a")

    order = np.arange(len(train))
    step = 0
    for epoch in range(1, args.epochs + 1):
        g.train(); d.train()
        np.random.shuffle(order)
        sums = {"loss_g": 0.0, "loss_d": 0.0}
        t0 = time.time()
        for idx in order:
            _, a_img, b_img = train[idx]
            # direction b2a (train.py:139 default): input = quantized b,
            # target = original a
            real_a = to_chw(b_img)
            real_b = to_chw(a_img)
            fake_b = g(real_a)

            def d_of(pair):
                out = d(pair)
                return out if isinstance(out[0], list) else [out]

            # D loss (train.py:308-320)
            pred_fake = d_of(torch.cat([real_a, fake_b.detach()], 1))
            pred_real = d_of(torch.cat([real_a, real_b], 1))
            loss_d = 0.5 * (gan_loss(pred_fake, False)
                            + gan_loss(pred_real, True))

            # G loss (train.py:336-380; facades: LSGAN + 100*L1)
            pred_fake_g = d_of(torch.cat([real_a, fake_b], 1))
            loss_g = gan_loss(pred_fake_g, True)
            if facades:
                loss_g = loss_g + 100.0 * F.l1_loss(fake_b, real_b)
            else:
                loss_g = (loss_g
                          + feat_match(pred_fake_g, pred_real)
                          + 10.0 * vgg_loss(vgg, fake_b, real_b)
                          + tv_loss(fake_b))

            def guard(params):
                # train/state.py _zero_nonfinite + global-norm clip parity
                if args.grad_clip > 0:
                    for p in params:
                        if p.grad is not None:
                            torch.nan_to_num_(p.grad, nan=0.0,
                                              posinf=0.0, neginf=0.0)
                    torch.nn.utils.clip_grad_norm_(params, args.grad_clip)

            opt_g.zero_grad(); loss_g.backward(retain_graph=False)
            guard(list(g.parameters()))
            opt_g.step()
            opt_d.zero_grad(); loss_d.backward()
            guard(list(d.parameters()))
            opt_d.step()
            sums["loss_g"] += float(loss_g)
            sums["loss_d"] += float(loss_d)
            step += 1

        n = len(order)
        rec = {"kind": "train", "framework": "torch-cpu", "epoch": epoch,
               "steps": step, "loss_g": sums["loss_g"] / n,
               "loss_d": sums["loss_d"] / n,
               "sec_per_step": (time.time() - t0) / n,
               "vgg_feature_source": vgg_source}
        print(json.dumps(rec)); log.write(json.dumps(rec) + "\n"); log.flush()

        # eval: dump predictions + PSNR (no_grad — Q9 fixed)
        g.eval()
        pred_dir = os.path.join(out_root, f"preds_e{epoch}")
        os.makedirs(pred_dir, exist_ok=True)
        psnrs, ssims = [], []
        with torch.no_grad():
            for name, a_img, b_img in test:
                pred = g(to_chw(b_img))
                img = to_img(pred)
                Image.fromarray(img).save(os.path.join(pred_dir, name))
                p, s = psnr_ssim(
                    np.clip((a_img + 1) * 127.5, 0, 255).astype(np.uint8),
                    img)
                psnrs.append(p); ssims.append(s)
        rec = {"kind": "eval", "framework": "torch-cpu", "epoch": epoch,
               "psnr_mean": float(np.mean(psnrs)),
               "psnr_max": float(np.max(psnrs)),
               "ssim_mean": float(np.mean(ssims)),
               "pred_dir": pred_dir}
        print(json.dumps(rec)); log.write(json.dumps(rec) + "\n"); log.flush()

    torch.save({"epoch": args.epochs, "state_dict_g": g.state_dict()},
               os.path.join(out_root, "net_g_final.pth"))
    log.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
