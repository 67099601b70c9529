"""Plain reference of ONE WHOLE TRAIN STEP, followed for the first steps.

Written from the training iteration of the source (train.py:269-443 as
SURVEY.md section 3.1 lays it out; pix2pixHD, Wang et al. 2018, section
3.2 for the losses), with the two repairs the configuration states (the
compression net has its own optimizer and a straight-through quantizer):

  1. code = stop(q(C(real_b)))            (configurations with a C net)
     fake = G(code)                       (else: fake = G(real_a))
  2. D loss = 0.5 * (LSGAN(D(real_a | stop(fake)), 0)
                     + LSGAN(D(real_a | real_b), 1)); every D call runs one
     power iteration of its spectral norms, fake first, then real.
  3. G loss = LSGAN(D(real_a | fake), 1) + 10 * feature matching against
     the real call's features + 10 * VGG19(fake, real_b) + tv * TV(fake);
     it sees the D of the step's start.
  4. Adam(2e-4, 0.5, 0.999) on G, then on D.
  5. C loss = MSE(G'(ste_q(C(real_b))), real_b) + 10 * VGG19(code, real_b)
     against the UPDATED generator G'; Adam on C.

Only ``jax.numpy`` / ``lax`` in float32 at ``Precision.HIGHEST``; nothing
of the program is imported. Norms in train mode use the batch's own
moments, so running statistics never enter. The generator and the
compression net run on the whole batch (BatchNorm couples the rows);
everything after the generator is a mean over rows and is taken in
blocks of rows so that float32 fits the chip beside nothing else.

State is a flat dict ``{"params_g/...": leaf, "params_d/...", "params_c/...",
"spectral_d/.../u", "vgg/conv1_1/kernel", ...}``.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import nn

VGG_LAYERS = (("conv1_1", "conv1_2"), ("conv2_1", "conv2_2"),
              ("conv3_1", "conv3_2", "conv3_3", "conv3_4"),
              ("conv4_1", "conv4_2", "conv4_3", "conv4_4"), ("conv5_1",))
VGG_WEIGHTS = (1 / 32, 1 / 16, 1 / 8, 1 / 4, 1.0)
NETS = ("params_g", "params_d", "params_c")

Flat = Dict[str, jnp.ndarray]


def sub(tree: Flat, prefix: str) -> Flat:
    return {k: v for k, v in tree.items() if k.startswith(prefix + "/")}


# ------------------------------------------------------------------ VGG19


def vgg_taps(p: Flat, x):
    """relu1_1, relu2_1, relu3_1, relu4_1, relu5_1 of the VGG19 trunk
    (3x3 convs, zero padding 1, 2x2 max-pools between the groups)."""
    taps, y = [], x
    for i, group in enumerate(VGG_LAYERS):
        if i:
            y = nn.max_pool_2(y)
        for j, name in enumerate(group):
            y = jnp.maximum(nn.zero_conv(y, p[f"vgg/{name}/kernel"],
                                         p[f"vgg/{name}/bias"], pad=1), 0)
            if j == 0:
                taps.append(y)
    return taps


def vgg_distance(p: Flat, x, y):
    fy = [jax.lax.stop_gradient(t) for t in vgg_taps(p, y)]
    return sum(w * jnp.mean(jnp.abs(a - b))
               for w, a, b in zip(VGG_WEIGHTS, vgg_taps(p, x), fy))


# ---------------------------------------------------------- discriminator


def spectral_kernel(kernel, u):
    """One power iteration on the kernel seen as (out, k*k*in); the kernel
    over sigma = u' W v, with u and v held constant under the gradient."""
    w = kernel.transpose(3, 0, 1, 2).reshape(kernel.shape[3], -1)
    ws = jax.lax.stop_gradient(w)
    unit = lambda t: t / (jnp.sqrt(jnp.sum(t * t)) + 1e-12)  # noqa: E731
    v = unit(jnp.matmul(ws.T, u, precision=nn.HIGHEST))
    u = unit(jnp.matmul(ws, v, precision=nn.HIGHEST))
    sigma = jnp.dot(u, jnp.matmul(w, v, precision=nn.HIGHEST),
                    precision=nn.HIGHEST)
    return kernel / sigma, u


def patch_discriminator(p: Flat, scale: str, x) -> Tuple[list, Flat]:
    """C64 - C128 - C256 - C512 - 1, all 4x4 with zero padding 2, strides
    2, 2, 2, 1, 1, LeakyReLU(0.2) after all but the last; the three inner
    convs spectrally normalised. Returns the five activations and the
    power-iteration vectors after this call."""
    d, s = f"params_d/{scale}", f"spectral_d/{scale}"
    feats, new_u = [], {}
    y = nn.leaky_relu(nn.zero_conv(
        x, p[f"{d}/_PlainConv_0/Conv_0/kernel"],
        p[f"{d}/_PlainConv_0/Conv_0/bias"], stride=2, pad=2))
    feats.append(y)
    for i, stride in enumerate((2, 2, 1)):
        kernel, u = spectral_kernel(p[f"{d}/SpectralConv_{i}/kernel"],
                                    p[f"{s}/SpectralConv_{i}/u"])
        new_u[f"{s}/SpectralConv_{i}/u"] = u
        y = nn.leaky_relu(nn.zero_conv(
            y, kernel, p[f"{d}/SpectralConv_{i}/bias"], stride=stride, pad=2))
        feats.append(y)
    feats.append(nn.zero_conv(y, p[f"{d}/_PlainConv_1/Conv_0/kernel"],
                              p[f"{d}/_PlainConv_1/Conv_0/bias"], pad=2))
    return feats, new_u


def discriminator(p: Flat, pair, num_d: int = 3):
    """Finest scale first; scale i sees the pair average-pooled i times.
    The finest is ``scale{num_d - 1}`` in the state's naming."""
    out, new_u, x = [], {}, pair
    for i in range(num_d):
        feats, u = patch_discriminator(p, f"scale{num_d - 1 - i}", x)
        out.append(feats)
        new_u.update(u)
        if i != num_d - 1:
            x = nn.avg_pool_3s2(x)
    return out, new_u


def lsgan(preds, real: bool):
    target = 1.0 if real else 0.0
    return sum(jnp.mean(jnp.square(scale[-1] - target)) for scale in preds)


def feature_matching(fake, real, n_layers: int, weight: float):
    w = weight * (4.0 / (n_layers + 1)) / len(fake)
    return sum(w * jnp.mean(jnp.abs(f - jax.lax.stop_gradient(r)))
               for sf, sr in zip(fake, real) for f, r in zip(sf[:-1], sr[:-1]))


def total_variation(x):
    return (jnp.mean(jnp.abs(x[:, :, :-1] - x[:, :, 1:]))
            + jnp.mean(jnp.abs(x[:, :-1] - x[:, 1:])))


def quantize_ste(raw, bits: int):
    """The quantizer forward; backward the clamp's own gradient."""
    clipped = jnp.clip(raw, 0.0, 1.0)
    return clipped + jax.lax.stop_gradient(nn.quantize(raw, bits) - clipped)


# ---------------------------------------------------------------- the step


def _blocks(x, rows: int):
    return x.reshape((x.shape[0] // rows, rows) + x.shape[1:])


class TrainReference:
    """``model``: the configuration's reference module (``g_forward``,
    ``c_forward`` or None, ``ROW_BLOCK``). ``hyper``: the configuration
    file's ``train_reference`` group."""

    def __init__(self, model, hyper: dict):
        self.model, self.h = model, hyper
        self.has_c = getattr(model, "c_forward", None) is not None
        self._fwd = jax.jit(self._forward)
        self._losses = jax.jit(self._row_block_losses)
        self._gback = jax.jit(self._generator_grads)
        self._cvgg = jax.jit(self._code_vgg)
        self._cback = jax.jit(self._compression_grads)
        self._adam = jax.jit(self._adam_update)

    # -- phases, each one jitted program ---------------------------------
    def _forward(self, p: Flat, batch):
        a, b = nn.to_unit(batch["input"]), nn.to_unit(batch["target"])
        if self.has_c:
            code = nn.quantize(self.model.c_forward(p, b),
                               self.h["quant_bits"])
        else:
            code = a
        return a, b, code, self.model.g_forward(p, code, remat=True)

    def _row_block_losses(self, p: Flat, a, b, fake):
        """D's loss and gradient, G's loss parts and their gradient with
        respect to the generated image, block of rows by block of rows
        (every term is a mean over rows)."""
        h, rows = self.h, min(self.model.ROW_BLOCK, a.shape[0])
        weight = rows / a.shape[0]
        d_params = sub(p, "params_d")
        rest = {k: v for k, v in p.items() if k not in d_params}

        def one(carry, blk):
            ab, bb, fb = blk

            def d_loss(dp):
                q = {**rest, **dp}
                pf, u1 = discriminator(q, jnp.concatenate([ab, fb], -1))
                pr, u2 = discriminator({**q, **u1},
                                       jnp.concatenate([ab, bb], -1))
                return 0.5 * (lsgan(pf, False) + lsgan(pr, True)), (pr, u2)

            (ld, (pred_real, u2)), gd = jax.value_and_grad(
                d_loss, has_aux=True)(d_params)

            def g_loss(f):
                pf, _ = discriminator(p, jnp.concatenate([ab, f], -1))
                parts = {"g_gan": lsgan(pf, True)}
                if h["lambda_feat"] > 0:
                    parts["g_feat"] = feature_matching(
                        pf, pred_real, h["n_layers_D"], h["lambda_feat"])
                if h["lambda_vgg"] > 0:
                    parts["g_vgg"] = h["lambda_vgg"] * vgg_distance(p, f, bb)
                if h["lambda_tv"] > 0:
                    parts["g_tv"] = h["lambda_tv"] * total_variation(f)
                return sum(parts.values()), parts

            (lg, parts), gf = jax.value_and_grad(g_loss, has_aux=True)(fb)
            acc = {"loss_d": ld, "loss_g": lg, **parts, "grads_d": gd}
            carry = jax.tree_util.tree_map(lambda c, x: c + weight * x,
                                           carry, acc)
            return carry, (weight * gf, u2)

        zero = {"loss_d": 0.0, "loss_g": 0.0, "g_gan": 0.0,
                "grads_d": jax.tree_util.tree_map(jnp.zeros_like, d_params)}
        for name in ("feat", "vgg", "tv"):
            if h[f"lambda_{name}"] > 0:
                zero[f"g_{name}"] = 0.0
        zero = jax.tree_util.tree_map(lambda x: jnp.asarray(x, jnp.float32),
                                      zero)
        acc, (grad_fake, u2) = jax.lax.scan(
            one, zero, (_blocks(a, rows), _blocks(b, rows),
                        _blocks(jax.lax.stop_gradient(fake), rows)))
        grads_d = acc.pop("grads_d")
        u2 = jax.tree_util.tree_map(lambda x: x[-1], u2)
        return acc, grads_d, grad_fake.reshape(fake.shape), u2

    def _generator_grads(self, p: Flat, code, grad_fake):
        rest = {k: v for k, v in p.items() if not k.startswith("params_g/")}
        return jax.grad(lambda g: jnp.vdot(
            self.model.g_forward({**rest, **g}, code, remat=True),
            grad_fake))(sub(p, "params_g"))

    def _code_vgg(self, p: Flat, b):
        """VGG19(code, real_b) and its gradient with respect to the code,
        in blocks of rows."""
        rows = min(self.model.ROW_BLOCK, b.shape[0])
        weight = rows / b.shape[0]
        code = nn.quantize(self.model.c_forward(p, b), self.h["quant_bits"])

        def one(carry, blk):
            cb, bb = blk
            loss, g = jax.value_and_grad(
                lambda c: self.h["lambda_vgg"] * vgg_distance(p, c, bb))(cb)
            return carry + weight * loss, weight * g

        loss, grad_code = jax.lax.scan(
            one, jnp.zeros((), jnp.float32),
            (_blocks(code, rows), _blocks(b, rows)))
        return loss, grad_code.reshape(code.shape)

    def _compression_grads(self, p: Flat, b, grad_code):
        rest = {k: v for k, v in p.items() if not k.startswith("params_c/")}

        def loss(c):
            q = {**rest, **c}
            code = quantize_ste(self.model.c_forward(q, b),
                                self.h["quant_bits"])
            mse = jnp.mean(jnp.square(
                self.model.g_forward(q, code, remat=True) - b))
            return mse + jnp.vdot(code, grad_code), mse

        (_, mse), grads = jax.value_and_grad(loss, has_aux=True)(
            sub(p, "params_c"))
        return mse, grads

    def _adam_update(self, p: Flat, grads: Flat, m: Flat, v: Flat, count):
        h = self.h
        b1, b2 = h["beta1"], h["beta2"]
        t = (count + 1).astype(jnp.float32)
        out_p, out_m, out_v = {}, {}, {}
        for k, g in grads.items():
            out_m[k] = b1 * m[k] + (1 - b1) * g
            out_v[k] = b2 * v[k] + (1 - b2) * jnp.square(g)
            step = (out_m[k] / (1 - b1 ** t)) / (
                jnp.sqrt(out_v[k] / (1 - b2 ** t)) + h["eps"])
            out_p[k] = p[k] - h["lr"] * step
        return out_p, out_m, out_v

    # -- the first steps --------------------------------------------------
    def follow(self, state: Flat, batches):
        """Follow ``batches`` from ``state``. Returns each step's losses,
        the first step's gradients as each optimizer got them, and the
        parameters after the last step — all as numpy, by leaf."""
        p = {k: jnp.asarray(v) for k, v in state.items()}
        trainable = {k for k in p if k.split("/", 1)[0] in NETS}
        m = {k: jnp.zeros_like(p[k]) for k in trainable}
        v = {k: jnp.zeros_like(p[k]) for k in trainable}
        losses, first_grads = [], None
        for i, batch in enumerate(batches):
            count = jnp.asarray(i, jnp.int32)
            a, b, code, fake = self._fwd(p, batch)
            acc, grads_d, grad_fake, u2 = self._losses(p, a, b, fake)
            del fake
            grads = dict(self._gback(p, code, grad_fake))
            grads.update(grads_d)
            del grad_fake, code
            for net in ("params_g", "params_d"):
                g = sub(grads, net)
                new_p, new_m, new_v = self._adam(sub(p, net), g, sub(m, net),
                                                 sub(v, net), count)
                p.update(new_p), m.update(new_m), v.update(new_v)
            p.update(u2)
            step_losses = {k: float(x) for k, x in acc.items()}
            if self.has_c:
                vgg_c, grad_code = (self._cvgg(p, b)
                                    if self.h["lambda_vgg"] > 0 else
                                    (0.0, jnp.zeros(b.shape, jnp.float32)))
                mse, gc = self._cback(p, b, grad_code)
                grads.update(gc)
                new_p, new_m, new_v = self._adam(
                    sub(p, "params_c"), gc, sub(m, "params_c"),
                    sub(v, "params_c"), count)
                p.update(new_p), m.update(new_m), v.update(new_v)
                step_losses["loss_c"] = float(mse) + float(vgg_c)
            losses.append(step_losses)
            if first_grads is None:
                first_grads = {k: np.asarray(g) for k, g in grads.items()}
            del grads
        params = {k: np.asarray(p[k]) for k in trainable}
        return losses, first_grads, params
