"""VQGAN: the first-stage autoencoder of Esser, Rombach, Ommer, "Taming
Transformers for High-Resolution Image Synthesis" (CVPR 2021,
arXiv:2012.09841, section 3.1), at the sizes of the authors' code
(github.com/CompVis/taming-transformers: ``diffusionmodules/model.py``
``Encoder`` / ``Decoder``, ``vqvae/quantize.py`` ``VectorQuantizer2`` with
the legacy loss; the released ``vqgan_imagenet_f16_16384``).

``GN`` = GroupNorm(32, eps 1e-6, affine), ``sw(x) = x * sigmoid(x)``
(:class:`p2p_tpu.ops.norm.GroupNorm`, one pass each); every k3
convolution pads 1 with zeros and has a bias; every convolution is a
:class:`p2p_tpu.ops.conv.ConvLayer` / ``UpsampleConvLayer``, so its form
is ``ops/conv.py``'s choice from the shape.

    Res(cin, cout)(x) = s(x) + conv3(sw(GN(conv3(sw(GN(x))))))
        s = identity, or conv1x1(cin -> cout) where cin != cout
    Attn(c)(x) = x + proj(softmax(q k^T * c^-0.5) v)
        q, k, v = conv1x1(GN(x)) each, one head over the H*W positions
    Down(x) = conv3_stride2_pad0(pad(x, bottom 1, right 1));  Up(x) =
        conv3(nearest_x2(x))
    Encoder: conv3(3 -> ch); a level per entry of ch_mult, each
        ``res_blocks`` Res (the first takes the level's input width), each
        followed by Attn where the extent is ``ATTN_EXTENT`` (16), then Down
        (all levels but the last); Res Attn Res; conv3(sw(GN(.)), -> z)
    Quantizer: z = conv1x1(enc); k_i = argmin_j |z_i - e_j|^2 over the
        ``codes`` rows of the codebook e (width ``embed_dim``, init
        uniform(+-1/codes)); forward value z + sg(e_k - z) (straight
        through: the decoder's gradient reaches z unchanged); codebook loss
        mean((sg(e_k) - z)^2) + BETA * mean((e_k - sg(z))^2): the code's
        LEGACY form, beta (0.25) on the codebook term, where the paper's eq. 4
        puts it on the commitment term; then conv1x1
    Decoder: conv3(z -> top); Res Attn Res; the levels in reverse with
        ``res_blocks`` + 1 Res each (+ Attn at ``ATTN_EXTENT``), then Up
        (all but the last); conv3(sw(GN(.)), ch -> 3); no tanh

The nearest-code search runs in float32 at ``Precision.HIGHEST`` whatever
the compute dtype is (a seeded codebook's rows are ~1e-4 apart: in bf16
the index is noise), on ``|e_j|^2 - 2 z_i . e_j``: ``|z_i|^2`` is the
same for every j and in float32 would swallow what differs.

The module maps an image to its reconstruction, like every generator
here (``cli.infer`` / ``cli.serve``: encode, nearest codes, decode). In a
training forward it also leaves, in the collection ``vq`` (mutable in
the train step only): ``codebook_loss``, which the step adds to G's loss
and pulls back through with its weight; ``indices`` ``[N, h, w]``;
``distances`` and ``latent`` for whoever checks the search; and
``last_input``, the input of the last convolution, from which the step
takes the adaptive adversarial weight (``train/step.adaptive_gan_weight``).

Scopes: ``gn_swish`` (every GN + sw site), ``attn``, ``vq`` (both 1x1
convolutions, distances, argmin, gather, and under its name in the
backward the codebook's scatter-add).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from p2p_tpu.ops.conv import ConvLayer, UpsampleConvLayer
from p2p_tpu.ops.norm import GroupNorm

#: torch's default Conv2d kernel init, kaiming_uniform(a=sqrt(5)):
#: uniform(+-1/sqrt(fan_in)). Biases start at zero here (torch draws them
#: uniform too: a stated departure).
_KERNEL_INIT = nn.initializers.variance_scaling(1.0 / 3.0, "fan_in",
                                                "uniform")
#: where the generator's last kernel sits in ``params_g``
LAST_KERNEL = ("decoder", "conv_out", "Conv_0", "kernel")
#: the extent at which every residual block is followed by attention, and
#: the weight of the legacy loss's codebook term (the authors'
#: ``attn_resolutions`` and ``beta``); the latent has the codebook's width
ATTN_EXTENT = 16
BETA = 0.25


def _conv(features: int, k: int, name: str, dtype, stride: int = 1,
          pad_mode: str = "zero") -> ConvLayer:
    return ConvLayer(features, kernel_size=k, stride=stride,
                     pad_mode=pad_mode, dtype=dtype,
                     kernel_init=_KERNEL_INIT, name=name)


class ResnetBlock(nn.Module):
    features: int
    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, x):
        h = _conv(self.features, 3, "conv1", self.dtype)(
            GroupNorm(name="norm1")(x))
        h = _conv(self.features, 3, "conv2", self.dtype)(
            GroupNorm(name="norm2")(h))
        if x.shape[-1] != self.features:
            x = _conv(self.features, 1, "nin_shortcut", self.dtype)(x)
        return x + h


class AttnBlock(nn.Module):
    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, x):
        n, hh, ww, c = x.shape
        with jax.named_scope("attn"):
            h = GroupNorm(swish=False, name="norm")(x)
            q, k, v = (_conv(c, 1, name, self.dtype)(h).reshape(
                n, hh * ww, c) for name in ("q", "k", "v"))
            logits = jnp.einsum("nic,njc->nij", q, k,
                                preferred_element_type=jnp.float32)
            w = jax.nn.softmax(logits * (float(c) ** -0.5), axis=-1)
            out = jnp.einsum("nij,njc->nic", w.astype(v.dtype), v)
            out = _conv(c, 1, "proj_out", self.dtype)(
                out.reshape(n, hh, ww, c))
            return x + out


def _level(x, width: int, blocks: int, name: str, dtype):
    for j in range(blocks):
        x = ResnetBlock(width, dtype, name=f"{name}_block_{j}")(x)
        if x.shape[1] == ATTN_EXTENT:
            x = AttnBlock(dtype, name=f"{name}_attn_{j}")(x)
    return x


def _middle(x, dtype):
    x = ResnetBlock(x.shape[-1], dtype, name="mid_block_1")(x)
    x = AttnBlock(dtype, name="mid_attn_1")(x)
    return ResnetBlock(x.shape[-1], dtype, name="mid_block_2")(x)


class Encoder(nn.Module):
    ch: int
    ch_mult: Tuple[int, ...]
    res_blocks: int
    z_channels: int
    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, x):
        x = _conv(self.ch, 3, "conv_in", self.dtype)(x)
        for i, mult in enumerate(self.ch_mult):
            x = _level(x, self.ch * mult, self.res_blocks, f"down_{i}",
                       self.dtype)
            if i != len(self.ch_mult) - 1:
                x = _conv(x.shape[-1], 3, f"down_{i}_downsample",
                          self.dtype, stride=2, pad_mode="zero_after")(x)
        x = _middle(x, self.dtype)
        return _conv(self.z_channels, 3, "conv_out", self.dtype)(
            GroupNorm(name="norm_out")(x))


class Decoder(nn.Module):
    ch: int
    ch_mult: Tuple[int, ...]
    res_blocks: int
    out_channels: int = 3
    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, z):
        x = _conv(self.ch * self.ch_mult[-1], 3, "conv_in", self.dtype)(z)
        x = _middle(x, self.dtype)
        for i in reversed(range(len(self.ch_mult))):
            x = _level(x, self.ch * self.ch_mult[i], self.res_blocks + 1,
                       f"up_{i}", self.dtype)
            if i != 0:
                x = UpsampleConvLayer(
                    x.shape[-1], kernel_size=3, upsample=2, pad_mode="zero",
                    dtype=self.dtype, kernel_init=_KERNEL_INIT,
                    name=f"up_{i}_upsample")(x)
        h = GroupNorm(name="norm_out")(x)
        self.sow("vq", "last_input", h, reduce_fn=lambda _, new: new,
                 init_fn=lambda: None)
        return _conv(self.out_channels, 3, "conv_out", self.dtype)(h)


def code_distances(z: jax.Array, codebook: jax.Array,
                   dtype=jnp.float32) -> jax.Array:
    """``|e_j|^2 - 2 z_i . e_j`` for the rows ``z_i`` of ``z`` ``[M, D]``
    and ``e_j`` of ``codebook`` ``[K, D]``: the squared distance less
    ``|z_i|^2``, which no argmin over j sees. ``dtype`` float32 (the
    model's) runs the product at ``Precision.HIGHEST``; anything narrower
    is what a control rounds to."""
    z, e = z.astype(dtype), codebook.astype(dtype)
    zdote = jnp.einsum("md,kd->mk", z, e, precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=dtype)
    return jnp.sum(jnp.square(e), axis=1)[None, :] - 2.0 * zdote


class VectorQuantizer(nn.Module):
    codes: int
    embed_dim: int
    # the dtype of the nearest-code search; anything but float32 is a
    # control (benchmark/tools/control_vq.py, tests/test_vqgan.py)
    distance_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, z):
        """``z`` ``[N, h, w, D]`` -> (straight-through quantised ``z``,
        codebook loss, indices ``[N, h, w]``, distances ``[N*h*w, K]``,
        the float32 rows ``[N*h*w, D]`` they were taken on)."""
        lim = 1.0 / self.codes
        codebook = self.param(
            "embedding", lambda key, shape: jax.random.uniform(
                key, shape, jnp.float32, -lim, lim),
            (self.codes, self.embed_dim))
        zf = z.astype(jnp.float32)
        flat = zf.reshape(-1, self.embed_dim)
        dist = code_distances(jax.lax.stop_gradient(flat), codebook,
                              self.distance_dtype)
        idx = jnp.argmin(dist, axis=1)
        zq = jnp.take(codebook, idx, axis=0).reshape(zf.shape)
        sg = jax.lax.stop_gradient
        loss = (jnp.mean(jnp.square(sg(zq) - zf))
                + BETA * jnp.mean(jnp.square(zq - sg(zf))))
        out = zf + sg(zq - zf)
        return (out.astype(z.dtype), loss, idx.reshape(z.shape[:-1]), dist,
                flat)


class VQGAN(nn.Module):
    """Image ``[N, H, W, 3]`` in [-1, 1] -> its reconstruction through
    encoder, nearest codes and decoder. ``train`` changes nothing in the
    arithmetic (no dropout, no batch statistic); the train step makes the
    collection ``vq`` mutable and reads the side outputs from it."""

    ch: int = 128
    ch_mult: Tuple[int, ...] = (1, 1, 2, 2, 4)
    res_blocks: int = 2
    codes: int = 16384
    embed_dim: int = 256
    out_channels: int = 3
    distance_dtype: jnp.dtype = jnp.float32
    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, x, train: bool = True):
        del train
        down = 2 ** (len(self.ch_mult) - 1)
        if x.shape[1] % down or x.shape[2] % down:
            raise ValueError(f"VQGAN needs H and W divisible by {down}, "
                             f"got {x.shape}")
        if self.dtype is not None:
            x = x.astype(self.dtype)
        h = Encoder(self.ch, self.ch_mult, self.res_blocks, self.embed_dim,
                    self.dtype, name="encoder")(x)
        with jax.named_scope("vq"):
            z = _conv(self.embed_dim, 1, "quant_conv", self.dtype)(h)
            zq, loss, idx, dist, flat = VectorQuantizer(
                self.codes, self.embed_dim, self.distance_dtype,
                name="quantize")(z)
            keep = dict(reduce_fn=lambda _, new: new, init_fn=lambda: None)
            self.sow("vq", "codebook_loss", loss, **keep)
            self.sow("vq", "indices", idx, **keep)
            self.sow("vq", "distances", dist, **keep)
            self.sow("vq", "latent", flat, **keep)
            zq = _conv(self.embed_dim, 1, "post_quant_conv", self.dtype)(zq)
        return Decoder(self.ch, self.ch_mult, self.res_blocks,
                       self.out_channels, self.dtype, name="decoder")(zq)


def side_outputs(vq_collection) -> Dict[str, jax.Array]:
    """The train step's view of the ``vq`` collection after a forward:
    ``codebook_loss``, ``indices``, ``distances``, ``latent`` (the rows
    the distances were taken on) and the decoder's ``last_input``, by
    name."""
    out = dict(vq_collection)
    out["last_input"] = out.pop("decoder")["last_input"]
    return out


def code_usage(indices: jax.Array, codes: int) -> Tuple[jax.Array, jax.Array]:
    """Distinct codes among ``indices`` and their perplexity ``exp(-sum p
    log p)``, p the share of each code in the batch (float32 scalars)."""
    counts = jnp.zeros((codes,), jnp.float32).at[indices.reshape(-1)].add(1.0)
    p = counts / indices.size
    used = jnp.sum(counts > 0).astype(jnp.float32)
    return used, jnp.exp(-jnp.sum(p * jnp.log(p + 1e-10)))


def vqgan_arithmetic(ch: int, ch_mult: Tuple[int, ...], res_blocks: int,
                     codes: int, embed_dim: int, h: int,
                     w: int) -> Dict[str, float]:
    """The autoencoder's forward arithmetic for one ``h`` x ``w`` image
    from its shapes (2 x multiply-adds), by part, in GFLOP, with the
    number of GN + swish sites, of attention blocks and of the quantizer's
    rows. Attention's share is its two products and four 1x1 convolutions;
    the quantizer's the distance product and its two 1x1 convolutions."""
    conv = lambda px, k, ci, co: 2.0 * px * k * k * ci * co  # noqa: E731
    parts = {"encoder": 0.0, "decoder": 0.0, "attention": 0.0}
    sites = {"gn_swish": 0, "attn": 0}

    def res(part, px, cin, cout):
        parts[part] += conv(px, 3, cin, cout) + conv(px, 3, cout, cout)
        if cin != cout:
            parts[part] += conv(px, 1, cin, cout)
        sites["gn_swish"] += 2

    def attn(px, c):
        parts["attention"] += 4 * conv(px, 1, c, c) + 2 * 2.0 * px * px * c
        sites["attn"] += 1

    def level(part, eh, ew, cin, cout, blocks):
        for _ in range(blocks):
            res(part, eh * ew, cin, cout)
            cin = cout
            if eh == ATTN_EXTENT:
                attn(eh * ew, cout)

    def middle(part, px, c):
        res(part, px, c, c), attn(px, c), res(part, px, c, c)

    eh, ew, c = h, w, ch
    parts["encoder"] += conv(eh * ew, 3, 3, ch)
    for i, mult in enumerate(ch_mult):
        level("encoder", eh, ew, c, ch * mult, res_blocks)
        c = ch * mult
        if i != len(ch_mult) - 1:
            eh, ew = eh // 2, ew // 2
            parts["encoder"] += conv(eh * ew, 3, c, c)
    middle("encoder", eh * ew, c)
    parts["encoder"] += conv(eh * ew, 3, c, embed_dim)
    sites["gn_swish"] += 1
    rows = eh * ew
    parts["quantizer"] = (2 * conv(rows, 1, embed_dim, embed_dim)
                          + 2.0 * rows * embed_dim * codes)
    parts["decoder"] += conv(rows, 3, embed_dim, c)
    middle("decoder", rows, c)
    for i in reversed(range(len(ch_mult))):
        level("decoder", eh, ew, c, ch * ch_mult[i], res_blocks + 1)
        c = ch * ch_mult[i]
        if i != 0:
            eh, ew = eh * 2, ew * 2
            parts["decoder"] += conv(eh * ew, 3, c, c)
    parts["decoder"] += conv(eh * ew, 3, c, 3)
    sites["gn_swish"] += 1
    out = {f"vqgan_{k}_gflop_per_image": v / 1e9 for k, v in parts.items()}
    out["generator_gflop_per_image"] = sum(parts.values()) / 1e9
    out["vqgan_gn_swish_sites"] = float(sites["gn_swish"])
    out["vqgan_attn_blocks"] = float(sites["attn"])
    out["vqgan_code_rows_per_image"] = float(rows)
    return out
