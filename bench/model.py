"""The paper's DCN benchmark nets, written from their equations.

arXiv:2107.02547 §V-A: VGG19 and SegNet (a VGG19 encoder mirrored by a
decoder) with the last ``n_deform`` 3x3 convolutions made deformable.
This module is the benchmark's yardstick for them and imports nothing of
the program under test:

* :func:`layers` walks a configuration into its convolutions, with the
  plane each one runs at (FLOP counts and the reference share it);
* :func:`build_weights` makes every weight on the device from a seed in
  one jitted program, re-scaling each deformable layer's offset conv so
  its sampling offsets average ``offset_px`` pixels on a probe image;
* :func:`reference` is the plain forward pass, one image at a time.

Deformable convolution (DCN-II; DCN-I shares one offset pair over the
K*K taps): stage 1 is a 3x3 conv giving (row, col) offsets per tap;
the tap's sampling point, the pixel plus the tap's place in the 3x3
window plus its offset, is clamped to the plane; stage 2 samples the
input there by bilinear interpolation (four neighbours, Eq. 2 and 5);
stage 3 contracts the K*K samples with the layer's weights. Every layer
is followed by ReLU, every encoder stage by a 2x2 max pool and each
decoder stage by a 2x nearest-neighbour upsample; VGG19 ends in a global
mean and a dense layer, SegNet in a 1x1 conv to per-pixel classes.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

VGG19_STAGES = ((64, 2), (128, 2), (256, 4), (512, 4), (512, 4))
KERNEL = 3


@dataclasses.dataclass(frozen=True)
class Net:
    """One network as a configuration file states it."""

    arch: str                 # "vgg19" | "segnet"
    n_deform: int             # last n convs deformable (-1: all)
    variant: str              # "dcn1" | "dcn2"
    img_size: int
    num_classes: int
    in_channels: int = 3
    width_mult: float = 1.0   # 1.0 in every cell; tests shrink it
    offset_px: float = 2.0    # mean |offset| each deformable layer is set to

    @classmethod
    def from_config(cls, cfg: dict) -> "Net":
        m = cfg["model"]
        return cls(arch=m["arch"], n_deform=m["n_deform"],
                   variant=m["variant"], img_size=m["img_size"],
                   num_classes=m["num_classes"],
                   in_channels=m.get("in_channels", 3),
                   width_mult=m.get("width_mult", 1.0),
                   offset_px=cfg["offset_px"])

    @property
    def offset_channels(self) -> int:
        return 2 if self.variant == "dcn1" else 2 * KERNEL * KERNEL


class Layer(NamedTuple):
    c_in: int
    c_out: int
    deform: bool
    hw: int                   # square plane side this conv runs at
    after: str                # "pool" | "upsample" | ""


def layers(net: Net) -> list[Layer]:
    """The net's 3x3 convolutions in execution order."""
    chans = [(max(8, int(c * net.width_mult)), n) for c, n in VGG19_STAGES]
    convs, c_prev = [], net.in_channels
    for c, n in chans:
        for _ in range(n):
            convs.append((c_prev, c))
            c_prev = c
    n_enc = len(convs)
    if net.arch == "segnet":
        rev = convs[::-1]
        convs = convs + [(co, ci if i < n_enc - 1 else rev[-1][1])
                         for i, (ci, co) in enumerate(rev)]
    elif net.arch != "vgg19":
        raise ValueError(f"unknown arch {net.arch!r}")
    n_def = len(convs) if net.n_deform < 0 else net.n_deform
    stage_ends, at = set(), 0
    for _, n in chans:
        at += n
        stage_ends.add(at - 1)
    out, hw, pooled = [], net.img_size, set()
    for i, (ci, co) in enumerate(convs):
        after = ""
        if i < n_enc and i in stage_ends and hw >= 2:
            after = "pool"
            pooled.add(i)
        elif i >= n_enc and (2 * n_enc - 1 - i) in pooled:
            after = "upsample"
        out.append(Layer(ci, co, i >= len(convs) - n_def, hw, after))
        hw = hw // 2 if after == "pool" else hw * 2 if after else hw
    return out


# -- matrix products at a stated precision ----------------------------------


def _split_bf16(a):
    """a = hi + lo + (rounding), both parts bf16. The rounding is made
    explicit with ``reduce_precision``: a bare f32 -> bf16 -> f32 round
    trip may be elided by XLA (excess precision), which would leave
    ``lo`` zero and the product one bf16 pass."""
    hi = jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
    lo = jax.lax.reduce_precision(a - hi, exponent_bits=8, mantissa_bits=7)
    return hi.astype(jnp.bfloat16), lo.astype(jnp.bfloat16)


def _three_pass(f, a, b):
    """``f(a, b)`` as three bf16 products summed in f32 (XLA's ``high``
    precision for f32, written out so it reads the same on any backend)."""
    ah, al = _split_bf16(a)
    bh, bl = _split_bf16(b)
    return f(ah, bl) + f(al, bh) + f(ah, bh)


def _conv(x, w, precision: str):
    def f(a, b):
        return jax.lax.conv_general_dilated(
            a, b, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
    return f(x, w) if precision == "highest" else _three_pass(f, x, w)


def _einsum(spec: str, a, b, precision: str):
    def f(p, q):
        return jnp.einsum(spec, p, q, precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)
    return f(a, b) if precision == "highest" else _three_pass(f, a, b)


# -- layers -----------------------------------------------------------------


def sample_points(offsets, variant: str):
    """Stage-1 offsets (N, H, W, L) -> clamped (row, col) sampling points
    (N, H, W, K*K, 2)."""
    n, h, w, _ = offsets.shape
    kk = KERNEL * KERNEL
    d = jnp.arange(KERNEL, dtype=jnp.float32) - (KERNEL - 1) / 2
    taps = jnp.stack(jnp.meshgrid(d, d, indexing="ij"), -1).reshape(kk, 2)
    rows = jnp.arange(h, dtype=jnp.float32)[:, None]
    cols = jnp.arange(w, dtype=jnp.float32)[None, :]
    centre = jnp.stack(jnp.broadcast_arrays(rows, cols), -1)   # (H, W, 2)
    off = (offsets[..., None, :] if variant == "dcn1"
           else offsets.reshape(n, h, w, kk, 2))
    pts = centre[None, :, :, None] + taps + off
    return jnp.clip(pts, 0.0, jnp.array([h - 1, w - 1], jnp.float32))


def bilinear(x, pts):
    """Eq. 2: x (N, H, W, C) sampled at pts (N, H, W, KK, 2)."""
    n, h, w, c = x.shape
    r0f, c0f = jnp.floor(pts[..., 0]), jnp.floor(pts[..., 1])
    fr, fc = pts[..., 0] - r0f, pts[..., 1] - c0f
    r0 = r0f.astype(jnp.int32)
    c0 = c0f.astype(jnp.int32)
    r1 = jnp.minimum(r0 + 1, h - 1)
    c1 = jnp.minimum(c0 + 1, w - 1)
    flat = x.reshape(n, h * w, c)

    def at(r, cc):
        idx = (r * w + cc).reshape(n, -1, 1)
        return jnp.take_along_axis(flat, idx, axis=1).reshape(
            r.shape + (c,))

    return (at(r0, c0) * ((1 - fr) * (1 - fc))[..., None]
            + at(r0, c1) * ((1 - fr) * fc)[..., None]
            + at(r1, c0) * (fr * (1 - fc))[..., None]
            + at(r1, c1) * (fr * fc)[..., None])


def deform_conv(x, p, variant: str, precision: str):
    offsets = _conv(x, p["w_off"], precision) + p["b_off"]
    sampled = bilinear(x, sample_points(offsets, variant))
    kk = KERNEL * KERNEL
    w = p["w"].reshape(kk, x.shape[-1], p["w"].shape[-1])
    return _einsum("nhwkc,kco->nhwo", sampled, w, precision) + p["b"]


def _after(x, layer: Layer):
    if layer.after == "pool":
        return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                     (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
    if layer.after == "upsample":
        return jnp.repeat(jnp.repeat(x, 2, axis=1), 2, axis=2)
    return x


def _head(net: Net, params, x, precision: str):
    if net.arch == "vgg19":
        return _einsum("nc,ck->nk", x.mean(axis=(1, 2)), params["fc"]["w"],
                       precision) + params["fc"]["b"]
    return (_conv(x, params["seg_head"]["w"], precision)
            + params["seg_head"]["b"])


def forward(net: Net, params, x, precision: str = "highest"):
    """Logits of x (N, H, W, C): (N, classes) or (N, H, W, classes)."""
    for layer, p in zip(layers(net), params["convs"]):
        if layer.deform:
            x = deform_conv(x, p, net.variant, precision)
        else:
            x = _conv(x, p["w"], precision) + p["b"]
        x = _after(jax.nn.relu(x), layer)
    return _head(net, params, x, precision)


@functools.lru_cache(maxsize=None)
def _forward_jit(net: Net, precision: str):
    return jax.jit(functools.partial(forward, net, precision=precision))


def reference(net: Net, params, x, precision: str = "highest"):
    """The plain forward pass of one image batch, jitted per net."""
    return _forward_jit(net, precision)(params, x)


# -- weights ----------------------------------------------------------------


def _seeded(net: Net, key, probe):
    ls = layers(net)
    convs, offsets_px = [], []
    x = probe
    for i, layer in enumerate(ls):
        kw, kb, ko = jax.random.split(jax.random.fold_in(key, i), 3)
        ci, co = layer.c_in, layer.c_out
        p = {"w": jax.random.normal(kw, (KERNEL, KERNEL, ci, co))
             * jnp.sqrt(2.0 / (KERNEL * KERNEL * ci)),
             "b": 0.01 * jax.random.normal(kb, (co,))}
        if layer.deform:
            unit = jax.random.normal(
                ko, (KERNEL, KERNEL, ci, net.offset_channels))
            raw = jnp.abs(_conv(x, unit, "highest")).mean()
            p["w_off"] = unit * (net.offset_px / raw)
            p["b_off"] = jnp.zeros((net.offset_channels,))
            offsets_px.append(jnp.abs(_conv(x, p["w_off"], "highest")).mean())
            x = deform_conv(x, p, net.variant, "highest")
        else:
            x = _conv(x, p["w"], "highest") + p["b"]
        x = _after(jax.nn.relu(x), layer)
        convs.append(p)
    kh, khb = jax.random.split(jax.random.fold_in(key, 10_000))
    c_last = ls[-1].c_out
    params = {"convs": convs}
    if net.arch == "vgg19":
        params["fc"] = {
            "w": 0.02 * jax.random.normal(kh, (c_last, net.num_classes)),
            "b": 0.01 * jax.random.normal(khb, (net.num_classes,))}
    else:
        params["seg_head"] = {
            "w": 0.02 * jax.random.normal(kh, (1, 1, c_last,
                                               net.num_classes)),
            "b": 0.01 * jax.random.normal(khb, (net.num_classes,))}
    return params, jnp.stack(offsets_px)


@functools.lru_cache(maxsize=None)
def _seeded_jit(net: Net):
    return jax.jit(functools.partial(_seeded, net))


def build_weights(net: Net, seed: int):
    """(params, mean |offset| px per deformable layer), all on device.

    One jitted program draws the weights from ``seed`` and calibrates the
    offset convs on a probe image drawn from the same seed."""
    key = jax.random.PRNGKey(seed % (1 << 32))
    probe = jax.random.normal(jax.random.fold_in(key, 20_000),
                              (1, net.img_size, net.img_size,
                               net.in_channels))
    return _seeded_jit(net)(key, probe)


def forward_flops(net: Net) -> int:
    """Multiply-add FLOPs (2 per MAC) of one image's forward pass: every
    conv, each deformable layer's offset conv and bilinear sampling (4
    MACs per sample per channel, K*K samples per pixel), and the head."""
    kk = KERNEL * KERNEL
    total = 0
    for layer in layers(net):
        px = layer.hw * layer.hw
        total += 2 * px * kk * layer.c_in * layer.c_out
        if layer.deform:
            total += 2 * px * kk * layer.c_in * net.offset_channels
            total += 2 * 4 * px * kk * layer.c_in
    c_last = layers(net)[-1].c_out
    if net.arch == "vgg19":
        total += 2 * c_last * net.num_classes
    else:
        total += 2 * net.img_size * net.img_size * c_last * net.num_classes
    return total
