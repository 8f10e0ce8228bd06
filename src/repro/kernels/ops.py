"""jit'd public wrappers around the Pallas kernels.

These handle layout (coords -> flat 4-neighbour indices + Eq.5
coefficients), padding to MXU-aligned block multiples, and batching
(vmap adds the batch grid dimension to the pallas_call), so callers see
plain NHWC tensors. Oracles in ``repro.kernels.ref``; XLA fallbacks in
``repro.core.deform``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.deform import (DeformableConvParams, bli_coefficients,
                               conv2d, offsets_to_coords)
from repro.kernels.dcn_bli import bli_tile_matmul
from repro.kernels.dcn_fused import dcn_fused_tile


def round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def resolve_interpret(flag: bool | None) -> bool:
    """None = auto-detect: Pallas interpret mode only off-accelerator, so
    GPU/TPU runs compile the kernels without a config change."""
    if flag is None:
        return jax.default_backend() == "cpu"
    return bool(flag)


def coords_to_idx_coeff(coords: jax.Array, h: int, w: int):
    """(..., 2) float coords -> flat 4-neighbour idx + coeffs (..., 4).

    Neighbour order (r0,c0) (r0,c1) (r1,c0) (r1,c1) matches Eq. 5
    (eta, theta, mu, gamma) as produced by ``bli_coefficients``.
    """
    floor_rc, coeffs = bli_coefficients(coords)
    r0 = jnp.clip(floor_rc[..., 0], 0, h - 1)
    c0 = jnp.clip(floor_rc[..., 1], 0, w - 1)
    r1 = jnp.clip(r0 + 1, 0, h - 1)
    c1 = jnp.clip(c0 + 1, 0, w - 1)
    idx = jnp.stack([r0 * w + c0, r0 * w + c1, r1 * w + c0, r1 * w + c1],
                    axis=-1).astype(jnp.int32)
    return idx, coeffs.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def bli_pallas(x: jax.Array, coords: jax.Array, *,
               interpret: bool | None = None) -> jax.Array:
    """Stage 2 (Eq. 2) via the MXU 4-hot matmul kernel.

    x: (N, H, W, C); coords: (N, H, W, KK, 2) -> (N, H, W, KK, C).
    """
    n, h, w, c = x.shape
    kk = coords.shape[3]
    idx, coeff = coords_to_idx_coeff(coords, h, w)

    p = h * w * kk
    p_pad = round_up(p, 128)
    c_pad = round_up(c, 128)

    x_flat = x.reshape(n, h * w, c)
    if c_pad != c:
        x_flat = jnp.pad(x_flat, ((0, 0), (0, 0), (0, c_pad - c)))
    idx_f = idx.reshape(n, p, 4)
    coeff_f = coeff.reshape(n, p, 4)
    if p_pad != p:
        idx_f = jnp.pad(idx_f, ((0, 0), (0, p_pad - p), (0, 0)))
        coeff_f = jnp.pad(coeff_f, ((0, 0), (0, p_pad - p), (0, 0)))

    fn = functools.partial(bli_tile_matmul,
                           interpret=resolve_interpret(interpret))
    out = jax.vmap(fn)(x_flat, idx_f, coeff_f)          # (N, P_pad, C_pad)
    return out[:, :p, :c].reshape(n, h, w, kk, c)


@functools.partial(jax.jit,
                   static_argnames=("kernel_size", "variant",
                                    "max_displacement", "interpret"))
def deformable_conv2d_pallas(
    x: jax.Array,
    params: DeformableConvParams,
    *,
    kernel_size: int = 3,
    variant: str = "dcn2",
    max_displacement: float | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Full deformable conv: XLA stage-1 conv + fused Pallas stages 2+3.

    The fused kernel is invoked per (scheduled) tile on hardware; on the
    validation path the whole plane is one tile (S = H*W), which exercises
    the identical kernel dataflow.
    """
    n, h, w, c = x.shape
    o = params.w.shape[-1]
    kk = kernel_size * kernel_size

    offsets = conv2d(x, params.w_off, params.b_off)                  # Eq. 1
    coords = offsets_to_coords(offsets.astype(jnp.float32),
                               kernel_size, variant, max_displacement)
    idx, coeff = coords_to_idx_coeff(coords, h, w)       # (N,H,W,KK,4)

    p = h * w
    p_pad = round_up(p, 128)
    idx_f = idx.reshape(n, p, kk, 4)
    coeff_f = coeff.reshape(n, p, kk, 4)
    if p_pad != p:
        idx_f = jnp.pad(idx_f, ((0, 0), (0, p_pad - p), (0, 0), (0, 0)))
        coeff_f = jnp.pad(coeff_f, ((0, 0), (0, p_pad - p), (0, 0), (0, 0)))

    x_flat = x.reshape(n, p, c)
    w2 = params.w.reshape(kk, c, o)

    fn = functools.partial(dcn_fused_tile, kernel_size=kernel_size,
                           interpret=resolve_interpret(interpret))
    out = jax.vmap(fn, in_axes=(0, 0, 0, None, None))(
        x_flat, idx_f, coeff_f, w2, params.b)            # (N,P_pad,O)
    return out[:, :p].reshape(n, h, w, o)
