"""Tile-perforated Harris corner response — Pallas TPU kernel.

The paper's second application at TPU grain: the image lives in VMEM (a
128x128 tile set easily fits), the grid walks output tiles, and a
prefetched keep mask drops whole tiles — dropped tiles write zero response
and skip the gradient/structure-tensor arithmetic entirely (the energy
saving is proportional to dropped tiles, as in Fig. 12's skipped loop
iterations).

The 3x3 Sobel + 5x5 Gaussian halo (3 px) is read from the full-image VMEM
ref with clamped dynamic slices, so tiles stay independent.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_HALO = 3  # 1 (sobel) + 2 (gaussian)


def _sep_conv(patch, k1d_a, k1d_b):
    """2-D conv via two 1-D passes with static shifts (small kernels)."""
    acc = jnp.zeros_like(patch)
    r = len(k1d_a) // 2
    for i, w in enumerate(k1d_a):
        if w != 0.0:
            acc += w * jnp.roll(patch, r - i, axis=0)
    out = jnp.zeros_like(patch)
    for i, w in enumerate(k1d_b):
        if w != 0.0:
            out += w * jnp.roll(acc, r - i, axis=1)
    return out


def _kernel(keep_ref, img_ref, o_ref, *, tile: int, k_harris: float,
            img_h: int, img_w: int):
    ti = pl.program_id(0)
    tj = pl.program_id(1)
    n_j = pl.num_programs(1)
    idx = ti * n_j + tj

    @pl.when(keep_ref[idx] == 0)
    def _skip():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(keep_ref[idx] > 0)
    def _compute():
        pad = _HALO
        ext = tile + 2 * pad
        y0 = jnp.clip(ti * tile - pad, 0, img_h - ext)
        x0 = jnp.clip(tj * tile - pad, 0, img_w - ext)
        patch = img_ref[pl.ds(y0, ext), pl.ds(x0, ext)]
        patch = patch.astype(jnp.float32)
        ix = _sep_conv(patch, (1 / 8, 2 / 8, 1 / 8), (-1.0, 0.0, 1.0))
        iy = _sep_conv(patch, (-1.0, 0.0, 1.0), (1 / 8, 2 / 8, 1 / 8))
        g = (1 / 16, 4 / 16, 6 / 16, 4 / 16, 1 / 16)
        sxx = _sep_conv(ix * ix, g, g)
        syy = _sep_conv(iy * iy, g, g)
        sxy = _sep_conv(ix * iy, g, g)
        resp = (sxx * syy - sxy * sxy) - k_harris * (sxx + syy) ** 2
        # slice the interior tile back out (account for edge clamping)
        oy = ti * tile - y0
        ox = tj * tile - x0
        o_ref[...] = jax.lax.dynamic_slice(resp, (oy, ox), (tile, tile))


@functools.partial(jax.jit, static_argnames=("tile", "k_harris",
                                             "interpret"))
def harris_pallas(img, tile_keep, *, tile: int = 16, k_harris: float = 0.05,
                  interpret: bool = False):
    """img: (H, W) fp32; tile_keep: (H//tile, W//tile) bool/int32.

    Returns the tile-perforated Harris response (H, W) fp32.
    NOTE: interior tiles match data.images.harris_response_perforated
    exactly; border tiles use clamped (replicated-window) halos instead of
    zero padding — the kernel's documented edge semantics.
    """
    H, W = img.shape
    assert H % tile == 0 and W % tile == 0
    n_i, n_j = H // tile, W // tile
    keep = tile_keep.reshape(-1).astype(jnp.int32)
    kernel = functools.partial(_kernel, tile=tile, k_harris=k_harris,
                               img_h=H, img_w=W)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_i, n_j),
        in_specs=[pl.BlockSpec(
            (H, W), lambda ti, tj, keep: (0, 0))],  # full image in VMEM
        out_specs=pl.BlockSpec((tile, tile),
                               lambda ti, tj, keep: (ti, tj)),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((H, W), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(keep, img)
