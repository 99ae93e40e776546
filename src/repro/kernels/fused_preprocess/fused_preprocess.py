"""Fused crop -> cast -> normalize kernel (TPU Pallas).

The device-side tail of the paper's data path: TQL projections like
``images[100:500, 100:500, :]`` followed by normalization (§4.3 Fig 4)
lower to ONE kernel that reads the uint8 crop window from HBM once and
writes normalized f32 — instead of XLA's slice + convert + sub + mul chain
(4 HBM round-trips of the full image).  Used by the data pipeline after
device_put of raw uint8 batches (halves H2D bytes vs shipping f32).

Grid (B,): one program per image; the BlockSpec block is the crop
window, cut by a slice in front of the kernel.  Each image is viewed as
(h, w*C) rows (a free reshape), so a block's last two dims are the whole
crop; mean/std come pre-tiled to one (1, w*C) row.  The v5e does not lower
a uint8 -> float32 cast, so pixels widen through int32.
"""

from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(img_ref, mean_ref, std_ref, out_ref):
    crop = img_ref[...].astype(jnp.int32).astype(jnp.float32) / 255.0
    out_ref[...] = (crop - mean_ref[...]) / std_ref[...]   # (h, w*C)


def fused_preprocess_fwd(images, crop: Tuple[int, int, int, int],
                         mean: Sequence[float], std: Sequence[float],
                         interpret: bool = False):
    """images (B,H,W,C) uint8; crop (y0, x0, h, w) -> (B,h,w,C) float32."""
    B, H, W, C = images.shape
    y0, x0, h, w = crop
    assert 0 <= y0 and y0 + h <= H and 0 <= x0 and x0 + w <= W, (crop, images.shape)
    mean_row = jnp.tile(jnp.asarray(mean, jnp.float32), w).reshape(1, w * C)
    std_row = jnp.tile(jnp.asarray(std, jnp.float32), w).reshape(1, w * C)
    # BlockSpecs index in block multiples, so general crop offsets are
    # taken by a slice in front of the kernel
    imgs = jax.lax.slice(images, (0, y0, x0, 0), (B, y0 + h, x0 + w, C))
    row_spec = pl.BlockSpec((1, w * C), lambda b: (0, 0))
    img_spec = pl.BlockSpec((None, h, w * C), lambda b: (b, 0, 0))
    out = pl.pallas_call(
        _kernel,
        grid=(B,),
        in_specs=[img_spec, row_spec, row_spec],
        out_specs=img_spec,
        out_shape=jax.ShapeDtypeStruct((B, h, w * C), jnp.float32),
        interpret=interpret,
    )(imgs.reshape(B, h, w * C), mean_row, std_row)
    return out.reshape(B, h, w, C)
