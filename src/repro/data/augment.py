"""Image augmentation ops in pure JAX (paper §3.2 lists NNL's pipeline:
padding, scaling, rotations, resizing, distortion, flipping, brightness
adjustment, contrast adjustment, and noising).

Every op is jit-able and batched (B, H, W, C), driven by a PRNG key, so the
input pipeline runs on-device and its cost is visible in the step profile.
Rotation/scaling/distortion are implemented as a single affine resample:
one gather per output pixel from a table of each pixel's 2x2 bilinear
neighbourhood -- one indexed pass for the geometric group.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def random_flip(key, images):
    flip = jax.random.bernoulli(key, 0.5, (images.shape[0],))
    return jnp.where(flip[:, None, None, None], images[:, :, ::-1], images)


def random_brightness(key, images, max_delta=0.2):
    d = jax.random.uniform(key, (images.shape[0], 1, 1, 1),
                           minval=-max_delta, maxval=max_delta)
    return images + d


def random_contrast(key, images, lower=0.8, upper=1.2):
    f = jax.random.uniform(key, (images.shape[0], 1, 1, 1),
                           minval=lower, maxval=upper)
    mean = images.mean(axis=(1, 2), keepdims=True)
    return (images - mean) * f + mean


def random_noise(key, images, std=0.02):
    return images + std * jax.random.normal(key, images.shape, images.dtype)


# The most bytes of neighbourhood table that one gather reads. The
# resample gathers from a group of images at a time, small enough for the
# TPU compiler to keep the group's table in on-chip vector memory: on a
# TPU v5e, gathering 16 images of 224 px at a time (39 MB of table) took
# about a quarter of the time of one gather over the whole batch from HBM.
GATHER_TABLE_BYTES = 48 << 20


def images_per_gather(images_shape) -> int:
    """The largest divisor of the batch whose table fits in
    ``GATHER_TABLE_BYTES`` (at least 1)."""
    B, H, W, C = images_shape
    per_image = (H + 1) * (W + 1) * 4 * C * 4       # 4 corners of C float32
    return max(k for k in range(1, B + 1) if B % k == 0
               and (k == 1 or k * per_image <= GATHER_TABLE_BYTES))


def _affine_resample(images, mats, out_hw):
    """Batched affine warp with bilinear sampling, clamped to the edge.

    mats: (B, 2, 3) mapping output pixel coords -> input coords.

    Each output pixel reads one row of a 2x2 neighbourhood table: the
    edge-padded image with each pixel's right, lower and lower-right
    neighbours concatenated on the channel axis, (B, H+1, W+1, 4C). One
    gather of 4C values per pixel replaces four gathers of C; clamping the
    top-left corner to [-1, H-1] x [-1, W-1] in image coordinates (one
    more in the padded table) clamps both of its rows and columns to the
    image as clamping each corner alone would. The gather runs over
    groups of ``images_per_gather`` images; coordinates, weights and the
    sum are computed for the whole batch.
    """
    B, H, W, C = images.shape
    oh, ow = out_hw
    ys, xs = jnp.meshgrid(jnp.arange(oh, dtype=jnp.float32),
                          jnp.arange(ow, dtype=jnp.float32), indexing="ij")
    grid = jnp.stack([ys.ravel(), xs.ravel(), jnp.ones(oh * ow)], 0)  # (3, P)
    src = jnp.einsum("bij,jp->bip", mats, grid)                        # (B,2,P)
    sy, sx = src[:, 0], src[:, 1]
    y0 = jnp.floor(sy)
    x0 = jnp.floor(sx)
    wy = sy - y0
    wx = sx - x0

    p = jnp.pad(images, ((0, 0), (1, 1), (1, 1), (0, 0)), mode="edge")
    table = jnp.concatenate([p[:, :-1, :-1], p[:, :-1, 1:],
                             p[:, 1:, :-1], p[:, 1:, 1:]], -1)
    yc = jnp.clip(y0.astype(jnp.int32), -1, H - 1) + 1
    xc = jnp.clip(x0.astype(jnp.int32), -1, W - 1) + 1
    idx = yc * (W + 1) + xc                                            # (B, P)
    k = images_per_gather(images.shape)
    quad = jax.lax.map(
        lambda ti: jnp.take_along_axis(ti[0], ti[1][..., None], axis=1),
        (table.reshape(B // k, k, (H + 1) * (W + 1), 4 * C),
         idx.reshape(B // k, k, oh * ow)))
    c00, c01, c10, c11 = jnp.split(quad.reshape(B, oh * ow, 4 * C), 4, -1)

    out = (c00 * ((1 - wy) * (1 - wx))[..., None]
           + c01 * ((1 - wy) * wx)[..., None]
           + c10 * (wy * (1 - wx))[..., None]
           + c11 * (wy * wx)[..., None])
    return out.reshape(B, oh, ow, C)


def random_affine(key, images, out_hw=None, max_rot=15.0, scale=(0.7, 1.3),
                  max_shift=0.1, mesh=None):
    """Rotation + scale + shift ('rotations, scaling, distortion, resizing')
    in one bilinear resample.

    With a ``mesh``, the batch's rows are split over all of its axes and
    each device resamples its own rows (``shard_map``): the grouped gather
    loops over the batch, and a loop over a split axis is not split by the
    partitioner. The maps are drawn for the whole batch either way."""
    B, H, W, _ = images.shape
    oh, ow = out_hw or (H, W)
    k1, k2, k3 = jax.random.split(key, 3)
    ang = jnp.deg2rad(jax.random.uniform(k1, (B,), minval=-max_rot,
                                         maxval=max_rot))
    sc = jax.random.uniform(k2, (B,), minval=scale[0], maxval=scale[1])
    shift = jax.random.uniform(k3, (B, 2), minval=-max_shift,
                               maxval=max_shift) * jnp.asarray([H, W])
    cos, sin = jnp.cos(ang) / sc, jnp.sin(ang) / sc
    cy, cx = (H - 1) / 2, (W - 1) / 2
    ocy, ocx = (oh - 1) / 2, (ow - 1) / 2
    # out (y,x) -> rotate/scale about center + shift
    m = jnp.stack([
        jnp.stack([cos, -sin, cy - cos * ocy + sin * ocx + shift[:, 0]], 1),
        jnp.stack([sin, cos, cx - sin * ocy - cos * ocx + shift[:, 1]], 1),
    ], 1)                                                              # (B,2,3)
    resample = functools.partial(_affine_resample, out_hw=(oh, ow))
    if mesh is not None:
        rows = P(tuple(mesh.axis_names))
        resample = jax.shard_map(resample, mesh=mesh, in_specs=(rows, rows),
                                 out_specs=rows)
    return resample(images, m)


def augment(key, images, out_hw=(224, 224), mesh=None):
    """The paper's full augmentation stack, fused order: geometric ->
    flip -> photometric -> noise. ``mesh``: see ``random_affine``."""
    k = jax.random.split(key, 5)
    x = random_affine(k[0], images, out_hw, mesh=mesh)
    x = random_flip(k[1], x)
    x = random_brightness(k[2], x)
    x = random_contrast(k[3], x)
    x = random_noise(k[4], x)
    return x
