"""Data pipeline (synthetic + augmentations) and serving-path tests."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, strategies as st

from repro.data import augment
from repro.data.synthetic import SyntheticImageNet, SyntheticTokens
from repro.serve.decode import RequestBatcher


# ------------------------------------------------------------- synthetic --

def test_imagenet_batches_deterministic():
    data = SyntheticImageNet(num_classes=10, image_size=32)
    a1, l1 = data.batch(3, 4)
    a2, l2 = data.batch(3, 4)
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))
    b1, _ = data.batch(4, 4)
    assert not np.allclose(np.asarray(a1), np.asarray(b1))


def test_imagenet_class_signal_exists():
    """Same-class samples are closer than cross-class (learnable)."""
    data = SyntheticImageNet(num_classes=4, image_size=32, noise=0.2)
    imgs, labels = data.batch(0, 64)
    imgs, labels = np.asarray(imgs), np.asarray(labels)
    centroids = np.stack([imgs[labels == c].mean(0) for c in range(4)
                          if (labels == c).any()])
    within = np.mean([np.linalg.norm(imgs[i] - centroids[labels[i]])
                      for i in range(len(imgs)) if labels[i] < len(centroids)])
    across = np.mean([np.linalg.norm(imgs[i] - centroids[(labels[i] + 1) %
                                                         len(centroids)])
                      for i in range(len(imgs)) if labels[i] < len(centroids)])
    assert within < across


def test_token_stream_learnable_structure():
    data = SyntheticTokens(vocab=1000)
    toks, labels = data.batch(0, 8, 64)
    assert toks.shape == (8, 64) and labels.shape == (8, 64)
    # the deterministic rule next = (prev*7+11) % V appears ~50% of the time
    det = (np.asarray(toks) * 7 + 11) % 1000
    match = (det[:, :-1] == np.asarray(toks)[:, 1:]).mean()
    assert 0.3 < match < 0.7, match


# ----------------------------------------------------------- augmentation --

def test_augment_shapes_and_finite():
    key = jax.random.key(0)
    imgs = jax.random.normal(jax.random.key(1), (4, 48, 48, 3))
    out = augment.augment(key, imgs, out_hw=(32, 32))
    assert out.shape == (4, 32, 32, 3)
    assert np.isfinite(np.asarray(out)).all()


def test_flip_is_exact_mirror():
    key = jax.random.key(0)
    imgs = jnp.arange(2 * 4 * 4 * 1, dtype=jnp.float32).reshape(2, 4, 4, 1)
    out = augment.random_flip(key, imgs)
    for b in range(2):
        ob, ib = np.asarray(out[b]), np.asarray(imgs[b])
        assert np.array_equal(ob, ib) or np.array_equal(ob, ib[:, ::-1])


def test_identity_affine_preserves_image():
    imgs = jax.random.normal(jax.random.key(2), (1, 16, 16, 3))
    out = augment.random_affine(jax.random.key(3), imgs, max_rot=0.0,
                                scale=(1.0, 1.0), max_shift=0.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(imgs),
                               rtol=1e-4, atol=1e-4)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 999))
def test_augment_property_bounded_output(seed):
    imgs = jnp.clip(jax.random.normal(jax.random.key(seed), (2, 16, 16, 3)), -3, 3)
    out = augment.augment(jax.random.key(seed + 1), imgs, out_hw=(16, 16))
    assert np.abs(np.asarray(out)).max() < 50


def _four_gather_resample(images, mats, out_hw):
    """The resample as four per-corner gathers of a C-wide row: the plain
    reference for ``augment._affine_resample``."""
    B, H, W, C = images.shape
    oh, ow = out_hw
    ys, xs = jnp.meshgrid(jnp.arange(oh, dtype=jnp.float32),
                          jnp.arange(ow, dtype=jnp.float32), indexing="ij")
    grid = jnp.stack([ys.ravel(), xs.ravel(), jnp.ones(oh * ow)], 0)
    src = jnp.einsum("bij,jp->bip", mats, grid)
    sy, sx = src[:, 0], src[:, 1]
    y0 = jnp.floor(sy)
    x0 = jnp.floor(sx)
    wy = sy - y0
    wx = sx - x0

    def gather(yi, xi):
        yc = jnp.clip(yi.astype(jnp.int32), 0, H - 1)
        xc = jnp.clip(xi.astype(jnp.int32), 0, W - 1)
        flat = images.reshape(B, H * W, C)
        return jnp.take_along_axis(flat, (yc * W + xc)[..., None], axis=1)

    out = (gather(y0, x0) * ((1 - wy) * (1 - wx))[..., None]
           + gather(y0, x0 + 1) * ((1 - wy) * wx)[..., None]
           + gather(y0 + 1, x0) * (wy * (1 - wx))[..., None]
           + gather(y0 + 1, x0 + 1) * (wy * wx)[..., None])
    return out.reshape(B, oh, ow, C)


def _affine(hw, out_hw, rot=0.0, scale=1.0, shift=(0.0, 0.0)):
    """One (2, 3) map as ``random_affine`` builds it: rotate by ``rot``
    degrees and scale about the centres, then shift by ``shift`` pixels."""
    (H, W), (oh, ow) = hw, out_hw
    a = np.deg2rad(rot)
    cos, sin = np.cos(a) / scale, np.sin(a) / scale
    cy, cx, ocy, ocx = (H - 1) / 2, (W - 1) / 2, (oh - 1) / 2, (ow - 1) / 2
    return np.array([[cos, -sin, cy - cos * ocy + sin * ocx + shift[0]],
                     [sin, cos, cx - sin * ocy - cos * ocx + shift[1]]],
                    np.float32)


# (input H, W), out_hw, one map per image of the batch
_RESAMPLE_CASES = {
    "square": ((16, 16), (16, 16), [_affine((16, 16), (16, 16), 10, 1.1),
                                    _affine((16, 16), (16, 16), -7, 0.8,
                                            (1.3, -2.6))]),
    "non_square": ((12, 20), (12, 20), [_affine((12, 20), (12, 20), 12, 0.9),
                                        _affine((12, 20), (12, 20), -3, 1.2,
                                                (0.5, 2.25))]),
    "resize": ((20, 14), (9, 23), [_affine((20, 14), (9, 23), 5, 1.0),
                                   _affine((20, 14), (9, 23), -15, 1.3)]),
    "integer_sources": ((10, 13), (10, 13), [_affine((10, 13), (10, 13)),
                                             _affine((10, 13), (10, 13),
                                                     shift=(3.0, -2.0))]),
    # rows and columns -1, 0, ..., H-1, H (and W likewise) exactly, with
    # and without a fractional part
    "edge_sources": ((7, 9), (9, 11), [
        np.array([[1, 0, -1], [0, 1, -1]], np.float32),
        np.array([[1, 0, -1.25], [0, 1, -0.75]], np.float32)]),
    "far_outside": ((16, 12), (16, 12), [
        _affine((16, 12), (16, 12), 15, 0.7, (200.0, -150.0)),
        _affine((16, 12), (16, 12), -15, 1.3, (-90.0, 75.5))]),
    "far_outside_negative": ((16, 12), (16, 12), [
        _affine((16, 12), (16, 12), -15, 0.7, (-300.0, -40.0)),
        _affine((16, 12), (16, 12), 15, 1.3, (41.0, 500.0))]),
}


@pytest.mark.parametrize("per_gather", ["batch", "image"])
@pytest.mark.parametrize("case", sorted(_RESAMPLE_CASES))
def test_resample_matches_four_gather_reference(case, per_gather,
                                                 monkeypatch):
    """The 2x2-table resample gives, bit for bit, what gathering each
    corner on its own gives: the same clamped corners, weights and sum,
    whether one gather reads the whole batch's table or one image's."""
    if per_gather == "image":
        monkeypatch.setattr(augment, "GATHER_TABLE_BYTES", 0)
    hw, out_hw, mats = _RESAMPLE_CASES[case]
    imgs = jax.random.normal(jax.random.key(5), (len(mats), *hw, 3))
    mats = jnp.asarray(np.stack(mats))
    want = jax.jit(_four_gather_resample, static_argnums=2)(imgs, mats, out_hw)
    got = jax.jit(augment._affine_resample, static_argnums=2)(imgs, mats,
                                                              out_hw)
    assert got.shape == (len(mats), *out_hw, 3)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_resample_in_groups_of_images_matches_reference(monkeypatch):
    """Six images gathered two at a time, each with a map of its own."""
    hw = (11, 14)
    per_image = (hw[0] + 1) * (hw[1] + 1) * 4 * 3 * 4
    monkeypatch.setattr(augment, "GATHER_TABLE_BYTES", 2 * per_image)
    assert augment.images_per_gather((6, *hw, 3)) == 2
    mats = np.stack([_affine(hw, hw, r, s, (d, -d)) for r, s, d in
                     [(-15, 0.7, 0), (-9, 0.9, 1.5), (-3, 1.1, -40),
                      (3, 1.3, 2.25), (9, 1.0, 0.5), (15, 0.8, 30)]])
    imgs = jax.random.normal(jax.random.key(6), (6, *hw, 3))
    want = jax.jit(_four_gather_resample, static_argnums=2)(imgs, mats, hw)
    got = jax.jit(augment._affine_resample, static_argnums=2)(imgs, mats, hw)
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("shape,want", [((256, 224, 224, 3), 16),
                                        ((128, 224, 224, 3), 16),
                                        ((24, 224, 224, 3), 12),
                                        ((97, 224, 224, 3), 1),
                                        ((2, 16, 16, 3), 2),
                                        ((1, 4000, 4000, 3), 1)])
def test_images_per_gather(shape, want):
    """The largest divisor of the batch whose table fits the budget, and
    never fewer than one image."""
    assert augment.images_per_gather(shape) == want


def test_augment_makes_one_gather_of_four_corners():
    """The compiled augmentation gathers once, a 2x2 neighbourhood (4C
    values) per output pixel, not once per corner."""
    C = 3
    hlo = jax.jit(augment.augment, static_argnums=2).lower(
        jax.random.key(0), jnp.zeros((2, 16, 16, C)), (16, 16)
    ).compile().as_text()
    gathers = [line for line in hlo.splitlines() if " gather(" in line]
    assert len(gathers) == 1, gathers
    sizes = re.search(r"slice_sizes=\{([\d,]+)\}", gathers[0]).group(1)
    assert np.prod([int(v) for v in sizes.split(",")]) == 4 * C


# ------------------------------------------------------ the job's batches --

def _parent_batch(data, index, gb):
    """The ResNet job's batch as one formula on one device: synthetic
    batch ``index``, then the augmentation keyed by the same index."""
    images, labels = data.batch(index, gb)
    key = jax.random.fold_in(jax.random.key(0), index)
    return augment.augment(key, images, out_hw=images.shape[1:3]), labels


@pytest.mark.multidevice
@pytest.mark.parametrize("n_devices", [1, 4], ids=["1x1", "2x2"])
def test_resnet_batch_is_laid_out_as_the_step_takes_it(n_devices):
    """``resnet_job``'s ``data_fn`` returns images and labels split by rows
    over the mesh exactly as the step splits its batch, each device holding
    ``gb / n`` rows, and the rows are the one-device formula's global rows
    bit for bit: the layout changes no value."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.train import device_mesh, resnet_job
    from repro.models import resnet

    cfg = resnet.ResNetConfig.tiny(image_size=16)
    mesh = device_mesh(jax.devices()[:n_devices])
    trainer, _, data_fn = resnet_job(cfg, per_chip_batches=(2,), mesh=mesh)
    gb, index = 8, 2_000_000_123          # as large as a seed's offset
    batch = data_fn(index, gb)

    rows = NamedSharding(mesh, P(trainer.dp_axes))
    for x in batch:
        assert x.sharding.is_equivalent_to(rows, x.ndim), x.sharding
        assert len(x.sharding.device_set) == n_devices
        assert [s.data.shape[0] for s in x.addressable_shards] == \
            [gb // n_devices] * n_devices
    data = SyntheticImageNet(num_classes=cfg.num_classes,
                             image_size=cfg.image_size)
    want = jax.jit(_parent_batch, static_argnums=(0, 2))(data, index, gb)
    for got, ref in zip(batch, want):
        assert np.array_equal(np.asarray(got), np.asarray(ref))
    # against the synthetic batch made op by op: the labels bitwise; the
    # images within 8 ulps of the batch's largest value. XLA's CPU backend
    # computes the compiled ``up + noise * normal`` as one fused
    # multiply-add where op-by-op execution rounds twice: 1 ulp per pixel,
    # which the augmentation's mixes and scales carry on (at most 3 ulps
    # over ten batches); a wrong row or key differs by the data's scale
    images, labels = data.batch(index, gb)
    key = jax.random.fold_in(jax.random.key(0), index)
    eager = jax.jit(augment.augment, static_argnums=2)(key, images,
                                                       images.shape[1:3])
    assert np.array_equal(np.asarray(batch[1]), np.asarray(labels))
    top = np.abs(np.asarray(eager)).max()
    np.testing.assert_allclose(np.asarray(batch[0]), np.asarray(eager),
                               rtol=0, atol=8 * np.spacing(top))


# ---------------------------------------------------------------- batcher --

def test_batcher_left_pad_and_truncate():
    b = RequestBatcher(batch_size=2, seq_len=4, pad_id=9)
    prompts, lens, n = b.pack([[1, 2], [1, 2, 3, 4, 5, 6]])
    assert n == 2
    np.testing.assert_array_equal(np.asarray(prompts[0]), [9, 9, 1, 2])
    np.testing.assert_array_equal(np.asarray(prompts[1]), [3, 4, 5, 6])


def test_batcher_rejects_overflow():
    b = RequestBatcher(batch_size=1, seq_len=4)
    with pytest.raises(ValueError):
        b.pack([[1], [2]])
