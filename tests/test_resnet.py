"""ResNet-50 model: shapes, param count, SyncBN, zero-gamma init."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from jax import shard_map

from repro.models import resnet


def test_resnet50_param_count():
    cfg = resnet.ResNetConfig.resnet50()
    params = resnet.init(jax.random.key(0), cfg)
    n = resnet.num_params(params)
    # ResNet-50 ~= 25.6M params
    assert 25.0e6 < n < 26.2e6, n


def test_tiny_forward_shapes_and_finite():
    cfg = resnet.ResNetConfig.tiny()
    params = resnet.init(jax.random.key(0), cfg)
    x = jnp.ones((2, 32, 32, 3), jnp.float32)
    logits = resnet.apply(params, x, cfg)
    assert logits.shape == (2, 10)
    assert logits.dtype == jnp.float32
    assert np.isfinite(np.asarray(logits)).all()


def test_zero_gamma_makes_blocks_identity_at_init():
    """With bn3 gamma zero-init, each residual block is ~identity+relu at
    init -- output variance should stay bounded through depth."""
    cfg = resnet.ResNetConfig.tiny()
    params = resnet.init(jax.random.key(1), cfg)
    for stage in params["stages"]:
        for block in stage:
            np.testing.assert_array_equal(np.asarray(block["bn3"]["bn_scale"]), 0.0)


def test_collect_and_reuse_stats():
    cfg = resnet.ResNetConfig.tiny()
    params = resnet.init(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(2), (4, 32, 32, 3))
    logits_train, stats = resnet.apply(params, x, cfg, collect_stats=True)
    logits_eval = resnet.apply(params, x, cfg, stats=stats)
    # same batch + its own stats == train-mode output
    np.testing.assert_allclose(np.asarray(logits_train),
                               np.asarray(logits_eval), rtol=1e-3, atol=1e-3)


@pytest.mark.multidevice
def test_sync_bn_matches_global_batch():
    """SyncBN over the data axis == local BN over the concatenated batch."""
    mesh = jax.make_mesh((4,), ("data",))
    cfg = resnet.ResNetConfig.tiny(compute_dtype=jnp.float32)
    params = resnet.init(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(3), (8, 32, 32, 3))

    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(P(), P("data")), out_specs=P("data"),
                       check_vma=False)
    def sharded(params, xb):
        return resnet.apply(params, xb, cfg, dp_axes=("data",))

    got = np.asarray(jax.jit(sharded)(params, x))
    want = np.asarray(resnet.apply(params, x, cfg))
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)
