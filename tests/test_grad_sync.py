"""Gradient-pytree synchronization: every strategy/lowering/mode must equal
the mean-of-per-rank-gradients oracle, over arbitrary pytrees (hypothesis)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from _hyp import given, settings, strategies as st
from jax import shard_map

from repro.core.grad_sync import GradSyncConfig, sync_tree
from repro.core.topology import TorusGrid

pytestmark = pytest.mark.multidevice

MESH = None


def get_mesh():
    global MESH
    if MESH is None:
        MESH = jax.make_mesh((2, 4), ("dy", "dx"))
    return MESH


GRID = TorusGrid(h_axes=("dx",), v_axes=("dy",))
WORLD = 8


def run_sync(tree_per_rank, cfg):
    """tree_per_rank: pytree whose leaves have leading dim WORLD."""
    mesh = get_mesh()
    spec = P(("dy", "dx"))

    @functools.partial(shard_map, mesh=mesh,
                       in_specs=spec, out_specs=spec, check_vma=False)
    def f(tree):
        local = jax.tree.map(lambda x: x[0], tree)
        out = sync_tree(local, GRID, cfg)
        return jax.tree.map(lambda x: x[None], out)

    return jax.jit(f)(tree_per_rank)


def oracle(tree_per_rank, mean=True):
    def red(x):
        s = np.asarray(x, np.float32).sum(0)
        return s / WORLD if mean else s
    return jax.tree.map(red, tree_per_rank)


def make_tree(rng):
    return {
        "dense": {"kernel": rng.randn(WORLD, 40, 7).astype(np.float32),
                  "bias": rng.randn(WORLD, 7).astype(np.float32)},
        "bn": {"scale": rng.randn(WORLD, 5).astype(np.float32)},
        "emb": rng.randn(WORLD, 33).astype(np.float32),
    }


def assert_replicated_close(out, want, rtol=1e-5, atol=1e-5):
    """Every rank of `out` holds the reduced value `want` (broadcast check)."""
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.broadcast_to(b, np.asarray(a).shape),
            rtol=rtol, atol=atol),
        out, want)


@pytest.mark.parametrize("strategy", ["psum", "ring", "hierarchical", "torus2d"])
@pytest.mark.parametrize("fuse", [True, False])
def test_sync_matches_mean_oracle(strategy, fuse):
    rng = np.random.RandomState(0)
    tree = make_tree(rng)
    cfg = GradSyncConfig(strategy=strategy, fuse=fuse, comm_dtype=jnp.float32)
    out = run_sync(jax.tree.map(jnp.asarray, tree), cfg)
    assert_replicated_close(out, oracle(tree))


@pytest.mark.parametrize("lowering", ["xla", "ring"])
def test_sync_ring_lowering(lowering):
    rng = np.random.RandomState(1)
    tree = make_tree(rng)
    cfg = GradSyncConfig(strategy="torus2d", lowering=lowering, fuse=True,
                         comm_dtype=jnp.float32)
    out = run_sync(jax.tree.map(jnp.asarray, tree), cfg)
    assert_replicated_close(out, oracle(tree))


def test_bf16_comm_close_to_fp32_oracle():
    rng = np.random.RandomState(2)
    tree = make_tree(rng)
    cfg = GradSyncConfig(strategy="torus2d", fuse=True, comm_dtype=jnp.bfloat16)
    out = run_sync(jax.tree.map(jnp.asarray, tree), cfg)
    want = oracle(tree)
    # bn/bias/scale go through the fp32 group -> exact; dense kernel is bf16
    assert_replicated_close(out["bn"]["scale"], want["bn"]["scale"])
    assert_replicated_close(out["dense"]["kernel"], want["dense"]["kernel"],
                            rtol=5e-2, atol=5e-2)


@settings(max_examples=20, deadline=None)
@given(
    shapes=st.lists(
        st.lists(st.integers(1, 9), min_size=0, max_size=3), min_size=1, max_size=5),
    strategy=st.sampled_from(["ring", "hierarchical", "torus2d"]),
    fuse=st.booleans(),
    seed=st.integers(0, 2**31 - 1),
)
def test_property_arbitrary_pytrees(shapes, strategy, fuse, seed):
    """Any collection of leaf shapes (incl. scalars, odd sizes) syncs to the
    exact mean on every rank."""
    rng = np.random.RandomState(seed)
    tree = {f"w{i}": rng.randn(WORLD, *s).astype(np.float32)
            for i, s in enumerate(shapes)}
    cfg = GradSyncConfig(strategy=strategy, fuse=fuse, comm_dtype=jnp.float32)
    out = run_sync(jax.tree.map(jnp.asarray, tree), cfg)
    assert_replicated_close(out, oracle(tree), rtol=1e-4)


def test_sum_mode():
    rng = np.random.RandomState(3)
    tree = {"w": rng.randn(WORLD, 16).astype(np.float32)}
    cfg = GradSyncConfig(strategy="torus2d", mean=False, comm_dtype=jnp.float32)
    out = run_sync(jax.tree.map(jnp.asarray, tree), cfg)
    assert_replicated_close(out["w"], tree["w"].sum(0))
