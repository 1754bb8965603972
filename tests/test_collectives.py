"""Correctness of the four all-reduce strategies x two lowerings.

Oracle: the sum of per-rank contributions (== lax.psum). Every strategy and
lowering must produce exactly the same mean/sum on every rank, for 1D and 2D
torus grids, odd shapes, and both dtypes.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from jax import shard_map

from repro.core import collectives
from repro.core.topology import TorusGrid, factorize, select_grid

pytestmark = pytest.mark.multidevice

STRATEGIES = ["psum", "ring", "hierarchical", "torus2d"]
LOWERINGS = ["xla", "ring"]


def make_mesh(shape, axes):
    return jax.make_mesh(shape, axes)


def run_allreduce(mesh, grid, strategy, lowering, per_rank):
    """per_rank: (world, chunk...) array; rank i contributes per_rank[i]."""
    spec = P(grid.axes)

    @functools.partial(
        shard_map, mesh=mesh, in_specs=spec, out_specs=spec, check_vma=False)
    def f(x):
        local = x[0]  # strip the sharded world dim -> this rank's tensor
        out = collectives.all_reduce(local, grid, strategy, lowering)
        return out[None]

    return np.asarray(jax.jit(f)(per_rank))


@pytest.mark.parametrize("strategy", STRATEGIES)
@pytest.mark.parametrize("lowering", LOWERINGS)
def test_allreduce_2d_grid_matches_sum(strategy, lowering):
    mesh = make_mesh((2, 4), ("dy", "dx"))
    grid = TorusGrid(h_axes=("dx",), v_axes=("dy",))
    world = 8
    rng = np.random.RandomState(0)
    data = rng.randn(world, 16, 3).astype(np.float32)  # dim0=16 divisible by 8
    out = run_allreduce(mesh, grid, strategy, lowering, jnp.asarray(data))
    want = data.sum(axis=0)
    for r in range(world):
        np.testing.assert_allclose(out[r], want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("strategy", STRATEGIES)
@pytest.mark.parametrize("lowering", LOWERINGS)
def test_allreduce_1d_grid(strategy, lowering):
    mesh = make_mesh((8,), ("data",))
    grid = select_grid(("data",))
    rng = np.random.RandomState(1)
    data = rng.randn(8, 24).astype(np.float32)
    out = run_allreduce(mesh, grid, strategy, lowering, jnp.asarray(data))
    for r in range(8):
        np.testing.assert_allclose(out[r], data.sum(0), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_allreduce_three_axis_multipod(strategy):
    """(pod, data) as vertical+horizontal: the multi-pod mapping."""
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    grid = select_grid(("pod", "data"))
    assert grid.h_axes == ("data",) and grid.v_axes == ("pod",)
    world = 4
    rng = np.random.RandomState(2)
    data = rng.randn(world, 8, 2).astype(np.float32)
    spec = P(("pod", "data"))

    @functools.partial(shard_map, mesh=mesh, in_specs=spec, out_specs=spec,
                       check_vma=False)
    def f(x):
        return collectives.all_reduce(x[0], grid, strategy, "xla")[None]

    out = np.asarray(jax.jit(f)(jnp.asarray(data)))
    for r in range(world):
        np.testing.assert_allclose(out[r], data.sum(0), rtol=1e-5, atol=1e-5)


def test_bf16_allreduce():
    mesh = make_mesh((2, 4), ("dy", "dx"))
    grid = TorusGrid(h_axes=("dx",), v_axes=("dy",))
    data = (np.arange(8 * 8).reshape(8, 8) % 5).astype(np.float32)
    x = jnp.asarray(data, dtype=jnp.bfloat16)
    out = run_allreduce(mesh, grid, "torus2d", "xla", x)
    np.testing.assert_allclose(np.asarray(out[0], np.float32),
                               data.sum(0), rtol=1e-2)


def test_ring_rs_ag_roundtrip_convention():
    """ring lowering RS followed by AG must reproduce XLA chunk order."""
    mesh = make_mesh((4,), ("d",))

    @functools.partial(shard_map, mesh=mesh, in_specs=P("d"), out_specs=P("d"),
                       check_vma=False)
    def f(x):
        local = x[0]
        rs_ring = collectives._rs(local, "d", "ring")
        rs_xla = collectives._rs(local, "d", "xla")
        ag = collectives._ag(rs_ring, "d", "ring")
        return jnp.stack([jnp.sum(jnp.abs(rs_ring - rs_xla)),
                          jnp.sum(jnp.abs(ag - collectives._ag(rs_xla, "d", "xla")))])[None]

    data = np.random.RandomState(3).randn(4, 16).astype(np.float32)
    out = np.asarray(jax.jit(f)(jnp.asarray(data)))
    np.testing.assert_allclose(out, 0.0, atol=1e-5)


def test_factorize_table4_shapes():
    from repro.core.topology import paper_table4_grid
    assert factorize(16) == (4, 4)
    assert factorize(256) == (16, 16)
    assert factorize(12) == (3, 4)
    assert paper_table4_grid(3456) == (48, 72)
    assert paper_table4_grid(4096) == (64, 64)


def test_cost_model_paper_claims():
    """2D-torus: fewer steps than ring; less wire than hierarchical."""
    nbytes = 100e6  # ~ResNet-50 fp16 gradient
    ring = collectives.comm_cost_model("ring", nbytes, 32, 32, 50e9, 5e-6)
    hier = collectives.comm_cost_model("hierarchical", nbytes, 32, 32, 50e9, 5e-6)
    torus = collectives.comm_cost_model("torus2d", nbytes, 32, 32, 50e9, 5e-6)
    assert ring["steps"] == 2 * (1024 - 1)
    assert torus["steps"] == 2 * 31 + 2 * 31
    assert torus["steps"] == hier["steps"]          # same step count (paper)
    assert torus["wire_bytes"] < hier["wire_bytes"]  # X-times-smaller phase 2
    assert torus["seconds"] < ring["seconds"]


def test_torus_collective_schedule_in_hlo():
    """Structural check: the compiled torus2d shows RS/AR/AG phases and the
    explicit-ring lowering shows 2(X-1)+2(Y-1) collective-permutes."""
    import re
    mesh = make_mesh((2, 4), ("dy", "dx"))
    grid = TorusGrid(h_axes=("dx",), v_axes=("dy",))

    def lowered_text(lowering):
        @functools.partial(shard_map, mesh=mesh, in_specs=P(("dy", "dx")),
                           out_specs=P(("dy", "dx")), check_vma=False)
        def f(x):
            return collectives.all_reduce(x[0], grid, "torus2d", lowering)[None]
        x = jnp.zeros((8, 64), jnp.float32)
        return jax.jit(f).lower(x).compile().as_text()

    xla = lowered_text("xla")
    assert re.search(r"reduce-scatter", xla)
    assert re.search(r"all-reduce", xla)
    assert re.search(r"all-gather", xla)

    ring = lowered_text("ring")
    n_cp = len(re.findall(r"collective-permute(?:-start)?\(", ring))
    # X=4,Y=2: RS_h 3 + align 1, AR_v (RS 1 + align 1 + unalign 1 + AG 1),
    # AG_h (unalign 1 + 3) -- at least 2(X-1)+2(Y-1)=8 permutes, bounded above
    assert n_cp >= 8, ring
