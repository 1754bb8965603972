"""Bucketed gradient sync: partition invariants, property-based round-trip
vs the psum oracle (all four strategies x both lowerings x bucketed/fused),
precision-group preservation, the reverse-backprop issue order, the HLO
audit proving independent per-bucket collectives, and the per-bucket
alpha-beta cost model."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from _hyp import given, settings, strategies as st
from jax import shard_map
from repro.core import collectives
from repro.core.grad_sync import (GradSyncConfig, bucket_layout,
                                  partition_buckets, sync_tree)
from repro.core.topology import TorusGrid
from repro.launch import hlo_stats

WORLD = 8
GRID = TorusGrid(h_axes=("dx",), v_axes=("dy",))

MESH = None


def get_mesh():
    global MESH
    if MESH is None:
        MESH = jax.make_mesh((2, 4), ("dy", "dx"))
    return MESH


# ------------------------------------------------------------ partition --

@settings(max_examples=30, deadline=None)
@given(sizes=st.lists(st.integers(0, 4096), min_size=0, max_size=40),
       bucket_bytes=st.integers(0, 8192))
def test_partition_buckets_invariants(sizes, bucket_bytes):
    buckets = partition_buckets(sizes, bucket_bytes)
    # exact, order-preserving partition of the index range
    flat = [i for b in buckets for i in b]
    assert flat == list(range(len(sizes)))
    assert all(b for b in buckets)
    if bucket_bytes <= 0:
        assert len(buckets) <= 1
    else:
        # every bucket except the last meets the size target
        for b in buckets[:-1]:
            assert sum(sizes[i] for i in b) >= bucket_bytes


def test_partition_single_oversized_leaf_gets_own_bucket():
    assert partition_buckets([100, 5, 5], 10) == [[0], [1, 2]]


@settings(max_examples=50, deadline=None)
@given(sizes=st.lists(st.integers(0, 4096), min_size=0, max_size=40),
       bucket_bytes=st.integers(1, 8192))
def test_partition_no_undersized_tail_bucket(sizes, bucket_bytes):
    """Regression (ISSUE 10): the greedy partition used to leave a trailing
    bucket far below target (worst case one tiny leaf) -- a pure-latency
    straggler issued last. Whenever there are >=2 buckets, every bucket,
    the last included, must now be >= bucket_bytes/2."""
    buckets = partition_buckets(sizes, bucket_bytes)
    if len(buckets) >= 2:
        for b in buckets:
            assert 2 * sum(sizes[i] for i in b) >= bucket_bytes


def test_partition_tail_merge_regression():
    # [10, 10, 1]: tail bucket [1] is < target/2 -> merged into predecessor
    assert partition_buckets([10, 10, 1], 10) == [[0], [1, 2]]
    # a tail >= half the target stays its own bucket
    assert partition_buckets([10, 10, 6], 10) == [[0], [1], [2]]
    # single bucket total: nothing to merge into
    assert partition_buckets([3], 10) == [[0]]


# --------------------------------------------------------------- layout --

def _mixed_tree(rng):
    return {
        "dense": {"kernel": rng.randn(WORLD, 40, 7).astype(np.float32),
                  "bias": rng.randn(WORLD, 7).astype(np.float32)},
        "bn": {"scale": rng.randn(WORLD, 5).astype(np.float32)},
        "emb": rng.randn(WORLD, 33).astype(np.float32),
    }


def test_layout_preserves_precision_groups():
    tree = {"a": {"kernel": np.zeros((64, 4), np.float32)},
            "bn": {"scale": np.zeros(8, np.float32)},
            "b": {"kernel": np.zeros((64, 4), np.float32)}}
    cfg = GradSyncConfig(bucket_bytes=128, comm_dtype=jnp.bfloat16)
    layout = bucket_layout(tree, cfg)
    for b in layout:
        assert b["dtype"] == ("float32" if b["group"] == "fp32" else "bfloat16")
        if b["group"] == "fp32":
            assert all("bn" in p or "bias" in p or "scale" in p
                       for p in b["paths"])


def test_layout_reverse_backprop_order():
    """The first issued bucket must hold the LAST leaves in flatten order
    (whose grads backprop produces first)."""
    tree = {f"layer{i:02d}": {"kernel": np.zeros((256, 4), np.float32)}
            for i in range(8)}
    cfg = GradSyncConfig(bucket_bytes=2 * 256 * 4 * 4,
                         comm_dtype=jnp.float32)
    layout = [b for b in bucket_layout(tree, cfg) if b["group"] == "comm"]
    assert len(layout) == 4
    assert layout[0]["paths"] == ["layer07/kernel", "layer06/kernel"]
    assert layout[-1]["paths"] == ["layer01/kernel", "layer00/kernel"]

    fwd = GradSyncConfig(bucket_bytes=cfg.bucket_bytes,
                         comm_dtype=jnp.float32, reverse_order=False)
    layout_fwd = [b for b in bucket_layout(tree, fwd) if b["group"] == "comm"]
    assert layout_fwd[0]["paths"] == ["layer00/kernel", "layer01/kernel"]


def test_layout_zero_bucket_bytes_is_single_fused_buffer():
    rng = np.random.RandomState(0)
    layout = bucket_layout(_mixed_tree(rng), GradSyncConfig(bucket_bytes=0))
    assert [b["group"] for b in layout] == ["comm", "fp32"]


# ------------------------------------------------------- sync round-trip --

def run_sync(tree_per_rank, cfg):
    mesh = get_mesh()
    spec = P(("dy", "dx"))

    @functools.partial(shard_map, mesh=mesh,
                       in_specs=spec, out_specs=spec, check_vma=False)
    def f(tree):
        local = jax.tree.map(lambda x: x[0], tree)
        out = sync_tree(local, GRID, cfg)
        return jax.tree.map(lambda x: x[None], out)

    return jax.jit(f)(tree_per_rank)


def oracle(tree_per_rank):
    return jax.tree.map(
        lambda x: np.asarray(x, np.float32).sum(0) / WORLD, tree_per_rank)


@pytest.mark.multidevice
@pytest.mark.parametrize("strategy", ["psum", "ring", "hierarchical", "torus2d"])
@pytest.mark.parametrize("lowering", ["xla", "ring"])
def test_bucketed_sync_matches_oracle_all_strategies(strategy, lowering):
    rng = np.random.RandomState(0)
    tree = _mixed_tree(rng)
    cfg = GradSyncConfig(strategy=strategy, lowering=lowering, fuse=True,
                         comm_dtype=jnp.float32, bucket_bytes=512)
    out = run_sync(jax.tree.map(jnp.asarray, tree), cfg)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.broadcast_to(b, np.asarray(a).shape),
            rtol=1e-5, atol=1e-5),
        out, oracle(tree))


@pytest.mark.multidevice
@settings(max_examples=15, deadline=None)
@given(
    shapes=st.lists(
        st.lists(st.integers(1, 9), min_size=0, max_size=3),
        min_size=1, max_size=6),
    strategy=st.sampled_from(["psum", "ring", "hierarchical", "torus2d"]),
    lowering=st.sampled_from(["xla", "ring"]),
    bucket_bytes=st.sampled_from([0, 64, 300, 1 << 20]),
    seed=st.integers(0, 2**31 - 1),
)
def test_property_bucketed_roundtrip(shapes, strategy, lowering, bucket_bytes,
                                     seed):
    """Any pytree -> bucket partition -> sync -> unpartition reproduces the
    psum mean on every rank, for every strategy/lowering/bucket size."""
    rng = np.random.RandomState(seed)
    tree = {f"w{i}": rng.randn(WORLD, *s).astype(np.float32)
            for i, s in enumerate(shapes)}
    cfg = GradSyncConfig(strategy=strategy, lowering=lowering, fuse=True,
                         comm_dtype=jnp.float32, bucket_bytes=bucket_bytes)
    out = run_sync(jax.tree.map(jnp.asarray, tree), cfg)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.broadcast_to(b, np.asarray(a).shape),
            rtol=1e-4, atol=1e-5),
        out, oracle(tree))


@pytest.mark.multidevice
def test_bucketed_bitexact_vs_fused_for_psum():
    """Bucketing only changes buffer packing, not per-element reduction
    order, for the xla psum lowering: results must be bit-exact equal to the
    single-fused-buffer baseline."""
    rng = np.random.RandomState(7)
    tree = _mixed_tree(rng)
    outs = []
    for bb in (0, 400):
        cfg = GradSyncConfig(strategy="psum", fuse=True,
                             comm_dtype=jnp.float32, bucket_bytes=bb)
        outs.append(run_sync(jax.tree.map(jnp.asarray, tree), cfg))
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        outs[0], outs[1])


# ------------------------------------------------------------ HLO audit --

@pytest.mark.multidevice
def test_hlo_shows_independent_collectives_per_bucket():
    """The structural overlap proof: a multi-bucket config compiles to >=2
    independent reduction exchanges where the fused config shows exactly 1."""
    mesh = get_mesh()
    # comm-group-only tree (no bn/bias/scale) so fused == exactly 1 exchange
    tree = {f"w{i}": jnp.zeros((64, 64), jnp.float32) for i in range(8)}

    def compile_audit(bucket_bytes):
        cfg = GradSyncConfig(strategy="torus2d", fuse=True,
                             comm_dtype=jnp.float32,
                             bucket_bytes=bucket_bytes)

        @functools.partial(shard_map, mesh=mesh, in_specs=P(), out_specs=P(),
                           check_vma=False)
        def f(t):
            return sync_tree(t, GRID, cfg)

        hlo = jax.jit(f).lower(tree).compile().as_text()
        return hlo_stats.bucket_audit(hlo, min_bytes=1024)

    fused = compile_audit(0)
    assert fused["num_exchanges"] == 1, fused["by_kind"]

    bucketed = compile_audit(32 * 1024)
    assert bucketed["num_exchanges"] >= 2, bucketed["by_kind"]
    # every bucket produced its own full torus chain
    assert bucketed["by_kind"]["reduce-scatter"]["count"] == 4
    assert bucketed["by_kind"]["all-gather"]["count"] == 4


# ------------------------------------------------- per-leaf path grouping --

def _many_small_leaves_tree(n=24):
    """A TP-ish model slice: a few large kernels plus many small replicated
    scales/biases -- the regime where one-psum-per-leaf is latency-bound."""
    rng = np.random.RandomState(3)
    tree = {}
    for i in range(3):
        tree[f"block{i}"] = {
            "kernel": rng.randn(WORLD, 512, 8).astype(np.float32)}
    for i in range(n):
        tree[f"norm{i:02d}"] = {
            "gain": rng.randn(WORLD, 17).astype(np.float32)}
    return tree


def test_per_leaf_layout_groups_small_leaves():
    tree = jax.tree.map(lambda x: x[0], _many_small_leaves_tree())
    cfg = GradSyncConfig(fuse=False, comm_dtype=jnp.float32, bucket_bytes=0)
    layout = bucket_layout(tree, cfg)
    per_leaf = [b for b in layout if b["mode"] == "per_leaf"]
    grouped = [b for b in layout if b["mode"] == "grouped"]
    assert len(per_leaf) == 3            # the large kernels
    assert len(grouped) == 1             # all 24 gains share one psum
    assert grouped[0]["num_leaves"] == 24
    # bucket_bytes partitions the shared buffer too
    cfg_b = GradSyncConfig(fuse=False, comm_dtype=jnp.float32,
                           bucket_bytes=6 * 17 * 4)
    grouped_b = [b for b in bucket_layout(tree, cfg_b)
                 if b["mode"] == "grouped"]
    assert len(grouped_b) == 4


@pytest.mark.multidevice
def test_per_leaf_grouped_sync_matches_oracle():
    tree = _many_small_leaves_tree()
    for bb in (0, 6 * 17 * 4):
        cfg = GradSyncConfig(strategy="torus2d", fuse=False,
                             comm_dtype=jnp.float32, bucket_bytes=bb)
        out = run_sync(jax.tree.map(jnp.asarray, tree), cfg)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.broadcast_to(b, np.asarray(a).shape),
                rtol=1e-5, atol=1e-5),
            out, oracle(tree))


@pytest.mark.multidevice
def test_per_leaf_grouping_reduces_hlo_collectives():
    """Acceptance criterion (ISSUE 10): for a model with many small leaves
    the fuse=False path must compile to fewer collective ops than
    one-exchange-per-leaf."""
    mesh = get_mesh()
    n_small = 24
    tree = jax.tree.map(lambda x: x[0],
                        _many_small_leaves_tree(n_small))
    n_leaves = len(jax.tree.leaves(tree))
    cfg = GradSyncConfig(strategy="torus2d", fuse=False,
                         comm_dtype=jnp.float32, bucket_bytes=0)

    @functools.partial(shard_map, mesh=mesh, in_specs=P(), out_specs=P(),
                       check_vma=False)
    def f(t):
        return sync_tree(t, GRID, cfg)

    hlo = jax.jit(f).lower(tree).compile().as_text()
    n_coll = len(hlo_stats.collective_schedule(hlo))
    # old behavior: >= one collective per leaf (torus2d large leaves emit
    # several). New: 3 large-leaf chains + ONE grouped psum.
    assert n_coll < n_leaves, (n_coll, n_leaves)
    # 3 large-leaf torus chains (one y-phase all-reduce each) + exactly ONE
    # grouped psum covering all 24 small leaves
    n_ar = sum(1 for op in hlo_stats.collective_schedule(hlo)
               if op["kind"] == "all-reduce")
    assert n_ar == 4, n_ar


# ------------------------------------------------------------ cost model --

def test_bucketed_cost_model_latency_vs_overlap():
    nbytes, x, y = 51e6, 16, 16
    bw, lat = 50e9, 1e-6
    fused = collectives.bucketed_comm_cost_model(
        "torus2d", nbytes, 0, x, y, bw, lat, backward_seconds=0.040)
    bucketed = collectives.bucketed_comm_cost_model(
        "torus2d", nbytes, 4 << 20, x, y, bw, lat, backward_seconds=0.040)
    assert fused["num_buckets"] == 1
    assert bucketed["num_buckets"] == 13
    # more buckets -> more total latency on the wire...
    assert bucketed["serial_seconds"] > fused["serial_seconds"]
    # ...but overlap with backprop hides most of it
    assert bucketed["exposed_seconds"] < fused["fused_exposed_seconds"]
    assert bucketed["overlap_win_seconds"] > 0

    # without a backward pass to hide behind, bucketing is strictly worse
    no_overlap = collectives.bucketed_comm_cost_model(
        "torus2d", nbytes, 4 << 20, x, y, bw, lat, backward_seconds=0.0)
    assert no_overlap["exposed_seconds"] >= fused["fused_exposed_seconds"]


def test_bucketed_cost_model_bucket_sizes_sum():
    m = collectives.bucketed_comm_cost_model(
        "ring", 10_000_000, 3_000_000, 8, 8, 50e9, 1e-6)
    assert m["num_buckets"] == 4
    assert sum(c["wire_bytes"] for c in m["per_bucket"]) > 0
