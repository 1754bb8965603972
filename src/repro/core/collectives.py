"""All-reduce algorithms from the paper (§2.2), as named-axis collectives.

Four strategies, each an all-reduce over the data-parallel mesh axes:

  psum          -- single ``lax.psum`` over all DP axes (XLA-native oracle).
  ring          -- flat ring over the flattened DP axes (Baidu ring [14]).
  hierarchical  -- AR inside the horizontal groups, then AR across vertical
                   groups on the FULL volume (Jia et al. [6]).
  torus2d       -- the paper's scheme: reduce-scatter along horizontal rings,
                   all-reduce along vertical rings on 1/X of the volume,
                   all-gather along horizontal rings.

Each strategy has two *lowerings*:

  xla   -- one ``psum_scatter`` / ``psum`` / ``all_gather`` per phase; XLA
           chooses the in-axis algorithm and can overlap phases.
  ring  -- the paper's literal step-by-step ring algorithm built from
           ``lax.ppermute`` (2(n-1) explicit neighbor exchanges); useful to
           audit the collective schedule in HLO and faithful to the paper.

All functions must be called inside ``jax.shard_map`` where the involved
axes are manual. Inputs are the *local* gradient shard; callers are
responsible for the leading dimension being divisible by the relevant ring
sizes (see ``grad_sync.pad_to``).
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax import lax

from repro.core.topology import TorusGrid

AxisName = str | tuple[str, ...]


def _axis_index(axis: AxisName):
    if isinstance(axis, (tuple, list)):
        idx = jnp.int32(0)
        for a in axis:
            idx = idx * lax.axis_size(a) + lax.axis_index(a)
        return idx
    return lax.axis_index(axis)


def _fwd_perm(n: int) -> list[tuple[int, int]]:
    return [(i, (i + 1) % n) for i in range(n)]


# ---------------------------------------------------------------------------
# Explicit ring primitives (paper's literal algorithm, via ppermute)
# ---------------------------------------------------------------------------

def ring_reduce_scatter(x: jax.Array, axis: AxisName) -> jax.Array:
    """Ring reduce-scatter along ``axis``.

    ``x.shape[0]`` must be divisible by the axis size. Returns the fully
    reduced chunk with *global chunk index* ``(i + 1) % n`` on rank ``i``
    (standard ring convention); compose with :func:`ring_all_gather` which
    accounts for the offset.
    """
    n = lax.axis_size(axis)
    if n == 1:
        return x
    i = _axis_index(axis)
    csize = x.shape[0] // n

    def chunk(k):
        return lax.dynamic_slice_in_dim(x, (k % n) * csize, csize, 0)

    acc = chunk(i)
    perm = _fwd_perm(n)
    for s in range(n - 1):
        recv = lax.ppermute(acc, axis, perm)
        acc = recv + chunk(i - 1 - s)
    return acc


def ring_all_gather(acc: jax.Array, axis: AxisName) -> jax.Array:
    """Ring all-gather of per-rank chunks produced by ring_reduce_scatter.

    Rank ``i`` holds global chunk ``(i + 1) % n``; after ``n - 1`` neighbor
    exchanges every rank holds the full concatenation in global order.
    """
    n = lax.axis_size(axis)
    if n == 1:
        return acc
    i = _axis_index(axis)
    csize = acc.shape[0]
    out = jnp.zeros((n * csize,) + acc.shape[1:], acc.dtype)
    out = lax.dynamic_update_slice_in_dim(out, acc, ((i + 1) % n) * csize, 0)
    perm = _fwd_perm(n)
    cur = acc
    for s in range(n - 1):
        cur = lax.ppermute(cur, axis, perm)
        # received from rank i-1-s, which held global chunk (i - s) % n
        out = lax.dynamic_update_slice_in_dim(out, cur, ((i - s) % n) * csize, 0)
    return out


def ring_all_reduce(x: jax.Array, axis: AxisName) -> jax.Array:
    """Flat ring all-reduce: RS then AG, 2(n-1) neighbor exchanges."""
    return ring_all_gather(ring_reduce_scatter(x, axis), axis)


# ---------------------------------------------------------------------------
# Phase implementations with selectable lowering
# ---------------------------------------------------------------------------

def _rs(x, axis, lowering):
    n = lax.axis_size(axis)
    if n == 1:
        return x
    if lowering == "xla":
        return lax.psum_scatter(x, axis, scatter_dimension=0, tiled=True)
    rs = ring_reduce_scatter(x, axis)
    # re-align to XLA convention (rank i holds chunk i) by rolling one hop
    return lax.ppermute(rs, axis, _fwd_perm(n))


def _ag(x, axis, lowering):
    n = lax.axis_size(axis)
    if n == 1:
        return x
    if lowering == "xla":
        return lax.all_gather(x, axis, axis=0, tiled=True)
    # incoming follows XLA convention (rank i holds chunk i); roll back one
    # hop to the ring convention then gather.
    back = [((i + 1) % n, i) for i in range(n)]
    return ring_all_gather(lax.ppermute(x, axis, back), axis)


def _ar(x, axis, lowering):
    if lax.axis_size(axis) == 1:
        return x
    if lowering == "xla":
        return lax.psum(x, axis)
    return ring_all_reduce(x, axis)


# ---------------------------------------------------------------------------
# The four strategies
# ---------------------------------------------------------------------------

def psum_all_reduce(x: jax.Array, grid: TorusGrid, lowering: str = "xla") -> jax.Array:
    del lowering
    return lax.psum(x, grid.axes)


def flat_ring_all_reduce(x: jax.Array, grid: TorusGrid, lowering: str = "xla") -> jax.Array:
    """One flat ring over all DP axes: 2(N-1) steps (paper's Ring baseline)."""
    axes = grid.axes
    if lowering == "xla":
        x = lax.psum_scatter(x, axes, scatter_dimension=0, tiled=True)
        return lax.all_gather(x, axes, axis=0, tiled=True)
    return ring_all_reduce(x, axes)


def hierarchical_all_reduce(x: jax.Array, grid: TorusGrid, lowering: str = "xla") -> jax.Array:
    """Jia et al. [6]: AR inside horizontal groups, then AR across vertical
    groups carrying the FULL gradient volume (the X-times-larger second step
    the paper's §2.2 calls out)."""
    x = _ar(x, grid.h_axes if len(grid.h_axes) > 1 else grid.h_axes[0], lowering)
    if grid.v_axes:
        x = _ar(x, grid.v_axes if len(grid.v_axes) > 1 else grid.v_axes[0], lowering)
    return x


def torus2d_all_reduce(x: jax.Array, grid: TorusGrid, lowering: str = "xla") -> jax.Array:
    """The paper's 2D-Torus all-reduce.

    reduce-scatter along horizontal rings -> all-reduce along vertical rings
    (on 1/X of the bytes) -> all-gather along horizontal rings.
    ``x.shape[0]`` must be divisible by X.
    """
    h = grid.h_axes if len(grid.h_axes) > 1 else grid.h_axes[0]
    x = _rs(x, h, lowering)
    if grid.v_axes:
        v = grid.v_axes if len(grid.v_axes) > 1 else grid.v_axes[0]
        x = _ar(x, v, lowering)
    return _ag(x, h, lowering)


STRATEGIES = {
    "psum": psum_all_reduce,
    "ring": flat_ring_all_reduce,
    "hierarchical": hierarchical_all_reduce,
    "torus2d": torus2d_all_reduce,
}


def all_reduce(x: jax.Array, grid: TorusGrid, strategy: str = "torus2d",
               lowering: str = "xla") -> jax.Array:
    """Dispatch an all-reduce (sum) of ``x`` over the grid's DP axes."""
    try:
        fn = STRATEGIES[strategy]
    except KeyError:
        raise ValueError(f"unknown strategy {strategy!r}; options {sorted(STRATEGIES)}") from None
    return fn(x, grid, lowering)


# ---------------------------------------------------------------------------
# Analytic cost model (paper §2.2 step counts; used by the bucket autotuner)
# ---------------------------------------------------------------------------

def comm_cost_model(strategy: str, nbytes: int, x: int, y: int,
                    link_bw: float, latency: float) -> dict:
    """Alpha-beta cost of one all-reduce of ``nbytes`` on an X x Y torus.

    Returns steps, bytes-on-wire per device, and estimated seconds. This is
    the model behind the paper's 2(X-1)-vs-2(N-1) argument and the
    hierarchical comparison (Table 2/6 analogue).
    """
    n = x * y
    if strategy == "ring":
        steps = 2 * (n - 1)
        wire = 2.0 * nbytes * (n - 1) / n
    elif strategy == "hierarchical":
        steps = 2 * (x - 1) + 2 * (y - 1)
        wire = 2.0 * nbytes * (x - 1) / x + 2.0 * nbytes * (y - 1) / y
    elif strategy == "torus2d":
        steps = 2 * (x - 1) + 2 * (y - 1)
        wire = 2.0 * nbytes * (x - 1) / x + 2.0 * (nbytes / x) * (y - 1) / y
    elif strategy == "psum":  # model as a good tree/ring hybrid == torus
        steps = 2 * (x - 1) + 2 * (y - 1)
        wire = 2.0 * nbytes * (x - 1) / x + 2.0 * (nbytes / x) * (y - 1) / y
    else:
        raise ValueError(strategy)
    seconds = steps * latency + wire / link_bw
    return {"strategy": strategy, "steps": steps, "wire_bytes": wire, "seconds": seconds}


def bucketed_comm_cost_model(strategy: str, nbytes: int, bucket_bytes: int,
                             x: int, y: int, link_bw: float, latency: float,
                             backward_seconds: float = 0.0) -> dict:
    """Alpha-beta cost of a *bucketed* gradient exchange overlapped with
    backprop (the schedule ``grad_sync.sync_tree`` emits for
    ``bucket_bytes > 0``).

    The gradient is split into ``k = ceil(nbytes / bucket_bytes)`` buckets.
    Every bucket pays the full per-step latency (steps x alpha -- the cost
    of more buckets) but bucket ``i`` becomes ready at
    ``backward_seconds * (i + 1) / k`` (gradients stream out of backprop in
    reverse-layer order at roughly uniform rate) and its exchange runs as
    soon as both the gradients and the link are free -- the overlap win.

    Returns::

        num_buckets, per_bucket (comm_cost_model dicts),
        serial_seconds   -- sum of bucket costs, no overlap (lower bound on
                            the fused latency had we not overlapped),
        exposed_seconds  -- comm time NOT hidden behind backprop
                            (finish of last bucket - backward_seconds),
        fused_exposed_seconds -- the single-buffer baseline: the whole
                            exchange starts after backward, fully exposed,
        overlap_win_seconds -- fused_exposed - exposed.

    With ``backward_seconds=0`` this degenerates to the pure serial
    latency-vs-bandwidth tradeoff (more buckets strictly worse).
    """
    if bucket_bytes <= 0 or bucket_bytes >= nbytes:
        k = 1
        sizes = [nbytes]
    else:
        k = -(-int(nbytes) // int(bucket_bytes))
        sizes = [bucket_bytes] * (k - 1) + [nbytes - bucket_bytes * (k - 1)]

    per_bucket = [comm_cost_model(strategy, s, x, y, link_bw, latency)
                  for s in sizes]
    serial = sum(c["seconds"] for c in per_bucket)

    # pipeline simulation: one link, buckets issued in ready order
    t = 0.0
    for i, c in enumerate(per_bucket):
        ready = backward_seconds * (i + 1) / k
        t = max(t, ready) + c["seconds"]
    exposed = t - backward_seconds

    fused = comm_cost_model(strategy, nbytes, x, y, link_bw, latency)
    return {
        "strategy": strategy,
        "num_buckets": k,
        "bucket_bytes": bucket_bytes,
        "per_bucket": per_bucket,
        "serial_seconds": serial,
        "exposed_seconds": exposed,
        "fused_exposed_seconds": fused["seconds"],
        "overlap_win_seconds": fused["seconds"] - exposed,
    }
