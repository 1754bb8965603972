"""Gradient synchronization over pytrees (the paper's technique as a
first-class framework feature).

Two execution modes, chosen by ``fuse``:

* ``fuse=True`` (paper-faithful, pure data-parallel): leaves are flattened
  into fused comm buffers (mixed-precision: comm-dtype group + fp32 group,
  §3.2 of the paper keeps BN statistics and LARS in fp32), padded to the
  ring size, exchanged with the selected strategy, and scattered back. This
  is what the paper's NCCL implementation does with bucket fusion, and it is
  only legal when the leaves are replicated over the model axis (ResNet /
  pure-DP configs).

  ``bucket_bytes`` controls *how many* fused buffers there are:

  - ``bucket_bytes=0`` (legacy): one buffer per precision group -- the
    exchange can only start after the full backward pass.
  - ``bucket_bytes>0``: each precision group is greedily partitioned into
    size-targeted buckets, **ordered in reverse-backprop order** (the pytree
    flatten order follows the forward pass, so the *last* leaves get their
    gradients *first* during backprop). One strategy-dispatch all-reduce is
    issued per bucket, earliest-ready bucket first, so XLA's latency-hiding
    scheduler can overlap each bucket's 2D-Torus exchange with the
    remaining backward compute. See docs/gradient_sync.md for the layout
    contract and ``collectives.bucketed_comm_cost_model`` for the
    latency-vs-overlap tradeoff model.

* ``fuse=False`` (tensor/fsdp-sharded models): each *large* leaf is
  synchronized independently along its leading dimension (padded to X), so
  model-axis sharding on other dimensions is untouched by the exchange.
  Small leaves (below ``small_leaf_threshold`` elements -- BN statistics,
  scales, biases, which the sharding rules replicate) are latency-bound,
  so instead of one tiny ``psum`` per leaf they are **grouped**: same
  comm-dtype small leaves are raveled into shared buffers (partitioned by
  ``partition_buckets`` when ``bucket_bytes > 0``) and exchanged with one
  ``psum`` per group -- the same latency amortization the fused path gets,
  without touching the model-sharded large leaves.

``bucket_bytes`` may also be the string ``"auto"``: ``resolve_sync_config``
replaces it with a tuned value from ``repro.core.autotune`` (analytic knee
of the cost model, refined against the gradient size when ``params_like``
is given) -- re-resolution after an elastic downgrade re-tunes for the
degraded strategy. ``sync_tree`` / ``bucket_layout`` require the resolved
integer.

Both modes must run inside ``jax.shard_map`` where the grid
axes are manual.
"""

from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Sequence

import jax
import jax.numpy as jnp
from jax import lax
import numpy as np

from repro.core import collectives
from repro.core.topology import TorusGrid


#: ``bucket_bytes`` sentinel: resolve the value via ``repro.core.autotune``
#: at ``resolve_sync_config`` time instead of hand-setting a constant.
AUTO = "auto"


@dataclasses.dataclass(frozen=True)
class GradSyncConfig:
    strategy: str = "torus2d"           # psum | ring | hierarchical | torus2d
    lowering: str = "xla"               # xla | ring (explicit ppermute)
    comm_dtype: Any = jnp.bfloat16      # paper: fp16; TPU-native: bf16
    fp32_paths: tuple[str, ...] = ("batch_stats", "bn", "scale", "bias")
    fuse: bool = True
    mean: bool = True
    small_leaf_threshold: int = 2048    # below: grouped psum (latency-bound)
    bucket_bytes: int | str = 0         # 0: single fused buffer per group;
                                        # >0: size-targeted comm buckets;
                                        # "auto": tuned at resolve time
    reverse_order: bool = True          # issue buckets reverse-backprop first


def _require_resolved(bucket_bytes) -> int:
    """``bucket_bytes`` as an int; rejects the unresolved ``"auto"``."""
    if isinstance(bucket_bytes, bool) or not isinstance(bucket_bytes, int):
        raise ValueError(
            f"bucket_bytes={bucket_bytes!r} is not resolved -- pass the "
            "config through resolve_sync_config (which replaces "
            f"bucket_bytes={AUTO!r} with an autotuned value, "
            "docs/gradient_sync.md) before sync_tree/bucket_layout")
    return bucket_bytes


def _path_str(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path).lower()


def _pad_to(x: jax.Array, multiple: int) -> jax.Array:
    n = x.shape[0]
    rem = (-n) % multiple
    if rem == 0:
        return x
    pad = [(0, rem)] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, pad)


def _world(grid: TorusGrid) -> int:
    size = 1
    for a in grid.axes:
        size *= lax.axis_size(a)
    return size


def _ring_multiple(grid: TorusGrid) -> int:
    """Leading-dim divisibility required by the strategies' scatter phases."""
    x = 1
    for a in grid.h_axes:
        x *= lax.axis_size(a)
    y = 1
    for a in grid.v_axes:
        y *= lax.axis_size(a)
    # torus2d ring lowering reduce-scatters the 1/X chunk again over Y
    return x * y


# ---------------------------------------------------------------------------
# The exchange plan (pure python; the dry-run HLO audit and the bucket gauges
# read it without a trace, so it must stay trace-free)
# ---------------------------------------------------------------------------

def partition_buckets(leaf_bytes: Sequence[int], bucket_bytes: int) -> list[list[int]]:
    """Greedy partition of leaf indices into size-targeted buckets.

    Walks the leaves in the given order and closes a bucket as soon as its
    cumulative size reaches ``bucket_bytes`` (so each bucket is at least the
    target size except the last, and a single oversized leaf forms its own
    bucket). A trailing bucket smaller than *half* the target is merged
    into its predecessor: the leftover tail (worst case one tiny leaf)
    would otherwise become a pure-latency straggler exchange issued last,
    exactly where it delays the step. ``bucket_bytes <= 0`` returns one
    bucket with everything -- the legacy fully-fused layout.
    """
    idx = list(range(len(leaf_bytes)))
    if bucket_bytes <= 0:
        return [idx] if idx else []
    buckets: list[list[int]] = []
    cur: list[int] = []
    cur_bytes = 0
    for i in idx:
        cur.append(i)
        cur_bytes += leaf_bytes[i]
        if cur_bytes >= bucket_bytes:
            buckets.append(cur)
            cur, cur_bytes = [], 0
    if cur:
        buckets.append(cur)
    if len(buckets) >= 2 and 2 * sum(
            leaf_bytes[i] for i in buckets[-1]) < bucket_bytes:
        buckets[-2].extend(buckets.pop())
    return buckets


def _itemsize(dtype) -> int:
    return np.dtype(dtype).itemsize


class _Exchange(NamedTuple):
    mode: str                # "fused" | "per_leaf" | "grouped"
    group: str               # "comm" | "fp32"
    dtype: Any
    leaves: list[int]        # leaf indices, in buffer order


def _plan(leaves_p, cfg: GradSyncConfig) -> list[_Exchange]:
    """The exchanges ``sync_tree`` issues, in issue order.

    ``fuse=True``: the leaves split into a comm-dtype and an fp32 group
    (the paper exchanges the bulk in half precision but keeps BN
    statistics / scales / biases, and any fp32 vector leaf, in fp32), the
    comm group issued first; each group is partitioned by
    ``partition_buckets`` into ``"fused"`` buffers, one strategy
    all-reduce each.

    ``fuse=False``: the groups follow the path tags alone (fp32 vectors
    are not special-cased). Each large leaf is one ``"per_leaf"`` strategy
    exchange along its leading dim, preserving any model-axis sharding on
    trailing dims. Small leaves (below ``small_leaf_threshold`` elements,
    or scalars) are pooled per group, the pools in order of first
    appearance, and partitioned into ``"grouped"`` buffers, one ``psum``
    each. Pooling ravels leaves, so it relies on small leaves being
    replicated over non-grid axes -- which the sharding rules guarantee
    (1-D scales/biases/BN stats are never model-sharded).

    Within each kind, leaves go in reverse-backprop order when
    ``cfg.reverse_order``: tree-flatten order tracks the forward pass, so
    the last leaves' grads materialize first in backward -- their exchange
    is issued first and overlaps the rest of backprop.
    """
    bucket_bytes = _require_resolved(cfg.bucket_bytes)
    dtypes = {"comm": cfg.comm_dtype, "fp32": jnp.float32}
    singles: list[tuple[str, int]] = []
    pools: dict[str, list[int]] = {"comm": [], "fp32": []} if cfg.fuse else {}
    for k, (path, leaf) in enumerate(leaves_p):
        fp32 = any(tag in _path_str(path) for tag in cfg.fp32_paths)
        if cfg.fuse:
            # only the fused rule keeps untagged fp32 vectors in fp32; one
            # rule for both would change the per-leaf path's numerics
            fp32 = fp32 or leaf.dtype == jnp.float32 and leaf.ndim <= 1
        group = "fp32" if fp32 else "comm"
        if cfg.fuse or leaf.size < cfg.small_leaf_threshold or leaf.ndim == 0:
            pools.setdefault(group, []).append(k)
        else:
            singles.append((group, k))
    if cfg.reverse_order:
        singles.reverse()
    plan = [_Exchange("per_leaf", g, dtypes[g], [k]) for g, k in singles]
    for g, ks in pools.items():
        order = ks[::-1] if cfg.reverse_order else ks
        sizes = [leaves_p[k][1].size * _itemsize(dtypes[g]) for k in order]
        plan += [_Exchange("fused" if cfg.fuse else "grouped", g, dtypes[g],
                           [order[i] for i in bucket])
                 for bucket in partition_buckets(sizes, bucket_bytes)]
    return plan


def bucket_layout(grads, cfg: GradSyncConfig = GradSyncConfig()) -> list[dict]:
    """The exchange schedule ``sync_tree`` will issue, as metadata.

    Returns one dict per exchange in **issue order** with keys ``group``
    ("comm"|"fp32"), ``dtype``, ``nbytes``, ``num_leaves``, ``paths``, and
    ``mode``: ``"fused"`` buckets for the ``fuse=True`` path; for
    ``fuse=False`` one ``"per_leaf"`` entry per large leaf plus
    ``"grouped"`` entries for the shared small-leaf buckets. Works on
    concrete arrays or ShapeDtypeStructs; never traces. Used by the
    dry-run audit and bucket sweep to cross-check the HLO against the
    intended schedule.
    """
    leaves_p, _ = jax.tree_util.tree_flatten_with_path(grads)
    return [{
        "group": ex.group,
        "dtype": np.dtype(ex.dtype).name,
        "nbytes": sum(leaves_p[k][1].size for k in ex.leaves)
        * _itemsize(ex.dtype),
        "num_leaves": len(ex.leaves),
        "paths": [_path_str(leaves_p[k][0]) for k in ex.leaves],
        "mode": ex.mode,
    } for ex in _plan(leaves_p, cfg)]


def sync_tree(grads, grid: TorusGrid, cfg: GradSyncConfig = GradSyncConfig()):
    """All-reduce (mean if cfg.mean) a gradient pytree over the DP grid,
    issuing the exchanges of ``bucket_layout`` in its order."""
    leaves_p, treedef = jax.tree_util.tree_flatten_with_path(grads)
    plan = _plan(leaves_p, cfg)
    if not leaves_p:
        return grads
    scale = 1.0 / _world(grid) if cfg.mean else 1.0
    mult = _ring_multiple(grid)
    leaves = [leaf for _, leaf in leaves_p]
    out: list = [None] * len(leaves)
    for ex in plan:
        if ex.mode == "per_leaf":
            # one strategy exchange along the leading dim, so model-axis
            # sharding on the other dims is untouched
            (k,) = ex.leaves
            g = leaves[k].astype(ex.dtype) * jnp.asarray(scale, ex.dtype)
            g = collectives.all_reduce(_pad_to(g, mult), grid, cfg.strategy,
                                       cfg.lowering)
            out[k] = g[:leaves[k].shape[0]].astype(leaves[k].dtype)
        else:
            _exchange_flat(ex, leaves, out, scale, mult, grid, cfg)
    return jax.tree_util.tree_unflatten(treedef, out)


def _exchange_flat(ex: _Exchange, leaves, out, scale, mult, grid: TorusGrid,
                   cfg: GradSyncConfig):
    """One raveled buffer of ``ex.leaves``: concatenate, pre-scale, reduce,
    and split the result back into ``out``."""
    flat = jnp.concatenate(
        [jnp.ravel(leaves[k]).astype(ex.dtype) for k in ex.leaves])
    # pre-scale: keeps fp16/bf16 partial sums in range (paper exchanges in
    # half precision)
    flat = flat * jnp.asarray(scale, ex.dtype)
    if ex.mode == "fused":
        reduced = collectives.all_reduce(_pad_to(flat, mult), grid,
                                         cfg.strategy, cfg.lowering)
        reduced = reduced[: flat.shape[0]]
    else:
        # grouped small leaves: one latency-amortized psum per buffer
        reduced = lax.psum(flat, grid.axes)
    off = 0
    for k in ex.leaves:
        size = leaves[k].size
        out[k] = reduced[off: off + size].reshape(
            leaves[k].shape).astype(leaves[k].dtype)
        off += size


def record_bucket_metrics(grads_like, cfg: GradSyncConfig,
                          registry) -> list[dict]:
    """Publish the bucket schedule as gauges on a metrics registry
    (repro.obs.metrics; docs/observability.md has the name table).

    ``sync_tree`` itself runs inside jit/shard_map, so per-bucket numbers
    can't be recorded at execution time -- but the schedule is a pure
    host-side function of the gradient *structure* (``bucket_layout``),
    which the trainer knows the moment it resolves the sync config. Called
    with the params tree (same treedef as the grads) and the resolved
    config, this sets, for the fused path:

    * ``grad_sync/num_buckets``            -- buckets in issue order
    * ``grad_sync/total_nbytes``           -- bytes over all buckets
    * ``grad_sync/bucketNN/nbytes``        -- per-bucket comm payload
    * ``grad_sync/bucketNN/num_leaves``    -- leaves packed into bucket NN

    and for the per-leaf ``fuse=False`` path:

    * ``grad_sync/num_exchanges``          -- total exchanges (both paths)
    * ``grad_sync/total_nbytes``           -- bytes over all exchanges
    * ``grad_sync/per_leaf_exchanges``     -- large-leaf strategy exchanges
    * ``grad_sync/grouped_buckets``        -- shared small-leaf psum buckets
    * ``grad_sync/bucketNN/...``           -- the grouped buckets only

    Every call first drops **all** ``grad_sync/`` metrics from the registry
    (``MetricsRegistry.remove_prefix``): an elastic re-resolve can change
    the bucket count or switch sync paths entirely, and gauges from the
    previous schedule must not linger and get exported as current.

    The multidevice obs smoke cross-checks the gauge count against
    ``hlo_stats.bucket_audit`` on the compiled step -- gauges describe the
    *intended* schedule, the audit the *compiled* one; they must agree.
    Returns the layout (issue order); [] only when ``registry`` is None.
    """
    if registry is None:
        return []
    remove_prefix = getattr(registry, "remove_prefix", None)
    if remove_prefix is not None:
        remove_prefix("grad_sync/")
    layout = bucket_layout(grads_like, cfg)
    registry.gauge("grad_sync/num_exchanges").set(len(layout))
    registry.gauge("grad_sync/total_nbytes").set(
        sum(b["nbytes"] for b in layout))
    if cfg.fuse:
        registry.gauge("grad_sync/num_buckets").set(len(layout))
        for i, b in enumerate(layout):
            registry.gauge(f"grad_sync/bucket{i:02d}/nbytes").set(b["nbytes"])
            registry.gauge(
                f"grad_sync/bucket{i:02d}/num_leaves").set(b["num_leaves"])
        return layout
    grouped = [b for b in layout if b["mode"] == "grouped"]
    registry.gauge("grad_sync/per_leaf_exchanges").set(
        sum(1 for b in layout if b["mode"] == "per_leaf"))
    registry.gauge("grad_sync/grouped_buckets").set(len(grouped))
    for i, b in enumerate(grouped):
        registry.gauge(f"grad_sync/bucket{i:02d}/nbytes").set(b["nbytes"])
        registry.gauge(
            f"grad_sync/bucket{i:02d}/num_leaves").set(b["num_leaves"])
    return layout


# ---------------------------------------------------------------------------
# Graceful degradation: strategy fallback chain (docs/robustness.md)
# ---------------------------------------------------------------------------

#: Ordered degradation chain per strategy (2d_torus -> ... -> ring -> psum).
#: Later entries trade the paper's bandwidth-optimal schedule for
#: robustness: hierarchical (xla lowering) is all-reduce-only so it lowers
#: everywhere torus2d cannot, the flat ring is a single in-axis exchange
#: XLA may reroute around a dead link, and psum is the native all-reduce
#: that always lowers.
FALLBACK_CHAINS: dict[str, tuple[str, ...]] = {
    "torus2d": ("torus2d", "hierarchical", "ring", "psum"),
    "hierarchical": ("hierarchical", "ring", "psum"),
    "ring": ("ring", "psum"),
    "psum": ("psum",),
}


def fallback_chain(strategy: str) -> tuple[str, ...]:
    return FALLBACK_CHAINS.get(strategy, (strategy, "psum"))


def _strategy_viable(strategy: str, lowering: str, grid: TorusGrid, mesh,
                     manual_axes, down_axes=(), probe: bool = True):
    """(viable, reason). ``reason`` explains the rejection when not viable.

    Two checks, cheapest first:

    1. *Down axes*: torus2d / hierarchical decompose the reduction into
       per-axis phases that map onto physical link dimensions -- a down
       torus axis kills them. The flat strategies (ring with the xla
       lowering, psum) leave routing to the compiler/fabric and survive;
       the explicit ppermute ring lowering pins neighbor links, so it is
       rejected too.
    2. *Trace probe*: a tiny ``jax.eval_shape`` of the strategy under the
       real mesh/grid catches anything else (missing primitives, bad axis
       factorization) without allocating or compiling. Only run when the
       shard_map is fully manual.
    """
    down = set(down_axes) & set(grid.axes)
    if down:
        if strategy in ("torus2d", "hierarchical"):
            return False, (f"torus axis(es) {sorted(down)} down: per-axis "
                           "phase decomposition unavailable")
        if lowering == "ring":
            return False, (f"axis(es) {sorted(down)} down: explicit ppermute "
                           "ring pins dead neighbor links")

    manual = set(manual_axes)
    partial = bool(set(mesh.axis_names) - manual)
    if probe and not partial:
        try:
            mult = 1
            for a in grid.axes:
                mult *= int(mesh.shape[a])

            def _probe_sync(x):
                return collectives.all_reduce(x, grid, strategy, lowering)

            smapped = jax.shard_map(
                _probe_sync, mesh=mesh,
                in_specs=jax.sharding.PartitionSpec(),
                out_specs=jax.sharding.PartitionSpec(),
                axis_names=frozenset(manual), check_vma=False)
            jax.eval_shape(
                smapped, jax.ShapeDtypeStruct((mult,), jnp.float32))
        except Exception as e:  # noqa: BLE001 -- any trace failure degrades
            return False, f"trace probe failed: {type(e).__name__}: {e}"
    return True, ""


def _resolve_bucket_bytes(cfg: GradSyncConfig, grid: TorusGrid, mesh,
                          params_like, hw, context: str
                          ) -> tuple[GradSyncConfig, list[dict]]:
    """Replace ``bucket_bytes="auto"`` with an autotuned value.

    Runs *after* the strategy fallback chain, so the tuned size matches the
    strategy that will actually execute -- an elastic downgrade (say
    torus2d -> ring, 4x the steps hence ~4x the knee) re-tunes here on
    re-resolution. With ``params_like`` (params/grads tree or
    ShapeDtypeStructs) the pick minimizes the cost model's exposed comm
    time over a grid around the analytic knee; without it, the knee alone.
    """
    if cfg.bucket_bytes != AUTO:
        return cfg, []
    from repro.core import autotune
    hw = hw or autotune.TPU_POD_HW
    x, y = grid.sizes(mesh)
    total_bytes = None
    if params_like is not None:
        layout = bucket_layout(
            params_like, dataclasses.replace(cfg, bucket_bytes=0))
        total_bytes = sum(b["nbytes"] for b in layout)
    rec = autotune.recommend_bucket_bytes(cfg.strategy, x, y, hw,
                                          total_bytes=total_bytes)
    event = {"event": "bucket_autotune", "context": context,
             "strategy": cfg.strategy, "mode": rec["mode"],
             "bucket_bytes": rec["bucket_bytes"],
             "analytic_knee_bytes": rec["analytic_knee_bytes"],
             "total_bytes": total_bytes, "hw": rec["hw"]["name"]}
    if rec["mode"] == "cost_model":
        event["exposed_seconds"] = rec["exposed_seconds"]
        event["num_buckets"] = rec["num_buckets"]
    return dataclasses.replace(cfg, bucket_bytes=rec["bucket_bytes"]), [event]


def resolve_sync_config(cfg: GradSyncConfig, grid: TorusGrid, mesh,
                        manual_axes, down_axes=(), probe: bool = True,
                        context: str = "startup", params_like=None,
                        hw=None) -> tuple[GradSyncConfig, list[dict]]:
    """Walk ``cfg.strategy``'s fallback chain; return the first viable
    config plus the rejection/downgrade events (for history/logging).

    Never raises: psum terminates every chain and always lowers. A
    downgrade is an event, not an error -- the job keeps training
    (docs/robustness.md). ``context`` tags the events with *when* the
    resolution ran: ``"startup"`` (job launch) or ``"elastic"`` (mid-run
    re-resolution after a permanent failure, ``repro.train.elastic``).

    ``bucket_bytes="auto"`` is resolved here too (after the strategy is
    fixed, so the tuned size matches the executing schedule), against
    ``params_like`` (the gradient structure; optional) and ``hw`` (an
    ``autotune.HardwareModel``; defaults to the paper-target pod). The
    pick is attached as a ``bucket_autotune`` event.
    """
    events: list[dict] = []
    chain = fallback_chain(cfg.strategy)
    for strategy in chain:
        ok, reason = _strategy_viable(strategy, cfg.lowering, grid, mesh,
                                      manual_axes, down_axes, probe)
        if ok:
            if strategy != cfg.strategy:
                events.append({
                    "event": "grad_sync_downgrade",
                    "from": cfg.strategy, "to": strategy,
                    "context": context,
                })
            resolved = dataclasses.replace(cfg, strategy=strategy)
            resolved, tune_events = _resolve_bucket_bytes(
                resolved, grid, mesh, params_like, hw, context)
            return resolved, events + tune_events
        events.append({"event": "grad_sync_strategy_rejected",
                       "strategy": strategy, "reason": reason,
                       "context": context})
    # unreachable in practice (psum has no rejection path), but never abort
    events.append({"event": "grad_sync_downgrade",
                   "from": cfg.strategy, "to": "psum", "context": context})
    resolved = dataclasses.replace(cfg, strategy="psum")
    resolved, tune_events = _resolve_bucket_bytes(
        resolved, grid, mesh, params_like, hw, context)
    return resolved, events + tune_events
