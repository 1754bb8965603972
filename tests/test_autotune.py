"""Bucket-size autotuner: analytic knee, cost-model pick, sweep
refinement, and the ``bucket_bytes="auto"`` resolution path."""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import collectives
from repro.core.autotune import (LEGACY_DEFAULT_BUCKET_BYTES, TPU_POD_HW,
                                 analytic_knee_bytes,
                                 candidate_bucket_bytes, pick_within_bracket,
                                 recommend_bucket_bytes, refine_from_sweep,
                                 sweep_bracket)
from repro.core.grad_sync import (AUTO, GradSyncConfig, bucket_layout,
                                  resolve_sync_config, sync_tree)
from repro.core.topology import TorusGrid

RESNET50_BF16_BYTES = 51e6


# ----------------------------------------------------------- analytic knee

def test_knee_matches_roadmap_formula():
    """For ring-family strategies wire volume ~2x payload, so the knee is
    ~steps * latency * link_bw / 2 (the ROADMAP formula): 16x16 torus2d has
    60 steps -> ~1.5 MB at 50 GB/s, 1 us."""
    knee = analytic_knee_bytes("torus2d", 16, 16, TPU_POD_HW)
    c = collectives.comm_cost_model("torus2d", 1 << 20, 16, 16,
                                    TPU_POD_HW.link_bw, TPU_POD_HW.latency_s)
    expected = c["steps"] * TPU_POD_HW.latency_s * TPU_POD_HW.link_bw \
        / (c["wire_bytes"] / (1 << 20))
    assert knee == int(expected)
    assert 1.0e6 < knee < 2.0e6

def test_knee_scales_with_steps():
    """The flat ring has ~8x the steps of the 2D torus at 256 chips, so its
    knee (latency amortization point) is correspondingly larger."""
    ring = analytic_knee_bytes("ring", 16, 16, TPU_POD_HW)
    torus = analytic_knee_bytes("torus2d", 16, 16, TPU_POD_HW)
    assert ring > 4 * torus


def test_knee_degenerate_grid():
    assert analytic_knee_bytes("psum", 1, 1, TPU_POD_HW) == 0


def test_candidate_grid_brackets_knee_and_clamps():
    cands = candidate_bucket_bytes(1 << 20, total_bytes=3 << 20)
    assert 0 in cands
    assert (1 << 20) in cands
    assert all(b < 3 << 20 for b in cands)
    assert min(b for b in cands if b) == (1 << 20) // 16


# -------------------------------------------------------- cost-model pick

def test_recommend_beats_fused_and_legacy_constant():
    """The acceptance criterion: the pick's exposed comm time beats both
    the unbucketed baseline and the old hand-set 4 MiB constant."""
    rec = recommend_bucket_bytes("torus2d", 16, 16, TPU_POD_HW,
                                 total_bytes=RESNET50_BF16_BYTES)
    assert rec["mode"] == "cost_model"

    def exposed(b):
        return collectives.bucketed_comm_cost_model(
            "torus2d", RESNET50_BF16_BYTES, b, 16, 16, TPU_POD_HW.link_bw,
            TPU_POD_HW.latency_s,
            backward_seconds=TPU_POD_HW.backward_seconds)["exposed_seconds"]

    assert rec["exposed_seconds"] < exposed(0)
    assert rec["exposed_seconds"] < exposed(LEGACY_DEFAULT_BUCKET_BYTES)
    # within the slack band of the candidate optimum by construction
    assert rec["exposed_seconds"] <= 1.05 * rec["best_exposed_seconds"]


def test_recommend_within_10pct_of_dense_grid():
    """The default geometric grid's pick stays within 10% of a much denser
    sweep's optimum -- the guarantee the dryrun sweep gate relies on."""
    rec = recommend_bucket_bytes("torus2d", 16, 16, TPU_POD_HW,
                                 total_bytes=RESNET50_BF16_BYTES)
    dense = [int(b) for b in np.geomspace(1e4, RESNET50_BF16_BYTES - 1, 200)]
    ref = recommend_bucket_bytes("torus2d", 16, 16, TPU_POD_HW,
                                 total_bytes=RESNET50_BF16_BYTES,
                                 candidates=dense)
    assert rec["exposed_seconds"] <= 1.10 * ref["best_exposed_seconds"]


def test_recommend_prefers_fewer_buckets_within_slack():
    rec = recommend_bucket_bytes("torus2d", 16, 16, TPU_POD_HW,
                                 total_bytes=RESNET50_BF16_BYTES)
    # every candidate with fewer buckets than the pick must be outside the
    # slack band (otherwise the tie-break would have chosen it)
    for c in rec["candidates"]:
        if c["num_buckets"] < rec["num_buckets"]:
            assert c["exposed_seconds"] > 1.05 * rec["best_exposed_seconds"]


def test_recommend_analytic_mode_without_total():
    rec = recommend_bucket_bytes("torus2d", 16, 16, TPU_POD_HW)
    assert rec["mode"] == "analytic"
    assert rec["bucket_bytes"] == rec["analytic_knee_bytes"]


def test_recommend_retunes_for_strategy():
    """A downgrade torus2d -> ring multiplies the steps, so the tuned
    bucket size must grow with it (the elastic re-tune property)."""
    torus = recommend_bucket_bytes("torus2d", 16, 16, TPU_POD_HW,
                                   total_bytes=RESNET50_BF16_BYTES)
    ring = recommend_bucket_bytes("ring", 16, 16, TPU_POD_HW,
                                  total_bytes=RESNET50_BF16_BYTES)
    assert ring["bucket_bytes"] > torus["bucket_bytes"]


# ------------------------------------------------------- sweep refinement

def _rows(values):
    return [{"bucket_bytes": b, "exposed_seconds": e, "num_exchanges": i + 1}
            for i, (b, e) in enumerate(values)]


def test_sweep_bracket_and_membership():
    rows = _rows([(0, 5e-3), (1 << 20, 1e-3), (4 << 20, 2e-3),
                  (16 << 20, 4e-3)])
    br = sweep_bracket(rows)
    assert br["best_bucket_bytes"] == 1 << 20
    assert br["low"] == 0 and br["high"] == 4 << 20
    assert pick_within_bracket(1 << 20, br)
    assert pick_within_bracket(4 << 20, br)
    assert not pick_within_bracket(16 << 20, br)
    # edge rows are unbounded on the open side
    br_lo = sweep_bracket(_rows([(0, 1e-3), (1 << 20, 2e-3)]))
    assert br_lo["low"] is None
    assert pick_within_bracket(0, br_lo)


def test_sweep_bracket_requires_rows():
    with pytest.raises(ValueError):
        sweep_bracket([{"bucket_bytes": 0}])


def test_refine_from_sweep_picks_fewest_exchanges_in_slack():
    rows = [{"bucket_bytes": 1 << 20, "exposed_seconds": 1.00e-3,
             "num_exchanges": 50},
            {"bucket_bytes": 4 << 20, "exposed_seconds": 1.03e-3,
             "num_exchanges": 13},
            {"bucket_bytes": 16 << 20, "exposed_seconds": 2.0e-3,
             "num_exchanges": 4}]
    ref = refine_from_sweep(rows, "torus2d", 16, 16, TPU_POD_HW,
                            total_bytes=RESNET50_BF16_BYTES)
    assert ref["bucket_bytes"] == 4 << 20   # within 5% of best, 13 < 50
    assert ref["analytic"]["mode"] == "cost_model"
    assert isinstance(ref["agrees"], bool)


# ------------------------------------------- resolve_sync_config("auto")

def _mesh_grid():
    mesh = jax.make_mesh((2, 4), ("dy", "dx"))
    return mesh, TorusGrid(h_axes=("dx",), v_axes=("dy",))


def _tree():
    rng = np.random.RandomState(0)
    return {f"layer{i:02d}": {
        "kernel": jnp.asarray(rng.randn(128, 64), jnp.float32),
        "bias": jnp.asarray(rng.randn(64), jnp.float32)}
        for i in range(6)}


def test_resolve_auto_produces_tuned_int_and_event():
    mesh, grid = _mesh_grid()
    tree = _tree()
    cfg = GradSyncConfig(strategy="torus2d", fuse=True,
                         comm_dtype=jnp.float32, bucket_bytes=AUTO)
    rcfg, events = resolve_sync_config(cfg, grid, mesh, mesh.axis_names,
                                       params_like=tree)
    assert isinstance(rcfg.bucket_bytes, int) and rcfg.bucket_bytes > 0
    tune = [e for e in events if e["event"] == "bucket_autotune"]
    assert len(tune) == 1
    assert tune[0]["mode"] == "cost_model"
    assert tune[0]["bucket_bytes"] == rcfg.bucket_bytes
    assert tune[0]["strategy"] == "torus2d"
    # layout is now computable (would raise on the unresolved sentinel)
    assert bucket_layout(tree, rcfg)


def test_resolve_auto_without_params_uses_knee():
    mesh, grid = _mesh_grid()
    cfg = GradSyncConfig(strategy="torus2d", fuse=True, bucket_bytes=AUTO)
    rcfg, events = resolve_sync_config(cfg, grid, mesh, mesh.axis_names)
    hw = TPU_POD_HW
    x, y = grid.sizes(mesh)
    assert rcfg.bucket_bytes == analytic_knee_bytes("torus2d", x, y, hw)
    assert events[-1]["mode"] == "analytic"


def test_resolve_auto_retunes_on_downgrade():
    """An elastic downgrade (down torus axis -> ring fallback... on the
    2x4 mesh torus2d dies when 'dy' is down) must re-tune bucket_bytes for
    the surviving strategy, not reuse the torus2d-tuned value."""
    mesh, grid = _mesh_grid()
    tree = _tree()
    cfg = GradSyncConfig(strategy="torus2d", fuse=True,
                         comm_dtype=jnp.float32, bucket_bytes=AUTO)
    healthy, _ = resolve_sync_config(cfg, grid, mesh, mesh.axis_names)
    degraded, events = resolve_sync_config(
        cfg, grid, mesh, mesh.axis_names, down_axes=("dy",),
        context="elastic", params_like=tree)
    assert degraded.strategy != "torus2d"
    tune = [e for e in events if e["event"] == "bucket_autotune"]
    assert tune and tune[0]["strategy"] == degraded.strategy
    assert tune[0]["context"] == "elastic"
    assert isinstance(degraded.bucket_bytes, int)
    # different schedule, different knee -> different tuned size
    assert degraded.bucket_bytes != healthy.bucket_bytes


def test_sync_tree_rejects_unresolved_auto():
    mesh, grid = _mesh_grid()
    cfg = GradSyncConfig(bucket_bytes=AUTO)
    with pytest.raises(ValueError, match="resolve_sync_config"):
        sync_tree({"w": jnp.zeros((64,))}, grid, cfg)
    with pytest.raises(ValueError, match="resolve_sync_config"):
        bucket_layout({"w": jnp.zeros((64,))}, cfg)


def test_hardware_model_per_mesh_defaults():
    from repro.configs import comm
    hw1 = comm.hw_for_mesh("pod16x16")
    hw2 = comm.hw_for_mesh("pod2x16x16")
    assert hw1.link_bw > hw2.link_bw       # inter-pod links are slower
    assert hw2.latency_s > hw1.latency_s
    assert comm.hw_for_mesh("unknown") == hw1
    hw3 = comm.hw_for_mesh("pod16x16", backward_seconds=0.1)
    assert hw3.backward_seconds == 0.1
    assert comm.default_bucket_bytes("qwen3-1.7b") == AUTO
    assert comm.default_bucket_bytes("llama3-405b", fsdp=True) == 0
    assert comm.backward_seconds_estimate(0, 0) > 0
    est = comm.backward_seconds_estimate(1e16, 256)
    assert 0 < est < 1
