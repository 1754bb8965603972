"""Paper Table 2 / Table 6 analogue: all-reduce scheme comparison.

Three parts:

1. **Microbenchmark** (8 host devices): wall time of one 100MB-gradient
   all-reduce per strategy x lowering. CPU wall-times are not TPU times,
   but the *relative* ordering of strategies on the same fabric is the
   paper's claim and is fabric-independent at fixed byte volumes.

2. **Analytic alpha-beta model** at the paper's scales (Table 4 grids,
   V100 + 2x IB-EDR: ~25 GB/s/link, 5 us latency) and at the TPU target
   (50 GB/s ICI): steps, wire bytes, estimated seconds, and the derived
   GPU-scaling-efficiency column the paper reports (Table 6).

3. **Bucket-size sweep** (``--sweep-bucket-bytes``): for each candidate
   ``bucket_bytes`` of the bucketed gradient-sync pipeline, the measured
   wall time of syncing a ResNet-50-like gradient pytree on 8 host
   devices, the number of independent exchanges the compiled HLO shows
   (the overlap opportunity), and the ``bucketed_comm_cost_model``
   prediction at the TPU target (exposed comm after overlapping a ~40 ms
   backward pass). Small buckets pay k x step latency; one bucket cannot
   overlap at all -- the sweep exposes the tradeoff the paper's bucket
   fusion tunes.
"""

from __future__ import annotations

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import argparse
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from jax import shard_map
from repro.core import autotune, collectives
from repro.core.grad_sync import GradSyncConfig, bucket_layout, sync_tree
from repro.core.topology import TorusGrid, paper_table4_grid
from repro.launch import hlo_stats

RESNET50_GRAD_BYTES = 102e6          # ~25.5M params, fp32; fp16 = half
IMG_PER_SEC_1GPU = 2565 / 4          # paper Table 6: 4 GPUs = 2565 img/s

# TPU target for the sweep's cost-model column: 16x16 torus, 50 GB/s ICI,
# ~40 ms ResNet-50 backward at the paper's per-worker batch
TPU_X, TPU_Y = 16, 16
TPU_LINK_BW, TPU_LATENCY = 50e9, 1e-6
BACKWARD_SECONDS = 0.040

DEFAULT_SWEEP = [0, 1 << 20, 4 << 20, 16 << 20, 64 << 20]


def microbench(nbytes: int = 8 << 20, iters: int = 5) -> list[dict]:
    mesh = jax.make_mesh((2, 4), ("dy", "dx"))
    grid = TorusGrid(h_axes=("dx",), v_axes=("dy",))
    n = nbytes // 4
    n -= n % 64
    rows = []
    for strategy in ("psum", "ring", "hierarchical", "torus2d"):
        for lowering in ("xla", "ring"):
            @functools.partial(shard_map, mesh=mesh, in_specs=P(("dy", "dx")),
                               out_specs=P(("dy", "dx")), check_vma=False)
            def f(x):
                return collectives.all_reduce(x[0], grid, strategy, lowering)[None]

            x = jnp.zeros((8, n // 8), jnp.float32)
            fn = jax.jit(f)
            fn(x).block_until_ready()
            t0 = time.perf_counter()
            for _ in range(iters):
                fn(x).block_until_ready()
            us = (time.perf_counter() - t0) / iters * 1e6
            rows.append({"name": f"allreduce_{strategy}_{lowering}",
                         "us_per_call": round(us, 1),
                         "derived": f"{nbytes / 2**20:.0f}MiB,8dev"})
    return rows


def analytic_table() -> list[dict]:
    """Cost model at paper scales + TPU target; derived = predicted scaling
    efficiency vs the paper's measured one where available."""
    rows = []
    paper_meas = {1024: 84.75, 2048: 83.10, 3456: 74.08, 4096: 73.44}
    for n in (1024, 2048, 3456, 4096):
        y, x = paper_table4_grid(n)
        per_gpu_img = IMG_PER_SEC_1GPU
        compute_t = 32 / per_gpu_img            # 32 img per worker per step
        for strategy in ("ring", "hierarchical", "torus2d"):
            c = collectives.comm_cost_model(
                strategy, RESNET50_GRAD_BYTES / 2,  # fp16 exchange
                x, y, link_bw=25e9, latency=5e-6)
            eff = compute_t / (compute_t + c["seconds"]) * 100
            meas = paper_meas.get(n) if strategy == "torus2d" else None
            rows.append({
                "name": f"model_{strategy}_n{n}",
                "us_per_call": round(c["seconds"] * 1e6, 1),
                "derived": (f"eff={eff:.1f}%"
                            + (f",paper={meas}%" if meas else "")),
            })
    # TPU target mesh: 256-chip pod as 16x16 torus, bf16 exchange
    for strategy in ("ring", "hierarchical", "torus2d"):
        c = collectives.comm_cost_model(
            strategy, RESNET50_GRAD_BYTES / 2, 16, 16,
            link_bw=50e9, latency=1e-6)
        rows.append({"name": f"tpu_model_{strategy}_16x16",
                     "us_per_call": round(c["seconds"] * 1e6, 1),
                     "derived": f"steps={c['steps']}"})
    return rows


# ---------------------------------------------------------------------------
# bucket-size sweep
# ---------------------------------------------------------------------------

def _resnet_like_tree(total_floats: int = 1 << 21) -> dict:
    """A gradient pytree with ResNet-ish layer-size spread: a few big conv
    kernels, many medium ones, a tail of tiny BN scales/biases."""
    rng = np.random.RandomState(0)
    tree: dict = {}
    big = total_floats // 4
    tree["fc"] = {"kernel": jnp.asarray(rng.randn(big // 64, 64), jnp.float32)}
    remaining = total_floats - big
    i = 0
    while remaining > 0:
        n = min(remaining, max(1024, remaining // 6))
        tree[f"conv{i}"] = {
            "kernel": jnp.asarray(rng.randn(max(1, n // 16), 16), jnp.float32),
            "bn_scale": jnp.asarray(rng.randn(32), jnp.float32),
        }
        remaining -= n
        i += 1
    return tree


def bucket_sweep(bucket_bytes_list=DEFAULT_SWEEP, strategy: str = "torus2d",
                 iters: int = 5) -> list[dict]:
    """Measured wall time + HLO exchange count + TPU-target cost model for
    each bucket size. ``bucket_bytes=0`` is the single-fused-buffer baseline."""
    mesh = jax.make_mesh((2, 4), ("dy", "dx"))
    grid = TorusGrid(h_axes=("dx",), v_axes=("dy",))
    tree = _resnet_like_tree()
    rows = []
    for bb in bucket_bytes_list:
        cfg = GradSyncConfig(strategy=strategy, fuse=True,
                             comm_dtype=jnp.float32, bucket_bytes=bb)
        n_buckets = len(bucket_layout(tree, cfg))

        @functools.partial(shard_map, mesh=mesh, in_specs=P(), out_specs=P(),
                           check_vma=False)
        def f(t):
            return sync_tree(t, grid, cfg)

        fn = jax.jit(f)
        audit = hlo_stats.bucket_audit(
            fn.lower(tree).compile().as_text(), min_bytes=1024)
        fn(tree)["fc"]["kernel"].block_until_ready()
        t0 = time.perf_counter()
        for _ in range(iters):
            fn(tree)["fc"]["kernel"].block_until_ready()
        us = (time.perf_counter() - t0) / iters * 1e6

        model = collectives.bucketed_comm_cost_model(
            strategy, RESNET50_GRAD_BYTES / 2, bb, TPU_X, TPU_Y,
            TPU_LINK_BW, TPU_LATENCY, backward_seconds=BACKWARD_SECONDS)
        rows.append({
            "name": f"bucket_sweep_{strategy}_bb{bb}",
            "us_per_call": round(us, 1),
            "derived": (f"buckets={n_buckets},hlo_exchanges="
                        f"{audit['num_exchanges']},tpu_exposed_us="
                        f"{model['exposed_seconds'] * 1e6:.0f},tpu_win_us="
                        f"{model['overlap_win_seconds'] * 1e6:.0f}"),
        })

    # the autotuner's pick at the TPU target, over the union of the swept
    # sizes and its own grid -- the row the sweep is ultimately *for*
    hw = autotune.HardwareModel(link_bw=TPU_LINK_BW, latency_s=TPU_LATENCY,
                                backward_seconds=BACKWARD_SECONDS,
                                name="tpu-16x16")
    total = RESNET50_GRAD_BYTES / 2
    knee = autotune.analytic_knee_bytes(strategy, TPU_X, TPU_Y, hw)
    union = sorted(set(int(b) for b in bucket_bytes_list)
                   | set(autotune.candidate_bucket_bytes(knee, int(total))))
    rec = autotune.recommend_bucket_bytes(strategy, TPU_X, TPU_Y, hw,
                                          total_bytes=total,
                                          candidates=union)
    bracket = autotune.sweep_bracket(
        [{"bucket_bytes": r["bucket_bytes"],
          "exposed_seconds": r["exposed_seconds"]}
         for r in rec["candidates"]])
    rows.append({
        "name": f"bucket_autotune_{strategy}",
        "us_per_call": round(rec["exposed_seconds"] * 1e6, 1),
        "derived": (f"pick={rec['bucket_bytes']},buckets="
                    f"{rec['num_buckets']},knee={knee},within_bracket="
                    f"{autotune.pick_within_bracket(rec['bucket_bytes'], bracket)}"),
    })
    return rows


def run() -> list[dict]:
    return microbench() + analytic_table() + bucket_sweep()


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--sweep-bucket-bytes", nargs="?", const=",".join(
        str(b) for b in DEFAULT_SWEEP), default=None, metavar="BYTES,...",
        help="run only the bucket-size sweep (optionally a comma-separated "
             "list of bucket sizes; 0 = fused baseline)")
    ap.add_argument("--strategy", default="torus2d",
                    choices=sorted(collectives.STRATEGIES))
    args = ap.parse_args()

    if args.sweep_bucket_bytes is not None:
        sizes = [int(s) for s in args.sweep_bucket_bytes.split(",")]
        rows = bucket_sweep(sizes, strategy=args.strategy)
    else:
        rows = run()
    print("name,us_per_call,derived")
    for r in rows:
        print(f"{r['name']},{r['us_per_call']},{r['derived']}")


if __name__ == "__main__":
    main()
