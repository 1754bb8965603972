"""Perf hillclimbing driver (§Perf): re-lower a (arch x shape) under a
named variant, extract roofline terms, and log hypothesis -> result.

    PYTHONPATH=src python -m repro.launch.perf --exp <name>
    PYTHONPATH=src python -m repro.launch.perf --list

Each experiment is a function returning a list of variant records; results
append to experiments/perf/<exp>.json. Variants re-use the dry-run builders
so numbers are directly comparable with the §Roofline baselines.
"""

import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.shapes import SHAPES
from repro.launch import hlo_stats
from repro.launch.mesh import (cache_pspecs, dp_axes_of, make_factorized_mesh,
                               make_production_mesh, param_pspecs,
                               with_shardings)
from repro.models import transformer as T

PEAK_FLOPS, HBM_BW, ICI_BW = 197e12, 819e9, 50e9


def _measure(fn, args, *, step: str, label: str, n_blocks_pair=None) -> dict:
    """Compile and extract roofline terms. If ``n_blocks_pair`` is given as
    ((fn1, args1), (fn2, args2), n_blocks), scan-extrapolate the costs."""
    t0 = time.time()
    compiled = fn.lower(*args).compile()
    dt = time.time() - t0
    ca = compiled.cost_analysis()
    coll = hlo_stats.collective_stats(compiled.as_text())
    flops = ca.get("flops", 0.0)
    bytes_acc = ca.get("bytes accessed", 0.0)
    coll_total = coll["total_wire_bytes"]
    f32 = coll["wire_by_dtype"].get("f32", 0)
    if n_blocks_pair is not None:
        (f1, a1), (f2, a2), nb = n_blocks_pair
        e1 = _extract_cost(f1, a1)
        e2 = _extract_cost(f2, a2)
        flops = e1["flops"] + (e2["flops"] - e1["flops"]) * (nb - 1)
        bytes_acc = (e1["bytes"] + (e2["bytes"] - e1["bytes"]) * (nb - 1))
        coll_total = (e1["coll"] + (e2["coll"] - e1["coll"]) * (nb - 1))
        f32 = e1["f32"] + (e2["f32"] - e1["f32"]) * (nb - 1)
    if step == "train":
        coll_total -= f32 / 2        # bf16-exchange correction (see dryrun)
    mem = compiled.memory_analysis()
    rec = {
        "label": label,
        "compute_s": flops / PEAK_FLOPS,
        "memory_s": bytes_acc / HBM_BW,
        "collective_s": coll_total / ICI_BW,
        "coll_bytes": coll_total,
        "coll_counts": {k: coll[k]["count"] for k in
                        ("all-reduce", "all-gather", "reduce-scatter",
                         "all-to-all", "collective-permute")},
        "temp_gib": (getattr(mem, "temp_size_in_bytes", 0) or 0) / 2**30,
        "compile_s": round(dt, 1),
    }
    rec["dominant"] = max(("compute_s", "memory_s", "collective_s"),
                          key=lambda k: rec[k])
    return rec


def _extract_cost(fn, args):
    c = fn.lower(*args).compile()
    ca = c.cost_analysis()
    coll = hlo_stats.collective_stats(c.as_text())
    return {"flops": ca.get("flops", 0.0),
            "bytes": ca.get("bytes accessed", 0.0),
            "coll": coll["total_wire_bytes"],
            "f32": coll["wire_by_dtype"].get("f32", 0)}


def _cost_cfg(cfg, k, seq_len):
    return dataclasses.replace(
        cfg, n_layers=cfg.n_prefix + k * len(cfg.pattern), scan_blocks=False,
        q_chunk_unroll=True, ssm_unroll=True)


def measure_train(arch_id, shape_name, mesh, sync, label, fuse=None,
                  extrapolate=True):
    from repro.launch import dryrun as D
    shape = SHAPES[shape_name]
    cfg = D.arch_for(arch_id, shape)
    fn, args = D.build_train(arch_id, cfg, shape, mesh, sync, fuse=fuse)
    pair = None
    if extrapolate:
        pair = tuple(
            D.build_train(arch_id, _cost_cfg(cfg, k, shape.seq_len), shape,
                          mesh, sync, fuse=fuse) for k in (1, 2)) + (cfg.n_blocks,)
    return _measure(fn, args, step="train", label=label, n_blocks_pair=pair)


def measure_decode(arch_id, shape_name, mesh, label, cfg_patch=None,
                   cache_override=None, extrapolate=True):
    from repro.launch import dryrun as D
    shape = SHAPES[shape_name]
    cfg = D.arch_for(arch_id, shape)
    if cfg_patch:
        cfg = dataclasses.replace(cfg, **cfg_patch)

    def build(c):
        return D.build_decode(arch_id, c, shape, mesh)

    fn, args = build(cfg)
    if cache_override is not None:
        args = (args[0], args[1], cache_override(cfg, args[2]), args[3])
    pair = None
    if extrapolate:
        pair = tuple(build(_cost_cfg(cfg, k, shape.seq_len))
                     for k in (1, 2)) + (cfg.n_blocks,)
    return _measure(fn, args, step="decode", label=label, n_blocks_pair=pair)


def save(exp_name: str, records: list):
    os.makedirs("experiments/perf", exist_ok=True)
    path = f"experiments/perf/{exp_name}.json"
    with open(path, "w") as f:
        json.dump(records, f, indent=1)
    for r in records:
        print(f"{exp_name:28s} {r['label']:42s} "
              f"cmp {r['compute_s']:.2e} mem {r['memory_s']:.2e} "
              f"coll {r['collective_s']:.2e} dom={r['dominant']}")
    return path


# ---------------------------------------------------------------------------
# Experiments
# ---------------------------------------------------------------------------

def exp_sync_strategies():
    """Paper Table-2 analogue at HLO level: gradient-sync strategy sweep on
    gemma-7b train_4k, single-pod (1D data ring) and multi-pod (2D torus)."""
    out = []
    for mp in (False, True):
        mesh = make_production_mesh(multi_pod=mp)
        mname = "2pod" if mp else "1pod"
        for sync in ("psum", "ring", "hierarchical", "torus2d"):
            out.append(measure_train("gemma-7b", "train_4k", mesh, sync,
                                     f"{mname}/{sync}"))
    return out


def exp_factorized_torus():
    """Beyond-production-mesh: factorize the single pod's data axis into a
    4x4 torus (paper Table 4 style) so the 2D decomposition exists INSIDE
    one pod; compare vs the flat 16-ring."""
    out = []
    flat = make_production_mesh()
    out.append(measure_train("gemma-7b", "train_4k", flat, "torus2d",
                             "flat data=16 (1D ring)"))
    fact = make_factorized_mesh(data_y=4, data_x=4, model=16)
    out.append(measure_train("gemma-7b", "train_4k", fact, "torus2d",
                             "factorized 4x4 torus"))
    out.append(measure_train("gemma-7b", "train_4k", fact, "hierarchical",
                             "factorized 4x4 hierarchical"))
    out.append(measure_train("gemma-7b", "train_4k", fact, "ring",
                             "factorized flat ring (control)"))
    return out


def exp_kimi_decode():
    """kimi-k2 decode_32k: collective-bound MoE decode. Variants attack the
    dispatch/combine traffic."""
    mesh = make_production_mesh()
    out = [measure_decode("kimi-k2-1t-a32b", "decode_32k", mesh, "baseline")]
    # capacity factor 1.0 (fewer padded slots to move)
    out.append(measure_decode("kimi-k2-1t-a32b", "decode_32k", mesh,
                              "capacity 1.0",
                              cfg_patch={"moe_capacity_factor": 1.0}))
    return out


def measure_decode_2dtp(arch_id, shape_name, mesh, label):
    """Decode variant: weights 2D-sharded over (data x model) with the token
    batch REPLICATED over data -- turns the per-token FSDP weight all-gather
    into cheap activation psums (weight-stationary serving). The KV cache
    stays batch-sharded over data (it must -- ~2 TB at 405B/32k/128)."""
    from repro.launch import dryrun as D
    shape = SHAPES[shape_name]
    cfg = D.arch_for(arch_id, shape)

    def build(c):
        dp = dp_axes_of(mesh)
        params_sds = jax.eval_shape(lambda: T.init(jax.random.key(0), c))
        params_sds = with_shardings(
            params_sds, mesh, param_pspecs(params_sds, fsdp=True, mesh=mesh))
        B = shape.global_batch
        cache_sds = jax.eval_shape(lambda: T.init_cache(c, B, shape.seq_len))
        cache_sds = with_shardings(cache_sds, mesh,
                                   cache_pspecs(cache_sds, dp, mesh))
        token = jax.ShapeDtypeStruct((B, 1), jnp.int32,
                                     sharding=NamedSharding(mesh, P()))
        index = jax.ShapeDtypeStruct((), jnp.int32,
                                     sharding=NamedSharding(mesh, P()))

        def fn(params, token, cache, index):
            return T.decode_step(params, token, cache, index, c)

        return jax.jit(fn), (params_sds, token, cache_sds, index)

    fn, args = build(cfg)
    pair = tuple(build(_cost_cfg(cfg, k, shape.seq_len))
                 for k in (1, 2)) + (cfg.n_blocks,)
    return _measure(fn, args, step="decode", label=label, n_blocks_pair=pair)


def exp_llama_decode():
    """llama3-405b decode_32k: collective-bound (per-token FSDP weight
    all-gathers). Variant: 2D-TP weight-stationary serving."""
    mesh = make_production_mesh()
    out = [measure_decode("llama3-405b", "decode_32k", mesh,
                          "baseline fsdp+batch-sharded")]
    out.append(measure_decode_2dtp("llama3-405b", "decode_32k", mesh,
                                   "2D-TP weight-stationary"))
    return out


EXPERIMENTS = {
    "sync_strategies": exp_sync_strategies,
    "factorized_torus": exp_factorized_torus,
    "kimi_decode": exp_kimi_decode,
    "llama_decode": exp_llama_decode,
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--exp", default=None)
    ap.add_argument("--list", action="store_true")
    args = ap.parse_args()
    if args.list or not args.exp:
        print("\n".join(EXPERIMENTS))
        return
    save(args.exp, EXPERIMENTS[args.exp]())


if __name__ == "__main__":
    main()
