"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh)
combination against the production mesh and record memory / FLOPs /
collective-traffic analysis. No arrays are ever allocated: inputs are
ShapeDtypeStructs; this proves the distribution config is coherent.

    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma-7b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
    PYTHONPATH=src python -m repro.launch.dryrun --all --both-meshes

Results land in experiments/dryrun/<arch>__<shape>__<mesh>.json.
"""

# The production mesh needs 512 placeholder devices; jax locks the device
# count at first init, so this MUST precede every other import. The chaos
# *training* smoke (--chaos-train) actually executes steps, so it uses the
# 8-device test mesh instead -- 512 simulated devices would make every
# step interminable.
import os
import sys
_N_DEVICES = 8 if "--chaos-train" in sys.argv else 512
os.environ["XLA_FLAGS"] = (
    f"--xla_force_host_platform_device_count={_N_DEVICES} "
    + os.environ.get("XLA_FLAGS", ""))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import comm as comm_cfg
from repro.configs import registry
from repro.configs.shapes import SHAPES, ShapeConfig, long_context_variant
from repro.core import autotune, collectives
from repro.core import grad_sync as grad_sync_lib
from repro.core import losses
from repro.core.grad_sync import GradSyncConfig, sync_tree
from repro.core import lars as lars_lib
from repro.core.topology import select_grid
from repro.launch import hlo_stats
from repro import obs
from repro.testing.chaos import FaultPlan
from repro.launch.mesh import (cache_pspecs, dp_axes_of, make_production_mesh,
                               param_pspecs, with_shardings)
from repro.models import transformer as T

# archs whose params cannot be data-replicated even at TP=16: jit-auto
# fsdp sharding (ZeRO-style; DESIGN.md §3). The rest use the paper's
# explicit shard_map gradient sync.
FSDP_ARCHS = {"llama-3.2-vision-90b", "kimi-k2-1t-a32b", "llama3-405b",
              "gemma2-27b"}


# Strategy degradation (injected torus-link faults) is handled by the
# shared fallback chain in repro.core.grad_sync.resolve_sync_config;
# build_train records the resolved strategy + downgrade events and run_one
# writes them to the JSON.


def _bucket_bytes_arg(s: str):
    """--bucket-bytes parser: an int, or the literal "auto" sentinel."""
    return s if s == grad_sync_lib.AUTO else int(s)


def sds(shape, dtype, mesh=None, spec=None):
    sharding = NamedSharding(mesh, spec) if mesh is not None else None
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def batch_spec(batch: int, mesh) -> P:
    """Shard the batch over DP axes only when divisible (long_500k has B=1)."""
    dp = dp_axes_of(mesh)
    import numpy as np
    dp_size = int(np.prod([mesh.shape[a] for a in dp]))
    return P(dp) if batch % dp_size == 0 else P()


def arch_for(arch_id: str, shape: ShapeConfig) -> T.ArchConfig:
    cfg = registry.get(arch_id)
    if shape.name == "long_500k":
        cfg = long_context_variant(cfg)
    if shape.step == "train":
        cfg = dataclasses.replace(cfg, remat=True)
    return cfg


def _vision_sds(cfg, batch, mesh, dp):
    if not cfg.vision_tokens:
        return None
    return sds((batch, cfg.vision_tokens, cfg.cross_kv_dim), jnp.bfloat16,
               mesh, batch_spec(batch, mesh))


# ---------------------------------------------------------------------------
# step builders: return (jitted_fn, args_tree_of_SDS)
# ---------------------------------------------------------------------------

def build_train(arch_id, cfg, shape, mesh, sync_strategy="torus2d",
                fuse=None, bucket_bytes=0, down_axes=()):
    sync_info = {"effective": None, "events": [], "config": None}
    dp = dp_axes_of(mesh)
    fsdp = arch_id in FSDP_ARCHS
    params_sds = jax.eval_shape(lambda: T.init(jax.random.key(0), cfg))
    pspecs = param_pspecs(params_sds, fsdp=fsdp, mesh=mesh)
    params_sds = with_shardings(params_sds, mesh, pspecs)
    mom_sds = params_sds   # momentum mirrors params
    tokens = sds((shape.global_batch, shape.seq_len), jnp.int32, mesh, P(dp))
    labels = tokens
    vision = _vision_sds(cfg, shape.global_batch, mesh, dp)

    def loss_of(params, tokens, labels, vision):
        logits, aux = T.forward(params, tokens, cfg, vision=vision)
        return losses.label_smoothing_xent(logits, labels, 0.1) + 0.01 * aux

    if fsdp:
        # jit-auto data+tensor sharding: XLA derives the ZeRO collective
        # schedule from in/out shardings (beyond-paper regime, DESIGN.md §3)
        def step(params, mom, tokens, labels, vision):
            loss, grads = jax.value_and_grad(loss_of)(params, tokens, labels,
                                                      vision)
            new_p, new_m = lars_lib.update(
                params, grads, {"momentum": mom}, lr=1.0, momentum=0.9)
            return loss, new_p, new_m["momentum"]

        out_sh = jax.tree.map(lambda sp: NamedSharding(mesh, sp), pspecs)
        fn = jax.jit(step, out_shardings=(NamedSharding(mesh, P()),
                                          out_sh, out_sh))
    else:
        # the paper's technique: manual DP grad sync under shard_map.
        # fuse=False: leaves are model-sharded (TP), raveling them would
        # force gathers. comm_dtype: the TPU target exchanges gradients in
        # bf16 (paper: fp16), but XLA's *CPU* AllReducePromotion pass
        # crashes on bf16 partial all-reduces over model-sharded operands
        # ("Invalid binary instruction opcode copy"); on CPU we lower the
        # exchange in f32, so the CPU compile's gradient-exchange bytes are
        # twice what the chip's bf16 exchange moves.
        comm_dtype = (jnp.bfloat16 if jax.default_backend() == "tpu"
                      else jnp.float32)
        grid = select_grid(dp)
        # bucket_bytes shapes both paths: fused comm buckets (pure DP) and
        # the grouped small-leaf psums of the per-leaf (TP) path. "auto"
        # is resolved below against this mesh's fabric constants.
        gcfg = GradSyncConfig(strategy=sync_strategy,
                              fuse=False if fuse is None else fuse,
                              comm_dtype=comm_dtype,
                              bucket_bytes=bucket_bytes)
        # graceful degradation: injected torus-link faults
        # (--inject-faults) kill the per-axis phase decompositions --
        # downgrade along the chain and record it rather than abort the
        # audit (docs/robustness.md).
        gcfg, sync_events = grad_sync_lib.resolve_sync_config(
            gcfg, grid, mesh, dp, down_axes=down_axes, probe=False,
            params_like=params_sds, hw=comm_cfg.hw_for_mesh(mesh))
        layout = grad_sync_lib.bucket_layout(params_sds, gcfg)
        sync_info = {"effective": gcfg.strategy, "events": sync_events,
                     "config": {k: (v if isinstance(
                         v, (int, float, bool, str, type(None))) else str(v))
                         for k, v in dataclasses.asdict(gcfg).items()},
                     "expected_exchanges": len(layout),
                     "min_exchange_bytes": (min(b["nbytes"] for b in layout)
                                            if layout else None)}

        def step(params, mom, tokens, labels, vision):
            loss, grads = jax.value_and_grad(loss_of)(params, tokens, labels,
                                                      vision)
            grads = sync_tree(grads, grid, gcfg)
            new_p, new_m = lars_lib.update(
                params, grads, {"momentum": mom}, lr=1.0, momentum=0.9)
            return jax.lax.pmean(loss, dp), new_p, new_m["momentum"]

        smapped = jax.shard_map(
            step, mesh=mesh,
            in_specs=(P(), P(), P(dp), P(dp),
                      P(dp) if vision is not None else P()),
            out_specs=(P(), P(), P()),
            axis_names=frozenset(dp), check_vma=False)
        fn = jax.jit(smapped)

    # vision=None is an empty pytree: jit/shard_map treat it transparently
    return fn, (params_sds, mom_sds, tokens, labels, vision), sync_info


def build_prefill(arch_id, cfg, shape, mesh):
    dp = dp_axes_of(mesh)
    fsdp = arch_id in FSDP_ARCHS
    params_sds = jax.eval_shape(lambda: T.init(jax.random.key(0), cfg))
    params_sds = with_shardings(params_sds, mesh,
                                param_pspecs(params_sds, fsdp=fsdp, mesh=mesh))
    tokens = sds((shape.global_batch, shape.seq_len), jnp.int32, mesh,
                 batch_spec(shape.global_batch, mesh))
    vision = _vision_sds(cfg, shape.global_batch, mesh, dp)

    def fn(params, tokens, vision):
        return T.prefill(params, tokens, cfg, vision=vision)

    return jax.jit(fn, static_argnames=()), (params_sds, tokens, vision)


def build_decode(arch_id, cfg, shape, mesh):
    dp = dp_axes_of(mesh)
    fsdp = arch_id in FSDP_ARCHS
    params_sds = jax.eval_shape(lambda: T.init(jax.random.key(0), cfg))
    params_sds = with_shardings(params_sds, mesh,
                                param_pspecs(params_sds, fsdp=fsdp, mesh=mesh))
    B = shape.global_batch
    cache_sds = jax.eval_shape(
        lambda: T.init_cache(cfg, B, shape.seq_len))
    cache_sds = with_shardings(cache_sds, mesh,
                               cache_pspecs(cache_sds, dp, mesh))
    token = sds((B, 1), jnp.int32, mesh, batch_spec(B, mesh))
    index = sds((), jnp.int32, mesh, P())

    def fn(params, token, cache, index):
        return T.decode_step(params, token, cache, index, cfg)

    return jax.jit(fn), (params_sds, token, cache_sds, index)


def _audit_floor(sync_info: dict) -> int:
    """min_bytes floor for the HLO bucket audit, derived from the resolved
    schedule instead of a hardcoded constant: low enough to keep the
    smallest intended exchange (a sub-KiB fp32 group of a small model
    would otherwise vanish from the audit), high enough (>= 16 B) to drop
    scalar loss/metric psums. FSDP runs have no manual schedule and keep
    the historical 1 KiB floor."""
    smallest = sync_info.get("min_exchange_bytes")
    if smallest is None:
        return 1024
    return max(16, min(1024, int(smallest)))


def _audit_summary(hlo: str, sync_info: dict) -> dict:
    audit = hlo_stats.bucket_audit(hlo, min_bytes=_audit_floor(sync_info))
    return {"num_exchanges": audit["num_exchanges"],
            "min_bytes": audit["dropped"]["min_bytes"],
            "by_kind": audit["by_kind"],
            "dropped": {k: audit["dropped"][k]
                        for k in ("count", "bytes", "by_kind")}}


# ---------------------------------------------------------------------------
# runner
# ---------------------------------------------------------------------------

def run_one(arch_id: str, shape_name: str, multi_pod: bool,
            sync_strategy: str = "torus2d", out_dir: str = "experiments/dryrun",
            save: bool = True, quiet: bool = False,
            bucket_bytes: int | str = 0,
            fault_plan: FaultPlan | None = None) -> dict:
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    cfg = arch_for(arch_id, shape)
    down_axes = tuple(fault_plan.down_axes) if fault_plan is not None else ()

    sync_info = {"effective": None, "events": [], "config": None}
    t0 = time.time()
    if shape.step == "train":
        fn, args, sync_info = build_train(arch_id, cfg, shape, mesh,
                                          sync_strategy,
                                          bucket_bytes=bucket_bytes,
                                          down_axes=down_axes)
    elif shape.step == "prefill":
        fn, args = build_prefill(arch_id, cfg, shape, mesh)
    else:
        fn, args = build_decode(arch_id, cfg, shape, mesh)

    lowered = fn.lower(*args)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = hlo_stats.collective_stats(hlo)

    n_chips = mesh.devices.size
    # artifact provenance (docs/observability.md): a fresh run_id names
    # this invocation; the fingerprint hashes the *resolved* distribution
    # config (post-downgrade strategy included) so artifacts from
    # different runs of the same config join on it.
    mesh_summary = {a: int(mesh.shape[a]) for a in mesh.axis_names}
    result = {
        "arch": arch_id, "shape": shape_name, "mesh": mesh_name,
        "run_id": obs.new_run_id(),
        "config_fingerprint": obs.fingerprint({
            "arch": arch_id, "shape": shape_name, "mesh": mesh_summary,
            "grad_sync": sync_info["config"],
            "fsdp": arch_id in FSDP_ARCHS}),
        "mesh_summary": mesh_summary,
        "grad_sync_config": sync_info["config"],
        "step": shape.step, "chips": int(n_chips),
        "fsdp": arch_id in FSDP_ARCHS,
        "sync_strategy": sync_strategy if shape.step == "train" else None,
        "sync_strategy_effective": sync_info["effective"],
        "sync_downgrade_events": sync_info["events"] or None,
        "fault_injection": ({"down_axes": list(down_axes)}
                            if down_axes else None),
        "bucket_bytes": bucket_bytes if shape.step == "train" else None,
        "bucket_bytes_resolved": ((sync_info["config"] or {}).get(
            "bucket_bytes") if shape.step == "train" else None),
        "expected_exchanges": sync_info.get("expected_exchanges"),
        "bucket_audit": (_audit_summary(hlo, sync_info)
                         if shape.step == "train" else None),
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
        },
        "cost": {
            "flops": cost.get("flops"),
            "bytes_accessed": cost.get("bytes accessed"),
        },
        "collectives": coll,
        "model_params": cfg.num_params(),
        "active_params": cfg.active_params(),
        "grad_comm_dtype": ("f32-on-cpu(bf16-on-tpu)"
                            if shape.step == "train" else None),
    }
    if save:
        os.makedirs(out_dir, exist_ok=True)
        path = os.path.join(out_dir, f"{arch_id}__{shape_name}__{mesh_name}.json")
        with open(path, "w") as f:
            json.dump(result, f, indent=1)
    if not quiet:
        mb = (result["memory"]["temp_bytes"] or 0) / n_chips / 2**30
        print(f"[OK] {arch_id:22s} {shape_name:12s} {mesh_name:10s} "
              f"lower {t_lower:5.1f}s compile {t_compile:6.1f}s "
              f"flops {cost.get('flops', 0):.3e} "
              f"coll {coll['total_bytes'] / 2**30:.2f}GiB "
              f"temp/chip {mb:.2f}GiB")
    return result


def sweep_bucket_bytes(arch_id: str, multi_pod: bool = False,
                       sync_strategy: str = "torus2d",
                       out_dir: str = "experiments/dryrun",
                       save: bool = True, smoke_arch: bool = False,
                       candidates: list[int] | None = None,
                       max_hlo_buckets: int = 256,
                       slack: float = 0.05) -> dict:
    """Empirical bucket-size sweep: compile the *sync-only* program (fused
    bucketed ``sync_tree`` under a fully-manual shard_map)
    at production scale for each candidate ``bucket_bytes``, audit the
    compiled HLO's independent exchanges, and pair every row with the
    alpha-beta cost model. The autotuner's pick
    (``autotune.recommend_bucket_bytes`` over the union of the sweep's
    candidates) is then gated against the sweep:

    * its cost-model ``exposed_seconds`` is within 10% of the sweep's best,
    * it strictly beats both ``bucket_bytes=0`` (fused) and the legacy
      hand-set 4 MiB constant,
    * it lands inside the sweep's measured-optimum bracket.

    Writes ``bucket_sweep__<arch>__<mesh>.json``; raises ``SystemExit``
    when a gate fails -- the CI ``bucket-sweep`` job runs exactly this on
    the smoke config. Candidates whose schedule exceeds ``max_hlo_buckets``
    skip compilation (cost-model row only, with the skip recorded): a
    full-size arch near the knee can need thousands of buckets, which the
    sweep reports rather than silently compiles for an hour.
    """
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    cfg = (registry.get_smoke(arch_id) if smoke_arch
           else registry.get(arch_id))
    dp = dp_axes_of(mesh)
    grid = select_grid(dp)
    x, y = grid.sizes(mesh)
    comm_dtype = (jnp.bfloat16 if jax.default_backend() == "tpu"
                  else jnp.float32)
    hw = comm_cfg.hw_for_mesh(mesh)

    # fully-manual over ALL mesh axes (model axis included) -> grads must
    # be replicated; the fused pure-DP path is exactly that regime.
    gcfg0 = GradSyncConfig(strategy=sync_strategy, fuse=True,
                           comm_dtype=comm_dtype, bucket_bytes=0)
    gcfg0, resolve_events = grad_sync_lib.resolve_sync_config(
        gcfg0, grid, mesh, mesh.axis_names, probe=False)
    strategy = gcfg0.strategy

    params_sds = jax.eval_shape(lambda: T.init(jax.random.key(0), cfg))
    params_sds = jax.tree.map(
        lambda s: sds(s.shape, s.dtype, mesh, P()), params_sds)
    layout0 = grad_sync_lib.bucket_layout(params_sds, gcfg0)
    total_bytes = sum(b["nbytes"] for b in layout0)
    knee = autotune.analytic_knee_bytes(strategy, x, y, hw)
    default_grid = autotune.candidate_bucket_bytes(knee, total_bytes)
    cand = sorted(set(candidates)) if candidates else default_grid

    rows = []
    for b in cand:
        gcfg = dataclasses.replace(gcfg0, bucket_bytes=b)
        layout = grad_sync_lib.bucket_layout(params_sds, gcfg)
        floor = max(16, min(1024, min(e["nbytes"] for e in layout)))
        m = collectives.bucketed_comm_cost_model(
            strategy, total_bytes, b, x, y, hw.link_bw, hw.latency_s,
            backward_seconds=hw.backward_seconds)
        row = {"bucket_bytes": b, "num_buckets": len(layout),
               "exposed_seconds": m["exposed_seconds"],
               "serial_seconds": m["serial_seconds"]}
        if len(layout) <= max_hlo_buckets:
            t0 = time.time()

            def sync_only(grads, _gcfg=gcfg):
                return sync_tree(grads, grid, _gcfg)

            smapped = jax.shard_map(
                sync_only, mesh=mesh, in_specs=P(), out_specs=P(),
                axis_names=frozenset(mesh.axis_names), check_vma=False)
            hlo = jax.jit(smapped).lower(params_sds).compile().as_text()
            audit = hlo_stats.bucket_audit(hlo, min_bytes=floor)
            row.update({
                "num_exchanges": audit["num_exchanges"],
                "audit_by_kind": audit["by_kind"],
                "audit_dropped": {k: audit["dropped"][k]
                                  for k in ("count", "bytes", "min_bytes")},
                "hlo_matches_schedule":
                    audit["num_exchanges"] == len(layout),
                "compile_s": round(time.time() - t0, 1),
            })
        else:
            row["hlo_skipped"] = (f"{len(layout)} buckets > "
                                  f"max_hlo_buckets={max_hlo_buckets}; "
                                  "cost-model row only")
        rows.append(row)
        print(f"[sweep] bucket_bytes={b:>12d}  buckets={len(layout):>5d}  "
              f"exposed={m['exposed_seconds'] * 1e6:9.1f}us  "
              f"hlo_exchanges={row.get('num_exchanges', '-')}")

    # the "auto" pick, evaluated over the union of the sweep's candidates
    # and the default grid -- same rule resolve_sync_config applies, so
    # the <=10%-of-best gate holds whenever the model is self-consistent
    union = sorted(set(cand) | set(default_grid))
    rec = autotune.recommend_bucket_bytes(strategy, x, y, hw,
                                          total_bytes=total_bytes,
                                          candidates=union, slack=slack)
    refined = autotune.refine_from_sweep(rows, strategy, x, y, hw,
                                         total_bytes=total_bytes,
                                         slack=slack)

    def exposed_at(b):
        return collectives.bucketed_comm_cost_model(
            strategy, total_bytes, b, x, y, hw.link_bw, hw.latency_s,
            backward_seconds=hw.backward_seconds)["exposed_seconds"]

    best_row = min(rows, key=lambda r: r["exposed_seconds"])
    checks = {
        "auto_within_10pct_of_sweep_best":
            rec["exposed_seconds"] <= 1.10 * best_row["exposed_seconds"],
        "auto_beats_fused":
            rec["exposed_seconds"] < exposed_at(0),
        "auto_beats_legacy_4mib":
            rec["exposed_seconds"]
            < exposed_at(autotune.LEGACY_DEFAULT_BUCKET_BYTES),
        "auto_within_sweep_bracket":
            autotune.pick_within_bracket(rec["bucket_bytes"],
                                         refined["bracket"]),
    }
    result = {
        "mode": "bucket_sweep", "arch": arch_id,
        "arch_variant": "smoke" if smoke_arch else "full",
        "mesh": mesh_name, "chips": int(mesh.devices.size),
        "strategy_requested": sync_strategy, "strategy": strategy,
        "resolve_events": resolve_events or None,
        "comm_dtype": str(jnp.dtype(comm_dtype)),
        "total_bytes": total_bytes,
        "hw": dataclasses.asdict(hw),
        "analytic_knee_bytes": knee,
        "rows": rows,
        "auto": {"bucket_bytes": rec["bucket_bytes"],
                 "num_buckets": rec["num_buckets"],
                 "exposed_seconds": rec["exposed_seconds"]},
        "refined": refined,
        "checks": checks,
    }
    if save:
        os.makedirs(out_dir, exist_ok=True)
        path = os.path.join(out_dir,
                            f"bucket_sweep__{arch_id}__{mesh_name}.json")
        with open(path, "w") as f:
            json.dump(result, f, indent=1)
        print(f"[sweep] wrote {path}")
    failed = [k for k, ok in checks.items() if not ok]
    if failed:
        raise SystemExit(
            f"[sweep] FAILED gates: {failed}; auto pick "
            f"{rec['bucket_bytes']} (exposed "
            f"{rec['exposed_seconds'] * 1e6:.1f}us) vs sweep best "
            f"{best_row['bucket_bytes']} "
            f"({best_row['exposed_seconds'] * 1e6:.1f}us)")
    print(f"[sweep] OK: auto bucket_bytes={rec['bucket_bytes']} "
          f"({rec['num_buckets']} buckets, "
          f"exposed {rec['exposed_seconds'] * 1e6:.1f}us) within "
          f"bracket [{refined['bracket']['low']}, "
          f"{refined['bracket']['high']}] of sweep best "
          f"{refined['bracket']['best_bucket_bytes']}")
    return result


def chaos_train(fault_step: int, out_dir: str = "experiments/dryrun",
                max_steps: int = 8, metrics_out: str | None = None,
                trace_out: str | None = None) -> dict:
    """Elastic-recovery smoke: run a real (tiny) training loop on the
    8-device mesh, kill torus axis "dy" permanently at ``fault_step``, and
    require the run to finish every planned step via a mid-run
    torus2d->ring downgrade + checkpoint rollback (docs/robustness.md,
    "Elastic recovery"). Writes ``<out_dir>/chaos_train.json``; raises
    ``SystemExit`` if the run aborts or the recovery is not visible in the
    event stream -- the CI chaos-smoke job gates on exactly this.

    ``fault_step < 0`` runs the same loop **fault-free** (no FaultPlan)
    with inverted gates -- completion with zero recovery/downgrade events
    and a zero ``elastic/recoveries`` counter -- and writes
    ``train_smoke.json`` instead. ``metrics_out`` / ``trace_out`` route
    the run's telemetry (metrics JSONL, Chrome trace) to files
    (docs/observability.md); the recovery counters in the JSONL's summary
    row are what CI cross-checks against the event-stream gates.
    """
    import shutil
    import tempfile

    import numpy as np

    from repro.core.schedules import BatchSchedule, BatchStage
    from repro.core.batch_control import build_plan
    from repro.data.synthetic import SyntheticImageNet
    from repro.models import resnet
    from repro.obs import ObsConfig, Telemetry
    from repro.train.state import TrainState
    from repro.train.trainer import Trainer, TrainerConfig

    faulty = fault_step >= 0
    tag = "chaos-train" if faulty else "train-smoke"
    mesh = jax.make_mesh((2, 4), ("dy", "dx"))
    cfg = resnet.ResNetConfig.tiny(num_classes=4)
    data = SyntheticImageNet(num_classes=4, image_size=32, noise=0.3)

    def loss_fn(params, batch, dp_axes):
        images, labels = batch
        logits = resnet.apply(params, images, cfg, dp_axes=dp_axes)
        return (losses.label_smoothing_xent(logits, labels, 0.1),
                jnp.zeros((), jnp.float32))

    plan = build_plan(BatchSchedule((BatchStage(0, 1.0, 2),)),
                      dataset_size=256, n_workers=8, max_steps=max_steps)
    obs_cfg = ObsConfig(metrics_path=metrics_out, trace_path=trace_out)
    tcfg = TrainerConfig(grad_sync=GradSyncConfig(strategy="torus2d"),
                         log_every=1, ckpt_every_steps=2, ckpt_keep_last=10,
                         retry_backoff_s=1e-4, obs=obs_cfg)
    fault_plan = (FaultPlan(axis_down_events=(("dy", fault_step),))
                  if faulty else None)
    ckpt_dir = tempfile.mkdtemp(prefix="chaos_train_ckpt_")
    completed, error = False, None
    state = TrainState.create(resnet.init(jax.random.key(0), cfg))
    # caller-owned telemetry: the registry snapshot must survive run() so
    # the result can record the recovery counters next to the event gates
    tel = Telemetry(obs_cfg, meta={
        "source": tag, "fault_step": fault_step, "planned_steps": max_steps})
    trainer = Trainer(mesh=mesh, dp_axes=("dy", "dx"), loss_fn=loss_fn,
                      cfg=tcfg, plan=plan,
                      data_fn=lambda i, gb: data.batch(i, gb),
                      checkpoint_dir=ckpt_dir, fault_plan=fault_plan,
                      telemetry=tel)
    t0 = time.time()
    try:
        state, history = trainer.run(state)
        completed = True
    except Exception as e:  # noqa: BLE001 -- the abort IS the test failure
        error = repr(e)
        history = []
        traceback.print_exc()
    finally:
        tel.close()   # summary row + Chrome trace, even on abort
        shutil.rmtree(ckpt_dir, ignore_errors=True)

    events = [h for h in history if h.get("kind") == "event"]
    downgrades = [e for e in events if e["event"] == "grad_sync_downgrade"]
    recoveries = [e for e in events if e["event"] == "elastic_recovery"]
    steps_done = int(state.step) if completed else 0
    losses_seen = [h["loss"] for h in history if "loss" in h]
    snap = tel.registry.snapshot()

    def counter_of(name):
        return int(snap.get(name, {}).get("value", 0))

    result = {
        "mode": "chaos_train" if faulty else "train_smoke",
        "mesh": "2x4", "chips": 8, "run_id": tel.run_id,
        "fault": ({"axis": "dy", "down_from_step": fault_step}
                  if faulty else None),
        "planned_steps": max_steps, "steps": steps_done,
        "completed": completed, "error": error,
        "wall_s": round(time.time() - t0, 1),
        "loss_finite": bool(np.all(np.isfinite(losses_seen))),
        "metrics_out": metrics_out, "trace_out": trace_out,
        "recovery_counters": {
            "elastic/recoveries": counter_of("elastic/recoveries"),
            "elastic/permanent_failures":
                counter_of("elastic/permanent_failures"),
            "events/elastic_recovery": counter_of("events/elastic_recovery"),
        },
        "events": events,
    }
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(
        out_dir, "chaos_train.json" if faulty else "train_smoke.json")
    with open(path, "w") as f:
        json.dump(result, f, indent=1)
    print(f"[{tag}] wrote {path}")

    problems = []
    if not completed:
        problems.append(f"run aborted: {error}")
    elif steps_done != max_steps:
        problems.append(f"finished {steps_done}/{max_steps} steps")
    if not result["loss_finite"]:
        problems.append("non-finite loss in history")
    if faulty:
        if not any(d.get("context") == "elastic" for d in downgrades):
            problems.append("no mid-run grad_sync_downgrade event")
        if not recoveries:
            problems.append("no elastic_recovery event")
        if counter_of("elastic/recoveries") < 1:
            problems.append("elastic/recoveries counter is zero")
    else:
        if downgrades or recoveries:
            problems.append(
                f"fault-free run saw {len(downgrades)} downgrade / "
                f"{len(recoveries)} recovery events")
        if counter_of("elastic/recoveries") != 0:
            problems.append("fault-free run has nonzero elastic/recoveries")
    if problems:
        raise SystemExit(f"[{tag}] FAILED: " + "; ".join(problems))
    if faulty:
        print(f"[{tag}] OK: axis dy died at step {fault_step}, run "
              f"finished {steps_done}/{max_steps} steps "
              f"(downgrade {downgrades[0]['from']}->{downgrades[0]['to']}, "
              f"rollback to step {recoveries[0]['step']})")
    else:
        print(f"[{tag}] OK: fault-free run finished "
              f"{steps_done}/{max_steps} steps, zero recovery events")
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--sync", default="torus2d",
                    choices=["psum", "ring", "hierarchical", "torus2d"])
    ap.add_argument("--bucket-bytes", type=_bucket_bytes_arg, default=0,
                    help="gradient-sync bucket size target; 0 = single fused "
                         "buffer; 'auto' = autotuned at resolve time "
                         "(see docs/gradient_sync.md)")
    ap.add_argument("--sweep-bucket-bytes", action="store_true",
                    help="bucket-size sweep: compile the sync-only program "
                         "per candidate bucket_bytes at production scale, "
                         "audit the HLO, gate the autotuner's pick against "
                         "the measured optimum bracket, and save "
                         "bucket_sweep__<arch>__<mesh>.json")
    ap.add_argument("--smoke-arch", action="store_true",
                    help="--sweep-bucket-bytes: use the arch's smoke "
                         "variant (CI-sized; full archs near the knee need "
                         "thousands of buckets, which the sweep skips "
                         "compiling)")
    ap.add_argument("--inject-faults", action="store_true",
                    help="mark the leading DP torus axis down "
                         "(testing/chaos.FaultPlan): the grad-sync strategy "
                         "must degrade along the fallback chain instead of "
                         "aborting; events land in the JSON "
                         "(docs/robustness.md)")
    ap.add_argument("--chaos-train", action="store_true",
                    help="elastic-recovery smoke: run a real tiny training "
                         "loop (8-device mesh), kill a torus axis "
                         "permanently mid-run, and require completion via "
                         "mid-run downgrade + checkpoint rollback")
    ap.add_argument("--fault-step", type=int, default=3,
                    help="step at which --chaos-train kills the axis; "
                         "negative runs the same loop fault-free "
                         "(train_smoke.json, inverted gates)")
    ap.add_argument("--metrics-out", default=None,
                    help="--chaos-train: write the run's metrics/event "
                         "JSONL here (docs/observability.md)")
    ap.add_argument("--trace-out", default=None,
                    help="--chaos-train: write a Chrome trace_event JSON "
                         "of the run's host spans here")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    if args.chaos_train:
        chaos_train(args.fault_step, args.out,
                    metrics_out=args.metrics_out, trace_out=args.trace_out)
        return

    if args.sweep_bucket_bytes:
        if not args.arch:
            raise SystemExit("--sweep-bucket-bytes needs --arch")
        sweep_bucket_bytes(args.arch, multi_pod=args.multi_pod,
                           sync_strategy=args.sync, out_dir=args.out,
                           smoke_arch=args.smoke_arch)
        return

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    archs = registry.ARCH_IDS if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]

    failures = []
    for mp in meshes:
        for arch_id in archs:
            for shape_name in shapes:
                mesh_name = "pod2x16x16" if mp else "pod16x16"
                path = os.path.join(
                    args.out, f"{arch_id}__{shape_name}__{mesh_name}.json")
                if args.skip_existing and os.path.exists(path):
                    print(f"[SKIP] {arch_id} {shape_name} {mesh_name}")
                    continue
                try:
                    fault_plan = None
                    if args.inject_faults:
                        # down the leading DP axis: the slow inter-pod axis
                        # on the 2-pod mesh, the whole data ring otherwise
                        mesh_dp = ("pod", "data") if mp else ("data",)
                        fault_plan = FaultPlan(down_axes=(mesh_dp[0],))
                    run_one(arch_id, shape_name, mp, args.sync, args.out,
                            bucket_bytes=args.bucket_bytes,
                            fault_plan=fault_plan)
                except Exception as e:  # noqa: BLE001
                    failures.append((arch_id, shape_name, mp, repr(e)))
                    print(f"[FAIL] {arch_id} {shape_name} multi_pod={mp}: {e}")
                    traceback.print_exc()
    if failures:
        raise SystemExit(f"{len(failures)} dry-run failures: {failures}")
    print("ALL DRY-RUNS PASSED")


if __name__ == "__main__":
    main()
