"""Scan-aware cost extrapolation for the dry-run artifacts.

``compiled.cost_analysis()`` (and the HLO text) count a ``lax.scan`` body
ONCE, so per-layer costs of the scanned block stack are undercounted by a
factor of n_blocks. This pass recovers the true per-step cost with a
two-point linear fit:

    lower the same step with n_blocks = 1 and = 2
        (and chunking disabled -- q_chunk=0, ssm_chunk=seq -- so no *inner*
         while loop hides cost either)
    body  = cost(2) - cost(1)
    total = cost(1) + body * (n_blocks - 1)

and merges {flops, bytes_accessed, collective bytes (per dtype)} back into
each experiments/dryrun/*.json as the ``cost_true`` field used by
benchmarks/roofline.py.

    PYTHONPATH=src python -m repro.launch.cost_extrapolate [--only <arch>]
"""

import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import argparse
import dataclasses
import glob
import json


from repro.configs.shapes import SHAPES
from repro.launch import hlo_stats
from repro.launch.mesh import make_production_mesh
from repro.models import transformer as T


def _cost_cfg(cfg: T.ArchConfig, k_blocks: int, seq_len: int) -> T.ArchConfig:
    n_layers = cfg.n_prefix + k_blocks * len(cfg.pattern)
    return dataclasses.replace(cfg, n_layers=n_layers, scan_blocks=False,
                               q_chunk_unroll=True, ssm_unroll=True)


def _extract(compiled):
    ca = compiled.cost_analysis()
    coll = hlo_stats.collective_stats(compiled.as_text())
    return {
        "flops": ca.get("flops", 0.0),
        "bytes_accessed": ca.get("bytes accessed", 0.0),
        "coll_total": float(coll["total_bytes"]),
        "coll_f32": float(coll["by_dtype"].get("f32", 0)),
        "coll_wire": float(coll["total_wire_bytes"]),
        "coll_wire_f32": float(coll["wire_by_dtype"].get("f32", 0)),
    }


def extrapolate(arch_id: str, shape_name: str, multi_pod: bool) -> dict:
    from repro.launch import dryrun as D
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    base_cfg = D.arch_for(arch_id, shape)

    costs = {}
    for k in (1, 2):
        cfg = _cost_cfg(base_cfg, k, shape.seq_len)
        if shape.step == "train":
            fn, args = D.build_train(arch_id, cfg, shape, mesh)
        elif shape.step == "prefill":
            fn, args = D.build_prefill(arch_id, cfg, shape, mesh)
        else:
            fn, args = D.build_decode(arch_id, cfg, shape, mesh)
        costs[k] = _extract(fn.lower(*args).compile())

    nb = base_cfg.n_blocks
    out = {}
    for key in costs[1]:
        body = costs[2][key] - costs[1][key]
        out[key] = costs[1][key] + body * (nb - 1)
        out[f"{key}_body"] = body
    out["n_blocks"] = nb
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="arch substring filter")
    ap.add_argument("--dir", default="experiments/dryrun")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    for path in sorted(glob.glob(os.path.join(args.dir, "*.json"))):
        with open(path) as f:
            rec = json.load(f)
        if args.only and args.only not in rec["arch"]:
            continue
        if "cost_true" in rec and not args.force:
            print(f"[skip] {os.path.basename(path)}")
            continue
        try:
            ct = extrapolate(rec["arch"], rec["shape"],
                             rec["mesh"] == "pod2x16x16")
            rec["cost_true"] = ct
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
            print(f"[ok] {os.path.basename(path)} "
                  f"flops {rec['cost']['flops']:.2e} -> {ct['flops']:.2e}")
        except Exception as e:  # noqa: BLE001
            print(f"[fail] {os.path.basename(path)}: {e!r}")


if __name__ == "__main__":
    main()
