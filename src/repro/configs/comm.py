"""Per-mesh communication hardware models and bucket-size defaults.

The bucket autotuner (``repro.core.autotune``) needs three constants per
fabric -- link bandwidth, per-step latency, and the backward-pass wall
time it overlaps with. This module is the single place those constants
live for the production meshes (``launch.mesh.make_production_mesh``), so
``launch.dryrun`` and the trainer resolve ``bucket_bytes="auto"`` against
the same numbers.

The per-arch default is simply ``"auto"`` for every arch that runs the
manual grad sync: the point of the autotuner is that no arch should carry
a hand-set byte count. FSDP archs (``launch.dryrun.FSDP_ARCHS``) get ``0``
-- XLA derives their collective schedule from shardings and
``bucket_bytes`` never reaches a sync.
"""

from __future__ import annotations

from repro.core.autotune import HardwareModel

#: Fabric constants per production mesh (paper-target numbers; see
#: docs/gradient_sync.md "Autotuning bucket_bytes"). The 2-pod mesh pays
#: the slower inter-pod links on its vertical phase, modeled here as a
#: lower effective bandwidth and a higher per-step latency.
HW_BY_MESH: dict[str, HardwareModel] = {
    "pod16x16": HardwareModel(link_bw=50e9, latency_s=1e-6,
                              backward_seconds=0.040, name="pod16x16"),
    "pod2x16x16": HardwareModel(link_bw=25e9, latency_s=5e-6,
                                backward_seconds=0.040, name="pod2x16x16"),
}


def hw_for_mesh(mesh, backward_seconds: float | None = None) -> HardwareModel:
    """HardwareModel for a mesh (object or name); unknown meshes fall back
    to the single-pod constants. ``backward_seconds`` overrides the default
    overlap window when the caller has a per-arch estimate."""
    name = mesh if isinstance(mesh, str) else (
        "pod2x16x16" if "pod" in getattr(mesh, "axis_names", ()) else
        "pod16x16")
    hw = HW_BY_MESH.get(name, HW_BY_MESH["pod16x16"])
    if backward_seconds is not None:
        import dataclasses
        hw = dataclasses.replace(hw, backward_seconds=backward_seconds)
    return hw


def backward_seconds_estimate(step_flops: float, n_chips: int,
                              peak_flops_per_chip: float = 90e12,
                              mfu: float = 0.4) -> float:
    """Rough backward wall time from a compiled step's FLOPs.

    Backward is ~2/3 of a train step's FLOPs (fwd + 2x in bwd); divide by
    the fleet's realizable throughput (peak x an assumed MFU). Only the
    *scale* matters -- ``backward_seconds`` moves where overlap saturates
    in the cost model, not the latency/bandwidth knee -- so a 2x error
    here barely moves the picked bucket size.
    """
    if step_flops <= 0 or n_chips <= 0:
        return HW_BY_MESH["pod16x16"].backward_seconds
    return (2.0 / 3.0) * step_flops / (n_chips * peak_flops_per_chip * mfu)


def default_bucket_bytes(arch_id: str, fsdp: bool = False) -> int | str:
    """Per-arch ``GradSyncConfig.bucket_bytes`` default: ``"auto"`` for
    every manually-synced arch, ``0`` for FSDP archs (no manual sync)."""
    return 0 if fsdp else "auto"
