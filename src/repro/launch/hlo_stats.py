"""Parse collective traffic and op stats out of post-SPMD HLO text.

``collective_bytes`` is not in ``compiled.cost_analysis()``; we recover it
from the optimized HLO: every all-reduce / all-gather / reduce-scatter /
all-to-all / collective-permute op's *output* bytes are summed per op kind
(output bytes == bytes received per device, the roofline-relevant number;
for reduce-scatter the on-wire volume per device is (n-1)/n of the input --
we report output bytes and note the convention in EXPERIMENTS.md).
"""

from __future__ import annotations

import re
from collections import defaultdict

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

# e.g.:  %all-gather.3 = bf16[16,4096]{1,0} all-gather(...)
#        ROOT %r = (f32[8]{0}, f32[8]{0}) tuple(...)
_OP_RE = re.compile(
    r"=\s*(?:\()?\s*([a-z0-9]+)\[([\d,]*)\][^ ]*\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(([^\n]*)", re.M)

_GROUPS_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _group_size(rest: str) -> int:
    m = _GROUPS_RE.search(rest)
    if m:
        return m.group(1).count(",") + 1
    m = _GROUPS_IOTA_RE.search(rest)
    if m:                      # [n_groups, group_size]<=[...]
        return int(m.group(2))
    return 2


def _wire_bytes(kind: str, out_bytes: int, n: int) -> float:
    """Bytes per device on the wire for a ring realization of the op.

    all-reduce: 2*(n-1)/n * size; all-gather: (n-1)/n * output;
    reduce-scatter: (n-1) * output (input is n*output);
    all-to-all: (n-1)/n * size; collective-permute: full size.
    """
    if n <= 1:
        return 0.0
    if kind == "all-reduce":
        return 2.0 * (n - 1) / n * out_bytes
    if kind == "all-gather":
        return (n - 1) / n * out_bytes
    if kind == "reduce-scatter":
        return float((n - 1) * out_bytes)
    if kind == "all-to-all":
        return (n - 1) / n * out_bytes
    return float(out_bytes)    # collective-permute


def _nbytes(dtype: str, dims: str) -> int:
    n = _DTYPE_BYTES.get(dtype, 4)
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n


def collective_stats(hlo_text: str) -> dict:
    """-> {op_kind: {"count", "bytes"}, "by_dtype": {dt: bytes},
    "total_bytes", "total_count"}. Per-dtype split lets the roofline apply
    the f32->bf16 exchange correction for the CPU-lowered gradient sync."""
    out: dict = {k: {"count": 0, "bytes": 0, "wire_bytes": 0.0}
                 for k in _COLLECTIVES}
    by_dtype: dict[str, int] = defaultdict(int)
    wire_by_dtype: dict[str, float] = defaultdict(float)
    for m in _OP_RE.finditer(hlo_text):
        dtype, dims, kind, rest = m.groups()
        nb = _nbytes(dtype, dims)
        wb = _wire_bytes(kind, nb, _group_size(rest))
        out[kind]["count"] += 1
        out[kind]["bytes"] += nb
        out[kind]["wire_bytes"] += wb
        by_dtype[dtype] += nb
        wire_by_dtype[dtype] += wb
    out["by_dtype"] = dict(by_dtype)
    out["wire_by_dtype"] = dict(wire_by_dtype)
    out["total_bytes"] = sum(v["bytes"] for k, v in out.items()
                             if isinstance(v, dict) and "bytes" in v)
    out["total_wire_bytes"] = sum(v["wire_bytes"] for k, v in out.items()
                                  if isinstance(v, dict) and "wire_bytes" in v)
    out["total_count"] = sum(v["count"] for k, v in out.items()
                             if isinstance(v, dict) and "count" in v)
    return out


_KIND_RE = re.compile(
    r"\s(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")


def collective_counts(hlo_text: str) -> dict[str, int]:
    """Collective instructions per kind, tuple-shaped ones included (an
    async ``-start``/``-done`` pair counts once)."""
    counts = dict.fromkeys(_COLLECTIVES, 0)
    for m in _KIND_RE.finditer(hlo_text):
        counts[m.group(1)] += 1
    return counts


def collective_schedule(hlo_text: str) -> list[dict]:
    """Every collective op in program order: {kind, dtype, nbytes, group_size}.

    Unlike :func:`collective_stats` (aggregates), this keeps the per-op
    sequence so a bucketed gradient exchange can be audited op by op.
    """
    out = []
    for m in _OP_RE.finditer(hlo_text):
        dtype, dims, kind, rest = m.groups()
        out.append({"kind": kind, "dtype": dtype,
                    "nbytes": _nbytes(dtype, dims),
                    "group_size": _group_size(rest)})
    return out


def bucket_audit(hlo_text: str, min_bytes: int = 0) -> dict:
    """Audit the gradient-sync bucket schedule in compiled HLO.

    Counts *independent reduction exchanges*: for the torus2d/ring/
    hierarchical xla lowerings each bucket compiles to its own
    reduce-scatter (+ all-reduce + all-gather) chain, and for psum to its
    own all-reduce -- so ``num_exchanges = max(#reduce-scatter,
    #all-reduce)`` over ops of at least ``min_bytes`` (filter out tiny
    metric/loss psums). A fully fused sync shows 1; a multi-bucket sync
    shows one per bucket, which is the structural proof that XLA *can*
    overlap each exchange with remaining backward compute.

    Ops below the floor are not silently hidden: the ``dropped`` entry
    reports their count/bytes (and per-kind split) so an audit whose floor
    swallowed real gradient buckets -- e.g. the sub-KiB fp32 group of a
    small model -- is visible in the artifact. Callers should derive
    ``min_bytes`` from the resolved bucket schedule (see
    ``launch.dryrun``), not hardcode it.
    """
    all_ops = collective_schedule(hlo_text)
    sched = [op for op in all_ops if op["nbytes"] >= min_bytes]
    dropped_ops = [op for op in all_ops if op["nbytes"] < min_bytes]
    by_kind: dict[str, dict] = defaultdict(lambda: {"count": 0, "bytes": 0})
    for op in sched:
        by_kind[op["kind"]]["count"] += 1
        by_kind[op["kind"]]["bytes"] += op["nbytes"]
    dropped_by_kind: dict[str, dict] = defaultdict(
        lambda: {"count": 0, "bytes": 0})
    for op in dropped_ops:
        dropped_by_kind[op["kind"]]["count"] += 1
        dropped_by_kind[op["kind"]]["bytes"] += op["nbytes"]
    n_rs = by_kind["reduce-scatter"]["count"]
    n_ar = by_kind["all-reduce"]["count"]
    return {
        "num_exchanges": max(n_rs, n_ar),
        "by_kind": dict(by_kind),
        "ops": sched,
        "dropped": {
            "min_bytes": min_bytes,
            "count": len(dropped_ops),
            "bytes": sum(op["nbytes"] for op in dropped_ops),
            "by_kind": dict(dropped_by_kind),
        },
    }


def op_histogram(hlo_text: str, top: int = 15) -> list[tuple[str, int]]:
    """Rough instruction histogram (op name -> count) for schedule audits."""
    counts: dict[str, int] = defaultdict(int)
    for m in re.finditer(r"=\s*(?:\()?[a-z0-9]+\[[^\]]*\][^ ]*\s*([a-z][\w-]*)\(",
                         hlo_text):
        counts[m.group(1)] += 1
    return sorted(counts.items(), key=lambda kv: -kv[1])[:top]
