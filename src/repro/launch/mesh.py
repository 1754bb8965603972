"""Production meshes and parameter-sharding rules.

``make_production_mesh``: the fixed target -- 16x16 = 256 chips per pod
(``data`` x ``model``), 2 pods = 512 chips multi-pod (``pod`` axis leading).

``param_pspecs``: path-based sharding rules (megatron-style TP over
``model`` + optional fsdp over ``data``). Scanned-block leaves carry a
leading (n_blocks,) dim -> specs are right-aligned against leaf rank.
"""

from __future__ import annotations

import re

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def dp_axes_of(mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a != "model")


# ---------------------------------------------------------------------------
# Parameter sharding rules
# ---------------------------------------------------------------------------

# (regex on the parameter path) -> spec for the *trailing* dims of the leaf.
# "F" is replaced by the fsdp axis ("data") when fsdp is on, else None.
_RULES: tuple[tuple[str, tuple], ...] = (
    (r"embedding$", ("model", "F")),              # (V, d) vocab-sharded
    (r"unembed/kernel$", ("F", "model")),         # (d, V)
    (r"(q|k|v|up|gate|in_x|in_gate)/kernel$", ("F", "model")),
    (r"(o|down|out|out_proj)/kernel$", ("model", "F")),
    (r"experts/(up|gate)$", ("model", "F", None)),  # (E, d, f) expert-parallel
    (r"experts/down$", ("model", None, "F")),       # (E, f, d)
    (r"router/kernel$", (None, None)),
    (r"in_proj/kernel$", ("model", None)),        # ssd packed proj: row-parallel
    (r"conv/kernel$", (None, None)),
    (r"(rg|ig)_kernel$", (None, "model")),
    (r"(rg|ig)_bias$", ("model",)),
    (r"lambda_param$", ("model",)),
    (r"(A_log|D|dt_bias)$", (None,)),
    (r"(norm_scale|norm_bias|bn_scale|bn_bias)$", (None,)),
    (r".*", None),                                # default: replicated
)


def _path_str(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)


def param_pspecs(params, *, fsdp: bool = False, mesh=None):
    """PartitionSpec pytree for a parameter tree (works on SDS trees too).

    Divisibility-aware: when a rule assigns a mesh axis to a dim it does not
    evenly divide (granite's 40 experts vs model=16; mamba's 50280 vocab),
    the axis is moved to the next trailing dim it divides, else dropped.
    """
    f = "data" if fsdp else None
    sizes = ({a: int(s) for a, s in mesh.shape.items()} if mesh is not None
             else {})

    def fixup(tr: tuple, shape: tuple) -> tuple:
        tr = list(tr)
        for i, ax in enumerate(tr):
            if ax is None or not sizes:
                continue
            if shape[i] % sizes.get(ax, 1) == 0:
                continue
            tr[i] = None
            for j in range(len(tr)):          # move to a dim it divides
                if tr[j] is None and shape[j] % sizes.get(ax, 1) == 0:
                    tr[j] = ax
                    break
        return tuple(tr)

    def spec_for(path, leaf):
        ps = _path_str(path)
        for pat, trailing in _RULES:
            if re.search(pat, ps):
                if trailing is None:
                    return P()
                tr = tuple(f if t == "F" else t for t in trailing)
                # right-align: scanned blocks have a leading (n_blocks,) dim
                lead = leaf.ndim - len(tr)
                if lead < 0:
                    return P()
                tr = fixup(tr, leaf.shape[lead:])
                if all(t is None for t in tr):
                    return P()
                return P(*((None,) * lead + tr))
        return P()

    return jax.tree_util.tree_map_with_path(spec_for, params)


def with_shardings(tree, mesh, specs):
    """Attach NamedShardings to a ShapeDtypeStruct tree (for .lower())."""
    return jax.tree.map(
        lambda s, sp: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, sp)),
        tree, specs)


def cache_pspecs(cache, dp_axes: tuple[str, ...], mesh):
    """KV/recurrent-state sharding, divisibility-aware.

    kv cache (B, L, Hkv, D): batch over DP axes (when divisible), model on
    Hkv if divisible else on D (qwen/llama kv=8 < model=16 -> shard the
    head_dim instead). Recurrent/conv states: batch over DP, model on the
    first trailing dim it divides. Scanned leaves ('blocks/...') carry a
    leading (n_blocks,) dim that stays unsharded.
    """
    dp_size = int(np.prod([mesh.shape[a] for a in dp_axes]))
    model_size = int(mesh.shape.get("model", 1))

    def spec_with_scan(path, leaf):
        ps = _path_str(path)
        scanned = ps.startswith("blocks")
        nd = leaf.ndim - (1 if scanned else 0)
        dims = leaf.shape[1:] if scanned else leaf.shape
        if nd == 0:
            return P()
        batch_ax = dp_axes if dims[0] % max(dp_size, 1) == 0 else None
        rest = [None] * (nd - 1)
        if nd == 4:                       # (B, L, Hkv, D) kv cache
            if dims[2] % model_size == 0:
                rest[1] = "model"
            elif dims[3] % model_size == 0:
                rest[2] = "model"
        else:                             # recurrent / conv state
            for i, d in enumerate(dims[1:]):
                if d % model_size == 0:
                    rest[i] = "model"
                    break
        inner = (batch_ax, *rest)
        if scanned:
            inner = (None,) + inner
        return P(*inner)

    return jax.tree_util.tree_map_with_path(spec_with_scan, cache)
