"""LARS optimizer (You et al. [10]), as used by the paper (§3.2).

Paper settings: coefficient (trust ratio eta) = 0.01, eps = 1e-6, momentum
SGD underneath, and -- critically -- *all LARS computation in FP32* because
the trust ratio (norm ratios) needs more dynamic range than half precision.
Weight decay is applied inside the LARS norm (You et al. eq. 4).

The update for parameter w with gradient g (already averaged across the DP
grid by grad_sync):

    local_lr = eta * ||w|| / (||g|| + wd * ||w|| + eps)
    v        = m * v + local_lr * global_lr * (g + wd * w)
    w        = w - v

Bias/BN parameters are excluded from LARS scaling and weight decay
(standard practice in [10] and every reproduction, incl. the paper's NNL
code): they use plain momentum SGD.

A fused Pallas kernel for the elementwise part lives in
``repro.kernels.lars_update``; this module is the optimizer logic and uses
the kernel via ``use_kernel=True`` (ref path by default so CPU tests are
oracle-exact).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class LARSConfig:
    eta: float = 0.01            # paper: "coefficient of 0.01"
    eps: float = 1e-6            # paper default
    weight_decay: float = 5e-5   # You et al. ImageNet setting
    skip_tags: tuple[str, ...] = ("bias", "bn", "scale", "norm", "embed_norm")
    use_kernel: bool = False     # route elementwise update through Pallas
    nesterov: bool = False


def _is_skip(path, cfg: LARSConfig) -> bool:
    ps = "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path).lower()
    return any(t in ps for t in cfg.skip_tags)


def init(params) -> dict:
    """Momentum buffers, fp32 (master-precision) like the params."""
    return {"momentum": jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)}


def update(params, grads, opt_state, *, lr, momentum, cfg: LARSConfig = LARSConfig()):
    """One LARS step. params/grads may be bf16; all math is fp32 (paper §3.2).

    lr, momentum are scalars (possibly traced -- schedules evaluate per
    step). Returns (new_params, new_opt_state).
    """
    mom_tree = opt_state["momentum"]

    grads_flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    params_flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    moms = jax.tree_util.tree_leaves(mom_tree)

    new_p, new_m = [], []
    for (path, p), (_, g), v in zip(params_flat, grads_flat, moms):
        p32 = p.astype(jnp.float32)
        g32 = g.astype(jnp.float32)
        if _is_skip(path, cfg):
            # plain momentum SGD, no trust ratio, no weight decay
            v_new = momentum * v + lr * g32
        else:
            if cfg.use_kernel:
                from repro.kernels import ops as kops
                p_out, v_new = kops.lars_update(
                    p32, g32, v, lr=lr, mom=momentum, eta=cfg.eta,
                    weight_decay=cfg.weight_decay, eps=cfg.eps)
                new_p.append(p_out.astype(p.dtype))
                new_m.append(v_new)
                continue
            w_norm = jnp.linalg.norm(p32)
            g_norm = jnp.linalg.norm(g32)
            trust = jnp.where(
                (w_norm > 0) & (g_norm > 0),
                cfg.eta * w_norm / (g_norm + cfg.weight_decay * w_norm + cfg.eps),
                1.0)
            upd = g32 + cfg.weight_decay * p32
            v_new = momentum * v + (trust * lr) * upd
        if cfg.nesterov:
            step = momentum * v_new + (v_new - momentum * v)
        else:
            step = v_new
        new_p.append((p32 - step).astype(p.dtype))
        new_m.append(v_new)

    params_out = jax.tree_util.tree_unflatten(treedef, new_p)
    mom_out = jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(mom_tree), new_m)
    return params_out, {"momentum": mom_out}

