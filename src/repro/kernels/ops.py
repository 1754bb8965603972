"""Jit-ready wrappers around the Pallas kernels.

On TPU the kernels compile to Mosaic. On the CPU backend they execute in
``interpret=True`` mode, so every CPU test validates the exact kernel
body. Any other backend is an error at import: silently interpreting
there would report an interpreter as the device.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import lars_update as _lars
from repro.kernels import ls_xent as _lsx


def _interpret_on(backend: str) -> bool:
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels here target TPU (Mosaic) or the CPU interpreter; "
        f"the default backend is {backend!r}")


INTERPRET = _interpret_on(jax.default_backend())


def lars_update(p, g, v, *, lr, mom, eta, weight_decay, eps,
                interpret: bool | None = None):
    """Fused LARS step; norms computed outside (tiny XLA reductions)."""
    interpret = INTERPRET if interpret is None else interpret
    p32 = p.astype(jnp.float32)
    g32 = g.astype(jnp.float32)
    w_norm = jnp.linalg.norm(p32)
    g_norm = jnp.linalg.norm(g32)
    trust = jnp.where((w_norm > 0) & (g_norm > 0),
                      eta * w_norm / (g_norm + weight_decay * w_norm + eps),
                      1.0)
    return _lars.lars_update_pallas(
        p32, g32, v, trust_lr=trust * lr, mom=mom,
        weight_decay=weight_decay, interpret=interpret)


def ls_xent(logits, labels, *, smoothing: float,
            interpret: bool | None = None):
    """Per-row label-smoothed cross-entropy, fused over the vocab dim."""
    interpret = INTERPRET if interpret is None else interpret
    return _lsx.ls_xent_pallas(logits, labels, smoothing=smoothing,
                               interpret=interpret)


def flash_attention(q, k, v, *, causal=True, window=None, softcap=None,
                    scale=None, interpret: bool | None = None):
    """Flash attention fwd (TPU kernel; HBM traffic O(S*D) not O(S^2))."""
    from repro.kernels import flash_attn as _fa
    interpret = INTERPRET if interpret is None else interpret
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               softcap=softcap, scale=scale,
                               interpret=interpret)
