"""Plain float32 Mamba-2 language model (Dao & Gu 2024, arXiv:2405.21060):
token embedding, ``n_layers`` pre-norm SSD blocks with residuals, a final
RMSNorm and the tied embedding as the output head.

One SSD block: ``in_proj`` to the gate z, the SSM input x, B, C (one
group) and dt; a causal depthwise convolution of width 4 over (x, B, C)
and SiLU; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; the scan

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T,    y_t = h_t C_t + D x_t

taken here as that sequential recurrence, one position after another, not
the chunked form; then ``rmsnorm(y * silu(z))`` and ``out_proj``.

The parameter tree has the layout the program's step takes (the layers
stacked on a leading axis under ``blocks``), so one set of weights made
here feeds both. Departures from the published model, each shared with
the program and tested against it at a small size on the CPU:

- RMSNorm scales are stored as ``1 + w`` with ``w`` starting at 0, eps
  1e-6; the convolution has no bias; ``A_log`` starts at ``log(1..H)``.
- LARS treats each stacked weight (all layers of one kind) as one layer.

``quant="fp8"`` computes every projection and the head in float8, as fp8
training does (operands in e4m3, the gradient into each product in e5m2,
per-tensor scales): the control.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from bench.reference import common

NORM_EPS = 1e-6
#: positions per checkpointed piece of the recurrence and of the loss
SCAN_PIECE = 64
LOSS_PIECE = 256


def dims(cfg):
    di = cfg["expand"] * cfg["d_model"]
    return di, cfg["ssm_state"], di // cfg["ssm_head_dim"], cfg["ssm_head_dim"]


def init(key, cfg):
    """Embedding N(0, 0.02); projections LeCun-normal; dt log-uniform in
    [1e-3, 0.1] through softplus^-1; ``A_log = log(1..H)``; D = 1; norm
    scales 0 (that is, 1 + 0)."""
    d, L, conv = cfg["d_model"], cfg["n_layers"], cfg["conv_width"]
    di, N, H, _ = dims(cfg)
    k = jax.random.split(key, 5)
    dt = jnp.exp(jax.random.uniform(k[3], (L, H)) *
                 (jnp.log(0.1) - jnp.log(1e-3)) + jnp.log(1e-3))
    mixer = {
        "in_proj": {"kernel": jax.random.normal(
            k[1], (L, d, 2 * di + 2 * N + H)) * d ** -0.5},
        "conv": {"kernel": jax.random.normal(
            k[2], (L, conv, di + 2 * N)) * conv ** -0.5},
        "dt_bias": jnp.log(jnp.expm1(dt)),
        "A_log": jnp.broadcast_to(
            jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32)), (L, H)),
        "D": jnp.ones((L, H)),
        "out_norm": {"norm_scale": jnp.zeros((L, di))},
        "out_proj": {"kernel": jax.random.normal(
            k[4], (L, di, d)) * di ** -0.5},
    }
    return {
        "embed": {"embedding": 0.02 * jax.random.normal(
            k[0], (cfg["vocab"], d))},
        "final_norm": {"norm_scale": jnp.zeros((d,))},
        "prefix": [],
        "blocks": [{"pre_norm": {"norm_scale": jnp.zeros((L, d))},
                    "mixer": mixer}],
    }


def _q(x, quant):
    return common.fp8(x) if quant == "fp8" else x


def _mm(x, w, quant):
    y = jnp.matmul(_q(x, quant), _q(w, quant), precision=common.HIGHEST)
    return common.fp8_grad(y) if quant == "fp8" else y


def _rmsnorm(w, x):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + NORM_EPS) * (
        1.0 + w)


def _recurrence(x, dt, A, B, C):
    """x: (b, S, H, P), dt: (b, S, H), A: (H,), B/C: (b, S, N) ->
    y: (b, S, H, P). Pieces of SCAN_PIECE positions are recomputed in the
    backward pass, so only their first states are kept."""
    b, S, H, P = x.shape
    N = B.shape[-1]

    def step(h, inp):
        xt, dtt, bt, ct = inp
        h = (jnp.exp(dtt * A)[..., None, None] * h
             + (dtt[..., None] * xt)[..., None] * bt[:, None, None, :])
        return h, jnp.einsum("bhpn,bn->bhp", h, ct, precision=common.HIGHEST)

    @jax.checkpoint
    def piece(h, inp):
        return lax.scan(step, h, inp)

    n = min(SCAN_PIECE, S)

    def tm(a):   # (b, S, ...) -> (S / n, n, b, ...)
        a = jnp.moveaxis(a, 1, 0)
        return a.reshape(S // n, n, *a.shape[1:])

    h0 = jnp.zeros((b, H, P, N), jnp.float32)
    _, y = lax.scan(piece, h0, (tm(x), tm(dt), tm(B), tm(C)))
    return jnp.moveaxis(y.reshape(S, b, H, P), 0, 1)


def _ssd_block(p, u, cfg, quant):
    di, N, H, P = dims(cfg)
    b, S, _ = u.shape
    zxbcdt = _mm(u, p["in_proj"]["kernel"], quant)
    z, xbc, dt = jnp.split(zxbcdt, [di, 2 * di + 2 * N], axis=-1)
    w = p["conv"]["kernel"]
    W = w.shape[0]
    xp = jnp.pad(xbc, ((0, 0), (W - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(xp[:, i:i + S] * w[i] for i in range(W)))
    x, B, C = jnp.split(xbc, [di, di + N], axis=-1)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    xh = x.reshape(b, S, H, P)
    y = _recurrence(xh, dt, A, B, C) + p["D"][:, None] * xh
    y = _rmsnorm(p["out_norm"]["norm_scale"],
                 y.reshape(b, S, di) * jax.nn.silu(z))
    return _mm(y, p["out_proj"]["kernel"], quant)


def hidden(params, tokens, cfg, quant=None):
    """Tokens (b, S) -> final hidden states (b, S, d) before the norm."""
    x = params["embed"]["embedding"][tokens]
    blocks = params["blocks"][0]

    @jax.checkpoint
    def layer(x, p):
        h = _rmsnorm(p["pre_norm"]["norm_scale"], x)
        return x + _ssd_block(p["mixer"], h, cfg, quant), None

    x, _ = lax.scan(layer, x, blocks)
    return x


def loss(params, batch, cfg, smoothing, quant=None):
    """Mean label-smoothed cross-entropy of the next token over every
    position; the head and the loss run in pieces of LOSS_PIECE positions
    so that the (b, S, vocab) logits are never whole."""
    tokens, labels = batch
    x = hidden(params, tokens, cfg, quant)
    x = _rmsnorm(params["final_norm"]["norm_scale"], x)
    emb = params["embed"]["embedding"]
    b, S, d = x.shape

    @jax.checkpoint
    def piece(total, inp):
        xs, ls = inp
        logits = _mm(xs, emb.T, quant)
        return total + common.ls_xent(logits, ls, smoothing).sum(), None

    n = min(LOSS_PIECE, S)
    xs = jnp.moveaxis(x.reshape(b, S // n, n, d), 1, 0)
    ls = jnp.moveaxis(labels.reshape(b, S // n, n), 1, 0)
    total, _ = lax.scan(piece, jnp.zeros((), jnp.float32), (xs, ls))
    return total / (b * S)
