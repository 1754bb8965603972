"""Fused label-smoothed cross-entropy kernel (Pallas, TPU target).

For 256K-vocab archs the loss is memory-bound: log_softmax materializes a
(B*S, V) fp32 tensor (134 MB per 128 rows at V=256k) and reads it twice.
This kernel streams the vocab dimension in VMEM tiles with an *online
logsumexp* (flash-attention-style rescaling), keeping only (rows, 1) running
accumulators; logits are read exactly once and no (rows, V) intermediate is
ever written.

Grid: (row_blocks, vocab_blocks); vocab is the inner (minor) loop so the
accumulators live across the j-sweep in VMEM scratch.

Per row r with label y, smoothing a, vocab K:
    loss = (1-a) * (lse - logit_y) - a * (sum_logits / K - lse)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ls_xent_kernel(labels_ref, logits_ref, out_ref,
                    m_ref, s_ref, sum_ref, lab_ref, *, nv_blocks, bv,
                    smoothing, vocab):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        s_ref[...] = jnp.zeros_like(s_ref)
        sum_ref[...] = jnp.zeros_like(sum_ref)
        lab_ref[...] = jnp.zeros_like(lab_ref)

    x = logits_ref[...].astype(jnp.float32)            # (br, bv)
    m_prev = m_ref[...]                                # (br, 1)
    m_cur = jnp.maximum(m_prev, x.max(axis=1, keepdims=True))
    scale = jnp.exp(m_prev - m_cur)
    s_ref[...] = s_ref[...] * scale + jnp.exp(x - m_cur).sum(
        axis=1, keepdims=True)
    m_ref[...] = m_cur
    # exclude vocab padding columns from the plain sum
    gcol = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) + j * bv
    sum_ref[...] = sum_ref[...] + jnp.where(gcol < vocab, x, 0.0).sum(
        axis=1, keepdims=True)

    # label logit: at most one column of this tile matches each row
    onehot = gcol == labels_ref[...]                   # (br, bv) vs (br, 1)
    lab_ref[...] = lab_ref[...] + jnp.where(onehot, x, 0.0).sum(
        axis=1, keepdims=True)

    @pl.when(j == nv_blocks - 1)
    def _finish():
        lse = m_ref[...] + jnp.log(s_ref[...])
        nll = lse - lab_ref[...]
        mean_logit = sum_ref[...] / vocab
        out_ref[...] = (1.0 - smoothing) * nll - smoothing * (mean_logit - lse)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def ls_xent_pallas(logits, labels, *, smoothing: float,
                   block_rows: int = 128, block_vocab: int = 2048,
                   interpret: bool = False):
    """logits: (..., V) float; labels: (...) int32 -> per-row loss fp32."""
    batch_shape = logits.shape[:-1]
    V = logits.shape[-1]
    R = 1
    for d in batch_shape:
        R *= d
    x = logits.reshape(R, V)
    y = labels.reshape(R, 1).astype(jnp.int32)

    # Mosaic tiles f32 as (8, 128) and 16-bit types as (16, 128): pad rows
    # and vocab up to whole tiles, then to whole blocks (padded logits are
    # -1e30: no effect on lse; the plain sum masks columns >= V, and
    # padded rows are discarded)
    sub = max(8, 32 // x.dtype.itemsize)
    br = min(block_rows, _round_up(R, sub))
    bv = min(block_vocab, _round_up(V, 128))
    Rp, Vp = _round_up(R, br), _round_up(V, bv)
    if Rp != R or Vp != V:
        x = jnp.pad(x, ((0, Rp - R), (0, Vp - V)), constant_values=-1e30)
        y = jnp.pad(y, ((0, Rp - R), (0, 0)))
    grid = (Rp // br, Vp // bv)

    # labels, output and accumulators are 2-D (rows, 1) columns: Mosaic
    # cannot lay out the row-to-column reshape a 1-D (rows,) block needs
    rows = pl.BlockSpec((br, 1), lambda i, j: (i, 0))
    out = pl.pallas_call(
        functools.partial(_ls_xent_kernel, nv_blocks=grid[1], bv=bv,
                          smoothing=smoothing, vocab=V),
        grid=grid,
        in_specs=[rows, pl.BlockSpec((br, bv), lambda i, j: (i, j))],
        out_specs=rows,
        out_shape=jax.ShapeDtypeStruct((Rp, 1), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((br, 1), jnp.float32),   # running max
            pltpu.VMEM((br, 1), jnp.float32),   # running sumexp
            pltpu.VMEM((br, 1), jnp.float32),   # running sum of logits
            pltpu.VMEM((br, 1), jnp.float32),   # label logit
        ],
        interpret=interpret,
    )(y, x)
    return out[:R, 0].reshape(batch_shape)
