"""Flash attention forward kernel (Pallas, TPU target).

The chunked-jnp attention materializes (q_chunk, S) fp32 logits +
softmax weights per layer in HBM. This kernel keeps the running max /
sum / output accumulator in VMEM across k-blocks (online softmax), so
per (q-block, k-block) tile only the (bq, bk) logits live in VMEM and
logits NEVER touch HBM: HBM traffic drops from O(S^2) to O(S * D) per
head.

Layout: q/k/v as (B, H, S, D) (heads-major so a (b, h) pair is a grid
row); GQA is handled by the wrapper (kv head index = h // group). Causal
+ sliding-window masking inside the kernel; k-blocks entirely above the
diagonal are masked to -inf (the index map still visits them -- Pallas
grids are dense -- but they contribute exp(-inf)=0; a production version
would use a data-dependent grid).

MXU alignment: block_q x D and block_k x D tiles with D in {64, 128, 256};
block sizes default to 128 (fp32 VREG/MXU friendly).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  bq, bk, nk, scale, causal, window, softcap):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    i = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)                      # (bq, D)
    k = k_ref[0].astype(jnp.float32)                      # (bk, D)
    v = v_ref[0].astype(jnp.float32)

    s = (q * scale) @ k.T                                 # (bq, bk)
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    qi = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kj = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), bool)
    if causal:
        mask &= kj <= qi
    if window is not None:
        mask &= kj > qi - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_cur = jnp.maximum(m_prev, s.max(axis=1))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur[:, None])
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + p @ v
    m_ref[...] = m_cur

    @pl.when(j == nk - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / denom[:, None]).astype(o_ref.dtype)


def flash_attention_hsd(q, k, v, *, causal=True, window=None, softcap=None,
                        scale=None, block_q=128, block_k=128,
                        interpret=False):
    """q: (BH, S, D), k/v: (BH, Skv, D) -- batch*head already folded.

    Returns (BH, S, D) in q.dtype. S, Skv must be block multiples (wrapper
    pads).
    """
    BH, S, D = q.shape
    Skv = k.shape[1]
    bq = min(block_q, S)
    bk = min(block_k, Skv)
    nq, nk = S // bq, Skv // bk
    scale = D ** -0.5 if scale is None else scale

    return pl.pallas_call(
        functools.partial(_flash_kernel, bq=bq, bk=bk, nk=nk, scale=scale,
                          causal=causal, window=window, softcap=softcap),
        grid=(BH, nq, nk),
        in_specs=[pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
                  pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
                  pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0))],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq,), jnp.float32),
                        pltpu.VMEM((bq,), jnp.float32),
                        pltpu.VMEM((bq, D), jnp.float32)],
        interpret=interpret,
    )(q, k, v)


def flash_attention(q, k, v, *, causal=True, window=None, softcap=None,
                    scale=None, block_q=128, block_k=128, interpret=False):
    """q: (B, S, H, D); k/v: (B, Skv, Hkv, D) with H % Hkv == 0 (GQA).

    Pads S/Skv to block multiples, folds (B, H), repeats kv heads per group
    (gather view, not a copy after XLA fusion), unfolds back.
    """
    B, S, H, D = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    group = H // Hkv

    pq = (-S) % min(block_q, max(S, 1))
    pk = (-Skv) % min(block_k, max(Skv, 1))
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))

    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S + pq, D)
    kg = jnp.repeat(k, group, axis=2)
    vg = jnp.repeat(v, group, axis=2)
    kf = kg.transpose(0, 2, 1, 3).reshape(B * H, Skv + pk, D)
    vf = vg.transpose(0, 2, 1, 3).reshape(B * H, Skv + pk, D)

    o = flash_attention_hsd(qf, kf, vf, causal=causal, window=window,
                            softcap=softcap, scale=scale, block_q=block_q,
                            block_k=block_k, interpret=interpret)
    o = o.reshape(B, H, S + pq, D).transpose(0, 2, 1, 3)
    return o[:, :S]
