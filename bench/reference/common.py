"""Pieces every plain reference shares: seeds, schedule B, LARS, the
label-smoothed loss, fp8 rounding for the control, and the three-step
training readings.

Plain float32 under ``jax.default_matmul_precision("highest")``, written
from the published descriptions (Goyal et al. / You et al. for LARS, the
paper's schedule B, Szegedy et al. for label smoothing). Imports nothing
of the program under test.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def key_from_seed(seed: int):
    """A PRNG key from a seed of up to 64 bits (``jax.random.key`` keeps
    only the low 32)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def schedule_b(epoch, global_batch, recipe):
    """LR and momentum of the paper's configuration B at ``epoch``.

    LR: linear warm-up from ``warmup_init`` to ``base_lr_1`` over
    ``warmup_epochs``, then ``base_lr * (1 - e / total)^2`` with the base
    switching at ``switch_epoch``. Momentum: Smith & Le's constant noise
    scale anchored at the reference batch, ``1 - (1 - m_ref) B_ref / B``,
    clipped to [0, 0.999].
    """
    s = recipe["schedule_b"]
    e = jnp.asarray(epoch, jnp.float32)
    warm = s["warmup_init"] + (s["base_lr_1"] - s["warmup_init"]) * e / s[
        "warmup_epochs"]
    q = (1.0 - e / s["total_epochs"]) ** 2
    later = jnp.where(e < s["switch_epoch"], s["base_lr_1"] * q,
                      s["base_lr_2"] * q)
    lr = jnp.where(e < s["warmup_epochs"], warm, later)
    m = 1.0 - (1.0 - s["ref_momentum"]) * s["ref_batch"] / float(global_batch)
    return lr, jnp.clip(jnp.float32(m), 0.0, 0.999)


def ls_xent(logits, labels, smoothing):
    """Per-row smoothed cross-entropy against (1 - a) onehot + a / K."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return (1.0 - smoothing) * nll - smoothing * logp.mean(-1)


def path_name(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


def lars_step(params, grads, mom, lr, momentum, recipe):
    """One LARS step (You et al. 2017) on every leaf.

    A leaf whose path names a norm scale, a BN parameter or a bias takes
    plain momentum SGD without weight decay; every other leaf is one LARS
    layer: ``local = eta |w| / (|g| + wd |w| + eps)`` (1 where either norm
    is 0), ``v = m v + local lr (g + wd w)``, ``w = w - v``.
    """
    lars = recipe["lars"]
    eta, eps, wd = lars["eta"], lars["eps"], lars["weight_decay"]
    skip = tuple(lars["plain_sgd_tags"])
    flat, tdef = jax.tree_util.tree_flatten_with_path(params)
    gl = jax.tree.leaves(grads)
    vl = jax.tree.leaves(mom)
    new_w, new_v = [], []
    for (path, w), g, v in zip(flat, gl, vl):
        if any(t in path_name(path).lower() for t in skip):
            v = momentum * v + lr * g
        else:
            wn = jnp.sqrt(jnp.sum(w * w))
            gn = jnp.sqrt(jnp.sum(g * g))
            local = jnp.where((wn > 0) & (gn > 0),
                              eta * wn / (gn + wd * wn + eps), 1.0)
            v = momentum * v + (local * lr) * (g + wd * w)
        new_w.append(w - v)
        new_v.append(v)
    return (jax.tree_util.tree_unflatten(tdef, new_w),
            jax.tree_util.tree_unflatten(tdef, new_v))


def _round8(x, dtype, top):
    """``x`` rounded to the float8 ``dtype`` under a per-tensor scale that
    maps its largest magnitude to ``top``, back in float32."""
    x = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, top / amax, 1.0)
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


@jax.custom_vjp
def fp8(x):
    """An operand of a product computed in float8, as fp8 training does:
    rounded to e4m3 in the forward pass, its gradient passed through."""
    return _round8(x, jnp.float8_e4m3fn, 448.0)


fp8.defvjp(lambda x: (fp8(x), None), lambda _, g: (g,))


@jax.custom_vjp
def fp8_grad(y):
    """The output of a product computed in float8: unchanged forward; the
    gradient that flows back into the product is rounded to e5m2, so the
    backward pass's products take float8 operands too."""
    return y


fp8_grad.defvjp(lambda y: (y, None),
                lambda _, g: (_round8(g, jnp.float8_e5m2, 57344.0),))


def leaf_norms(tree) -> jax.Array:
    """Euclidean norm of every leaf, in ``tree_leaves`` order."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


def train_readings(loss_fn, init, batches, epochs, global_batch, recipe,
                   row_block=None):
    """Three training steps from ``init()``; returns the readings that the
    comparison uses, as numpy arrays:

    ``loss`` (3,), the loss of each step; ``update1`` (leaves,), the norm of
    each leaf's first momentum (LR times the gradient where LARS does not
    scale the leaf, the trust-scaled step where it does); ``change3``
    (leaves,), the norm of each leaf's change after three steps; ``grad``
    (3, leaves), the norm of each leaf's gradient at each step.

    ``loss_fn(params, batch) -> mean loss``. Where the loss is a mean over
    independent rows, ``row_block`` rows at a time are differentiated and
    the gradients averaged, so that a large batch fits; ``init()`` makes
    the weights, and is called again at the end rather than holding them.
    """
    vg = jax.jit(jax.value_and_grad(loss_fn))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))
    scale = jax.jit(lambda t, s: jax.tree.map(lambda x: x * s, t))

    def value_and_grad(w, batch):
        rows = jax.tree.leaves(batch)[0].shape[0]
        n = rows if row_block is None else min(row_block, rows)
        if rows % n:
            raise ValueError(f"{rows} rows do not split into blocks of {n}")
        loss, g = 0.0, None
        for i in range(0, rows, n):
            part = jax.tree.map(lambda x: x[i:i + n], batch)
            li, gi = vg(w, part)
            loss, g = loss + float(li), gi if g is None else add(g, gi)
        k = rows // n
        return loss / k, (g if k == 1 else scale(g, 1.0 / k))

    @jax.jit
    def apply(w, g, v, lr, m):
        return lars_step(w, g, v, lr, m, recipe)

    norms = jax.jit(leaf_norms)
    delta = jax.jit(lambda a, b: leaf_norms(jax.tree.map(jnp.subtract, a, b)))
    w = init()
    v = jax.tree.map(jnp.zeros_like, w)
    losses, grads, update1 = [], [], None
    for k, (batch, epoch) in enumerate(zip(batches, epochs)):
        loss, g = value_and_grad(w, batch)
        lr, m = schedule_b(epoch, global_batch, recipe)
        w, v = apply(w, g, v, lr, m)
        losses.append(loss)
        grads.append(np.asarray(norms(g)))
        if k == 0:
            update1 = np.asarray(norms(v))
        del g
    del v
    return {"loss": np.asarray(losses), "update1": update1,
            "change3": np.asarray(delta(w, init())),
            "grad": np.stack(grads)}
