"""The plain references against the program at small sizes on the CPU,
both in float32: each departure the references note is shared with the
program, and nothing else differs."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.reference import common, mamba2, resnet50

RESNET = {"stage_sizes": [1, 1], "width": 8, "num_classes": 10,
          "image_size": 32}
MAMBA = {"model": "Mamba-2 (tiny)", "d_model": 64, "n_layers": 2,
         "expand": 2, "ssm_state": 16, "ssm_head_dim": 16, "ssm_chunk": 16,
         "conv_width": 4, "vocab": 128, "norm": "rmsnorm",
         "tie_embeddings": True, "compute_dtype": "float32", "remat": False}
RECIPE = {
    "schedule_b": {"warmup_epochs": 5.0, "warmup_init": 0.2,
                   "base_lr_1": 29.0, "base_lr_2": 50.0,
                   "switch_epoch": 30.0, "total_epochs": 90.0,
                   "ref_batch": 32768, "ref_momentum": 0.9},
    "lars": {"eta": 0.01, "eps": 1e-6, "weight_decay": 5e-5,
             "plain_sgd_tags": ["bias", "bn", "scale", "norm", "embed_norm"]}}


def _shapes(tree):
    return jax.tree.structure(tree), [x.shape for x in jax.tree.leaves(tree)]


def _close(a, b, tol):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert np.max(np.abs(a - b)) <= tol * np.max(np.abs(b)), (
        np.max(np.abs(a - b)), np.max(np.abs(b)))


def test_resnet_tree_matches_program():
    from repro.models import resnet

    cfg = resnet.ResNetConfig.tiny()
    assert _shapes(resnet50.init(jax.random.key(0), RESNET)) == _shapes(
        jax.eval_shape(lambda: resnet.init(jax.random.key(0), cfg)))


@pytest.mark.parametrize("quant", [None, "fp8"])
def test_resnet_forward_and_grad_match_program(quant):
    from repro.core import losses
    from repro.models import resnet

    cfg = resnet.ResNetConfig.tiny(compute_dtype=jnp.float32)
    params = resnet50.init(jax.random.key(1), RESNET)
    images = jax.random.normal(jax.random.key(2), (8, 32, 32, 3))
    labels = jax.random.randint(jax.random.key(3), (8,), 0, 10)

    def prog_loss(p):
        return losses.label_smoothing_xent(resnet.apply(p, images, cfg),
                                           labels, 0.1)

    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(prog_loss)(params)
        lr, gr = jax.value_and_grad(resnet50.loss)(
            params, (images, labels), 0.1, quant)
    if quant is None:
        assert abs(float(lp) - float(lr)) < 1e-5 * abs(float(lr))
        for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gr)):
            _close(a, b, 1e-4)
    else:
        # the control rounds to float8: visibly off, still finite
        assert 1e-5 < abs(float(lp) - float(lr)) / float(lr) < 0.1


def test_mamba_tree_matches_program():
    from bench.jobs import lm
    from repro.models import transformer as T

    arch = lm.arch(MAMBA)
    assert _shapes(mamba2.init(jax.random.key(0), MAMBA)) == _shapes(
        jax.eval_shape(lambda: T.init(jax.random.key(0), arch)))


def test_mamba_recurrence_matches_chunked_program():
    from bench.jobs import lm
    from repro.core import losses
    from repro.models import transformer as T

    arch = lm.arch(MAMBA)
    params = mamba2.init(jax.random.key(4), MAMBA)
    tokens = jax.random.randint(jax.random.key(5), (2, 64), 0, 128)
    labels = jnp.roll(tokens, -1, axis=1)

    def prog_loss(p):
        logits, _ = T.forward(p, tokens, arch)
        return losses.label_smoothing_xent(logits, labels, 0.1)

    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(prog_loss)(params)
        lr, gr = jax.value_and_grad(mamba2.loss)(
            params, (tokens, labels), MAMBA, 0.1)
    assert abs(float(lp) - float(lr)) < 1e-5 * abs(float(lr))
    for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gr)):
        _close(a, b, 1e-3)


def test_schedule_b_matches_program():
    from repro.core import schedules

    prog = schedules.ConfigB()
    for e in (0.0, 0.0002, 3.7, 5.0, 29.9, 30.0, 60.0):
        for b in (128, 256, 16384, 65536):
            lr, m = common.schedule_b(e, b, RECIPE)
            assert float(lr) == pytest.approx(float(prog.lr(e)), rel=1e-6)
            assert float(m) == pytest.approx(float(prog.mom(e, b)),
                                             abs=1e-7)


def test_lars_step_matches_program():
    from repro.core import lars

    params = resnet50.init(jax.random.key(6), RESNET)
    grads = jax.tree.map(
        lambda p: jax.random.normal(jax.random.key(p.size), p.shape), params)
    grads["stages"][0][0]["conv1"]["kernel"] *= 0.0      # zero gradient
    mom = jax.tree.map(lambda p: 0.01 * jnp.ones_like(p), params)
    cfg = dataclasses.replace(lars.LARSConfig())
    wp, vp = lars.update(params, grads, {"momentum": mom}, lr=0.7,
                         momentum=0.9, cfg=cfg)
    wr, vr = common.lars_step(params, grads, mom, 0.7, 0.9, RECIPE)
    for a, b in zip(jax.tree.leaves((wp, vp["momentum"])),
                    jax.tree.leaves((wr, vr))):
        _close(a, b, 1e-6)
