"""LARS, schedules, losses, batch-size control."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, strategies as st

from repro.core import lars, losses
from repro.core.batch_control import build_plan, epoch_of
from repro.core.schedules import BatchSchedule, BatchStage, ConfigA, ConfigB, paper_schedule


# ----------------------------------------------------------------- LARS ----

def _tree():
    rng = np.random.RandomState(0)
    return {"dense": {"kernel": jnp.asarray(rng.randn(8, 4), jnp.float32),
                      "bias": jnp.asarray(rng.randn(4), jnp.float32)}}


def test_lars_trust_ratio_scales_update():
    params = _tree()
    grads = jax.tree.map(jnp.ones_like, params)
    opt = lars.init(params)
    cfg = lars.LARSConfig(weight_decay=0.0)
    new_p, new_opt = lars.update(params, grads, opt, lr=1.0, momentum=0.0, cfg=cfg)
    # kernel: step = eta * ||w||/||g|| * g  (wd=0)
    w = params["dense"]["kernel"]
    g = grads["dense"]["kernel"]
    trust = cfg.eta * jnp.linalg.norm(w) / (jnp.linalg.norm(g) + cfg.eps)
    np.testing.assert_allclose(np.asarray(new_p["dense"]["kernel"]),
                               np.asarray(w - trust * g), rtol=1e-6)
    # bias: skip-listed -> plain SGD step of lr * g
    np.testing.assert_allclose(np.asarray(new_p["dense"]["bias"]),
                               np.asarray(params["dense"]["bias"] - 1.0), rtol=1e-6)


def test_lars_momentum_accumulates():
    params = _tree()
    grads = jax.tree.map(jnp.ones_like, params)
    opt = lars.init(params)
    p1, opt1 = lars.update(params, grads, opt, lr=0.1, momentum=0.9)
    p2, opt2 = lars.update(p1, grads, opt1, lr=0.1, momentum=0.9)
    v1 = opt1["momentum"]["dense"]["kernel"]
    v2 = opt2["momentum"]["dense"]["kernel"]
    assert np.all(np.abs(np.asarray(v2)) > np.abs(np.asarray(v1)) * 0.99)


def test_lars_bf16_params_fp32_master_math():
    params = jax.tree.map(lambda x: x.astype(jnp.bfloat16), _tree())
    grads = jax.tree.map(jnp.ones_like, params)
    opt = lars.init(params)
    new_p, _ = lars.update(params, grads, opt, lr=0.1, momentum=0.9)
    assert new_p["dense"]["kernel"].dtype == jnp.bfloat16
    assert opt["momentum"]["dense"]["kernel"].dtype == jnp.float32


# ------------------------------------------------------------ schedules ----

def test_config_a_warmup_and_decay():
    a = ConfigA()
    assert float(a.lr(0.0)) == pytest.approx(1e-5)
    assert float(a.lr(34.0)) == pytest.approx(34.0, rel=1e-3)
    assert float(a.lr(90.0)) == pytest.approx(0.0, abs=1e-6)
    assert float(a.lr(50.0)) < 34.0


def test_config_b_matches_paper_formula():
    b = ConfigB()
    assert float(b.lr(0.0)) == pytest.approx(0.2)
    assert float(b.lr(5.0)) == pytest.approx(29.0 * (1 - 5 / 90) ** 2, rel=0.08)
    assert float(b.lr(20.0)) == pytest.approx(29.0 * (1 - 20 / 90) ** 2, rel=1e-5)
    assert float(b.lr(60.0)) == pytest.approx(50.0 * (1 - 60 / 90) ** 2, rel=1e-5)


def test_config_b_momentum_noise_scale_anchor():
    b = ConfigB()
    # at the reference batch the momentum must be the reference momentum
    assert float(b.mom(10.0, 32 * 1024)) == pytest.approx(0.9, rel=1e-6)
    # larger batch -> larger momentum (constant noise scale)
    assert float(b.mom(10.0, 54 * 1024)) > 0.9
    assert float(b.mom(10.0, 119 * 1024)) > float(b.mom(10.0, 54 * 1024))


# --------------------------------------------------------------- losses ----

def test_label_smoothing_reduces_confident_gradient():
    logits = jnp.asarray([[10.0, -10.0, -10.0]])
    labels = jnp.asarray([0])
    plain = float(losses.softmax_xent(logits, labels))
    smooth = float(losses.label_smoothing_xent(logits, labels, smoothing=0.1))
    assert smooth > plain  # smoothing penalizes over-confidence


@settings(max_examples=25, deadline=None)
@given(b=st.integers(1, 6), k=st.integers(2, 30), seed=st.integers(0, 999),
       alpha=st.floats(0.0, 0.3))
def test_ls_xent_property_matches_manual(b, k, seed, alpha):
    rng = np.random.RandomState(seed)
    logits = rng.randn(b, k).astype(np.float32) * 3
    labels = rng.randint(0, k, size=(b,))
    got = np.asarray(losses.ls_xent_ref(jnp.asarray(logits), jnp.asarray(labels), alpha))
    # manual: -sum q log p with q = (1-a) onehot + a/k
    logp = np.log(np.exp(logits - logits.max(-1, keepdims=True)) /
                  np.exp(logits - logits.max(-1, keepdims=True)).sum(-1, keepdims=True))
    q = np.full((b, k), alpha / k)
    q[np.arange(b), labels] += 1 - alpha
    want = -(q * logp).sum(-1)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_masked_loss():
    logits = jnp.zeros((4, 10))
    labels = jnp.zeros((4,), jnp.int32)
    mask = jnp.asarray([True, True, False, False])
    full = float(losses.label_smoothing_xent(logits, labels, 0.1))
    masked = float(losses.label_smoothing_xent(logits, labels, 0.1, where=mask))
    assert full == pytest.approx(masked, rel=1e-6)  # uniform logits -> same


# -------------------------------------------------------- batch control ----

def test_paper_exp4_schedule_stages():
    sched = paper_schedule("exp4")
    assert len(sched.stages) == 4
    assert sched.stages[0].per_worker_batch == 16
    assert sched.stages[-1].per_worker_batch == 32
    assert sched.total_epochs == 90


def test_plan_steps_and_epochs():
    sched = BatchSchedule((BatchStage(0, 1, 16), BatchStage(1, 2, 32)))
    plan = build_plan(sched, dataset_size=1280, n_workers=4)
    assert plan.stages[0].global_batch == 64
    assert plan.stages[0].num_steps == 20     # 1 epoch * 1280 / 64
    assert plan.stages[1].num_steps == 10     # 1 epoch * 1280 / 128
    e = epoch_of(plan, plan.stages[1], 5)
    assert e == pytest.approx(1.5)


def test_plan_max_steps_truncation():
    plan = build_plan(paper_schedule("exp1"), dataset_size=1_281_167,
                      n_workers=2176, max_steps=100)
    assert plan.total_steps == 100
