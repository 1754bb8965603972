"""Pallas kernels vs ref.py oracles: shape/dtype sweeps in interpret mode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, strategies as st

from repro.kernels import ops, ref


@pytest.mark.parametrize("backend,interpret", [("tpu", False), ("cpu", True),
                                               ("gpu", None)])
def test_interpret_mode_only_on_cpu(backend, interpret):
    """Mosaic on TPU, the interpreter on CPU, and no silent fallback."""
    if interpret is None:
        with pytest.raises(RuntimeError, match="gpu"):
            ops._interpret_on(backend)
    else:
        assert ops._interpret_on(backend) is interpret


# ------------------------------------------------------------------ LARS --

SHAPES = [(7,), (128,), (64, 64), (33, 5), (8, 9, 10), (1, 1), (300, 129)]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lars_kernel_matches_ref(shape, dtype):
    rng = np.random.RandomState(hash(shape) % 2**31)
    p = jnp.asarray(rng.randn(*shape), dtype)
    g = jnp.asarray(rng.randn(*shape), dtype) * 0.1
    v = jnp.asarray(rng.randn(*shape), jnp.float32) * 0.01
    kw = dict(lr=0.5, mom=0.9, eta=0.01, weight_decay=5e-5, eps=1e-6)
    p_new, v_new = ops.lars_update(p, g, v, **kw, interpret=True)
    p_ref, v_ref = ref.lars_update_ref(p, g, v, **kw)
    np.testing.assert_allclose(np.asarray(p_new), np.asarray(p_ref),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(v_new), np.asarray(v_ref),
                               rtol=1e-5, atol=1e-6)


def test_lars_kernel_zero_grad_trust_is_one():
    p = jnp.ones((16,))
    g = jnp.zeros((16,))
    v = jnp.zeros((16,))
    p_new, v_new = ops.lars_update(p, g, v, lr=1.0, mom=0.9, eta=0.01,
                                   weight_decay=0.0, eps=1e-6, interpret=True)
    np.testing.assert_allclose(np.asarray(p_new), 1.0)


def test_lars_kernel_jits_and_grads_flow():
    p = jnp.asarray(np.random.randn(50, 3), jnp.float32)
    g = jnp.ones_like(p)
    v = jnp.zeros_like(p)

    @jax.jit
    def f(p, g, v, lr):
        return ops.lars_update(p, g, v, lr=lr, mom=0.9, eta=0.01,
                               weight_decay=5e-5, eps=1e-6, interpret=True)
    p1, v1 = f(p, g, v, 0.1)
    assert p1.shape == p.shape and np.isfinite(np.asarray(p1)).all()


# --------------------------------------------------------------- LS-xent --

@pytest.mark.parametrize("rows,vocab", [(4, 16), (3, 300), (130, 2048),
                                        (5, 2049), (2, 5000), (1, 7)])
@pytest.mark.parametrize("smoothing", [0.0, 0.1])
def test_ls_xent_kernel_matches_ref(rows, vocab, smoothing):
    rng = np.random.RandomState(rows * 1000 + vocab)
    logits = jnp.asarray(rng.randn(rows, vocab) * 4, jnp.float32)
    labels = jnp.asarray(rng.randint(0, vocab, (rows,)), jnp.int32)
    got = ops.ls_xent(logits, labels, smoothing=smoothing, interpret=True)
    want = ref.ls_xent_ref(logits, labels, smoothing)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_ls_xent_kernel_bf16_logits():
    rng = np.random.RandomState(0)
    logits = jnp.asarray(rng.randn(8, 512) * 3, jnp.bfloat16)
    labels = jnp.asarray(rng.randint(0, 512, (8,)), jnp.int32)
    got = ops.ls_xent(logits, labels, smoothing=0.1, interpret=True)
    want = ref.ls_xent_ref(logits.astype(jnp.float32), labels, 0.1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-2, atol=2e-2)


def test_ls_xent_kernel_batched_shape():
    rng = np.random.RandomState(1)
    logits = jnp.asarray(rng.randn(2, 6, 100), jnp.float32)
    labels = jnp.asarray(rng.randint(0, 100, (2, 6)), jnp.int32)
    got = ops.ls_xent(logits, labels, smoothing=0.1, interpret=True)
    assert got.shape == (2, 6)
    want = ref.ls_xent_ref(logits, labels, 0.1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@settings(max_examples=15, deadline=None)
@given(rows=st.integers(1, 20), vocab=st.integers(2, 600),
       scale=st.floats(0.1, 20.0), seed=st.integers(0, 999))
def test_ls_xent_property_sweep(rows, vocab, scale, seed):
    rng = np.random.RandomState(seed)
    logits = jnp.asarray(rng.randn(rows, vocab) * scale, jnp.float32)
    labels = jnp.asarray(rng.randint(0, vocab, (rows,)), jnp.int32)
    got = ops.ls_xent(logits, labels, smoothing=0.1, interpret=True)
    want = ref.ls_xent_ref(logits, labels, 0.1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_lars_optimizer_kernel_path_matches_ref_path():
    """core.lars with use_kernel=True == use_kernel=False."""
    from repro.core import lars
    rng = np.random.RandomState(2)
    params = {"w": {"kernel": jnp.asarray(rng.randn(32, 8), jnp.float32)}}
    grads = {"w": {"kernel": jnp.asarray(rng.randn(32, 8), jnp.float32)}}
    opt = lars.init(params)
    ref_p, ref_o = lars.update(params, grads, opt, lr=0.3, momentum=0.9,
                               cfg=lars.LARSConfig(use_kernel=False))
    ker_p, ker_o = lars.update(params, grads, opt, lr=0.3, momentum=0.9,
                               cfg=lars.LARSConfig(use_kernel=True))
    np.testing.assert_allclose(np.asarray(ker_p["w"]["kernel"]),
                               np.asarray(ref_p["w"]["kernel"]),
                               rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------- flash attention

@pytest.mark.parametrize("s,skv,h,hkv,d", [
    (64, 64, 2, 2, 32), (128, 128, 4, 2, 32), (96, 96, 2, 1, 64),
    (64, 128, 2, 2, 32),
])
def test_flash_attention_matches_ref(s, skv, h, hkv, d):
    rng = np.random.RandomState(s + skv)
    q = jnp.asarray(rng.randn(2, s, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(2, skv, hkv, d), jnp.float32)
    v = jnp.asarray(rng.randn(2, skv, hkv, d), jnp.float32)
    got = ops.flash_attention(q, k, v, interpret=True)
    want = ref.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("window", [16, 48])
def test_flash_attention_sliding_window(window):
    rng = np.random.RandomState(window)
    q = jnp.asarray(rng.randn(1, 128, 2, 32), jnp.float32)
    k = jnp.asarray(rng.randn(1, 128, 2, 32), jnp.float32)
    v = jnp.asarray(rng.randn(1, 128, 2, 32), jnp.float32)
    got = ops.flash_attention(q, k, v, window=window, interpret=True)
    want = ref.flash_attention_ref(q, k, v, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_flash_attention_softcap_and_bf16():
    rng = np.random.RandomState(7)
    q = jnp.asarray(rng.randn(1, 64, 2, 32), jnp.bfloat16)
    k = jnp.asarray(rng.randn(1, 64, 2, 32), jnp.bfloat16)
    v = jnp.asarray(rng.randn(1, 64, 2, 32), jnp.bfloat16)
    got = ops.flash_attention(q, k, v, softcap=50.0, interpret=True)
    want = ref.flash_attention_ref(q, k, v, softcap=50.0)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=5e-2, atol=5e-2)


def test_flash_attention_equals_model_sdpa():
    """Kernel agrees with the model's attention path (same masking)."""
    from repro.nn import attention as A
    cfg = A.AttnConfig(d_model=64, n_heads=2, n_kv_heads=2, head_dim=32)
    rng = np.random.RandomState(9)
    q = jnp.asarray(rng.randn(1, 64, 2, 32), jnp.float32)
    k = jnp.asarray(rng.randn(1, 64, 2, 32), jnp.float32)
    v = jnp.asarray(rng.randn(1, 64, 2, 32), jnp.float32)
    mask = A.causal_mask(64, 64)[None]
    want = A._sdpa(q, k, v, mask, cfg).reshape(1, 64, 2, 32)
    got = ops.flash_attention(q, k, v, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
