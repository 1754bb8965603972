"""Deterministic synthetic datasets (no ImageNet in this container).

Two generators:

* ``SyntheticImageNet`` -- class-conditional Gaussian-blob images: each of
  the K classes has a fixed random template; a sample is template + noise.
  Linear-separable enough that ResNet training shows real convergence
  signal.
* ``SyntheticTokens`` -- order-2 Markov token stream with a fixed random
  transition matrix; gives language-model training a learnable signal.

Both are stateless: batch ``i`` is a pure function of (seed, i), so any
worker can produce its shard without coordination -- the same property a
sharded tf.data/grain pipeline provides on the real cluster.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class SyntheticImageNet:
    num_classes: int = 1000
    image_size: int = 224
    seed: int = 0
    noise: float = 0.8

    def templates(self, downsample: int = 8):
        """Fixed per-class low-res templates (deterministic in seed)."""
        k = jax.random.key(self.seed)
        hw = self.image_size // downsample
        return jax.random.normal(k, (self.num_classes, hw, hw, 3))

    def batch(self, index: int, batch_size: int):
        """Batch ``index`` -> (images (B,H,W,3) fp32, labels (B,) int32)."""
        k = jax.random.fold_in(jax.random.key(self.seed + 1), index)
        k1, k2 = jax.random.split(k)
        labels = jax.random.randint(k1, (batch_size,), 0, self.num_classes)
        tmpl = self.templates()[labels]                     # (B, hw, hw, 3)
        up = jnp.repeat(jnp.repeat(tmpl, self.image_size // tmpl.shape[1], 1),
                        self.image_size // tmpl.shape[2], 2)
        imgs = up + self.noise * jax.random.normal(
            k2, (batch_size, self.image_size, self.image_size, 3))
        return imgs, labels


@dataclasses.dataclass(frozen=True)
class SyntheticTokens:
    vocab: int = 32000
    seed: int = 0
    order_dim: int = 64     # rank of the transition structure

    def batch(self, index: int, batch_size: int, seq_len: int):
        """(tokens, labels): labels = next token (shifted).

        Sequential structure: with prob 0.5 the next token is the
        deterministic map f(prev) = (prev*7 + 11) % V, else fresh random --
        a first-order Markov chain an LM can actually learn.
        """
        k = jax.random.fold_in(jax.random.key(self.seed + 2), index)
        k1, k2, k3 = jax.random.split(k, 3)
        rnd = jax.random.randint(k1, (batch_size, seq_len + 1), 0, self.vocab)
        use_det = jax.random.bernoulli(k2, 0.5, (batch_size, seq_len))

        def step(prev, inp):
            r, b = inp
            nxt = jnp.where(b, (prev * 7 + 11) % self.vocab, r)
            return nxt, nxt

        _, rest = jax.lax.scan(
            step, rnd[:, 0], (rnd[:, 1:].T, use_det.T))
        tokens = jnp.concatenate([rnd[:, :1], rest.T], axis=1)
        return tokens[:, :-1].astype(jnp.int32), tokens[:, 1:].astype(jnp.int32)
