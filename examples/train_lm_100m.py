"""End-to-end driver: train a ~100M-param qwen3-family LM for a few hundred
steps with the paper's distributed recipe (2D-torus grad sync + LARS +
label smoothing + batch-size control).

    PYTHONPATH=src python examples/train_lm_100m.py [--steps 200]

On the 8-host-device CPU mesh this takes a while; --steps 40 for a quick
pass. Checkpoints land in /tmp/repro_lm100m.
"""

import argparse
import dataclasses
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp

from repro.configs import registry
from repro.core import losses
from repro.core.grad_sync import GradSyncConfig
from repro.core.schedules import BatchSchedule, BatchStage
from repro.core.batch_control import build_plan
from repro.data.synthetic import SyntheticTokens
from repro.models import transformer as T
from repro.train.state import TrainState
from repro.train.trainer import Trainer, TrainerConfig


def lm_100m() -> T.ArchConfig:
    """qwen3 family scaled to ~100M params (8L, d=512, vocab 32k)."""
    base = registry.get("qwen3-1.7b")
    return dataclasses.replace(
        base, name="qwen3-100m", n_layers=8, d_model=512, n_heads=8,
        n_kv_heads=4, head_dim=64, d_ff=1536, vocab=32_000)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--seq", type=int, default=256)
    args = ap.parse_args()

    mesh = jax.make_mesh((2, 4), ("dy", "dx"))
    cfg = lm_100m()
    n_params = cfg.num_params()
    print(f"arch {cfg.name}: {n_params / 1e6:.1f}M params")

    data = SyntheticTokens(vocab=cfg.vocab)

    def loss_fn(params, batch, dp_axes):
        tokens, labels = batch
        logits, aux = T.forward(params, tokens, cfg)
        return losses.label_smoothing_xent(logits, labels, 0.1), aux

    sched = BatchSchedule((BatchStage(0, 0.5, 1), BatchStage(0.5, 2.0, 2)))
    plan = build_plan(sched, dataset_size=8 * 2048, n_workers=8,
                      max_steps=args.steps)
    trainer = Trainer(
        mesh=mesh, dp_axes=("dy", "dx"), loss_fn=loss_fn,
        cfg=TrainerConfig(schedule="B",
                          grad_sync=GradSyncConfig(strategy="torus2d",
                                                   fuse=False,
                                                   comm_dtype=jnp.bfloat16)),
        plan=plan, data_fn=lambda i, gb: data.batch(i, gb, args.seq),
        checkpoint_dir="/tmp/repro_lm100m")

    state = TrainState.create(T.init(jax.random.key(0), cfg))
    state, history = trainer.run(state)
    steps = [h for h in history if h["kind"] == "metric"]
    print(f"loss {steps[0]['loss']:.3f} -> {steps[-1]['loss']:.3f} "
          f"over {int(state.step)} steps")


if __name__ == "__main__":
    main()
