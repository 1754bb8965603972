"""Quickstart: the paper's full recipe on a tiny ResNet in ~a minute on CPU.

    PYTHONPATH=src python examples/quickstart.py

Demonstrates: 2D-torus gradient sync, LARS, label smoothing, batch-size
control, SyncBN, mixed precision -- the complete Sony recipe at toy scale.
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp

from repro.core import losses
from repro.core.grad_sync import GradSyncConfig
from repro.core.schedules import BatchSchedule, BatchStage
from repro.core.batch_control import build_plan
from repro.data.synthetic import SyntheticImageNet
from repro.models import resnet
from repro.train.state import TrainState
from repro.train.trainer import Trainer, TrainerConfig


def main():
    mesh = jax.make_mesh((2, 4), ("dy", "dx"))   # 2x4 logical 2D torus
    cfg = resnet.ResNetConfig.tiny(num_classes=8)
    data = SyntheticImageNet(num_classes=8, image_size=32, noise=0.4)

    def loss_fn(params, batch, dp_axes):
        images, labels = batch
        logits = resnet.apply(params, images, cfg, dp_axes=dp_axes)
        return (losses.label_smoothing_xent(logits, labels, 0.1),
                jnp.zeros((), jnp.float32))

    # batch-size control: 2/worker then 4/worker (paper §2.1, Table 3)
    sched = BatchSchedule((BatchStage(0, 0.1, 2), BatchStage(0.1, 0.25, 4)))
    plan = build_plan(sched, dataset_size=4096, n_workers=8)
    print(f"plan: {plan.total_steps} steps over {len(plan.stages)} stages")

    trainer = Trainer(
        mesh=mesh, dp_axes=("dy", "dx"), loss_fn=loss_fn,
        cfg=TrainerConfig(
            schedule="B", label_smoothing=0.1,
            grad_sync=GradSyncConfig(strategy="torus2d",
                                     comm_dtype=jnp.bfloat16)),
        plan=plan, data_fn=lambda i, gb: data.batch(i, gb))

    state = TrainState.create(resnet.init(jax.random.key(0), cfg))
    state, history = trainer.run(state)
    steps = [h for h in history if h["kind"] == "metric"]
    print(f"final loss {steps[-1]['loss']:.4f} after {int(state.step)} steps")


if __name__ == "__main__":
    main()
