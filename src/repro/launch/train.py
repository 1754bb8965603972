"""Training launcher, and the jobs and mesh every entry point shares.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b --smoke \
        --steps 20 [--sync torus2d] [--schedule B] [--batch-stages 2,4]

``--smoke`` trains the reduced config on a data-parallel mesh built from
the devices present (``device_mesh``): (1, 1) on one TPU chip, (2, 2) on a
four-chip host. On the CPU, ask for virtual devices yourself
(``XLA_FLAGS=--xla_force_host_platform_device_count=8`` gives (2, 4)).
Without ``--smoke`` the full config trains on the production pod mesh.
The paper's recipe -- 2D-torus gradient sync, LARS, label smoothing,
batch-size control -- is the default.

``resnet_job`` builds the paper's own workload (ResNet-50 v1.5 on
synthetic ImageNet) as a ``Trainer``; ``examples/train_resnet50_e2e.py``
and ``chip_smoke.py`` run it.
"""

from __future__ import annotations

import argparse
import math
import os

import jax
import jax.numpy as jnp

from repro.configs import registry
from repro.core import losses
from repro.core.batch_control import build_plan
from repro.core.grad_sync import GradSyncConfig
from repro.core.schedules import BatchSchedule, BatchStage
from repro.core.topology import factorize
from repro.data import augment
from repro.data.synthetic import SyntheticImageNet, SyntheticTokens
from repro.models import resnet
from repro.models import transformer as T
from repro.train.state import TrainState
from repro.train.trainer import Trainer, TrainerConfig

#: ImageNet-1k training images: the epoch the schedules count in.
IMAGENET_TRAIN_IMAGES = 1_281_167

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and left
    alone. Otherwise the cache lives at ``<repo>/.jax_cache``: one fixed
    path, so that a later run finds what an earlier one compiled. Call
    before the first compile.
    """
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(_REPO_ROOT, ".jax_cache"))
    return jax.config.jax_compilation_cache_dir


def device_mesh(devices=None) -> jax.sharding.Mesh:
    """``(dy, dx)`` data-parallel mesh over ``devices`` (default: every
    device present), factorized as square as the paper's Table 4 grids:
    (1, 1) on one chip, (2, 2) on four, (2, 4) on eight."""
    devices = jax.devices() if devices is None else list(devices)
    return jax.make_mesh(factorize(len(devices)), ("dy", "dx"),
                         devices=devices)


@jax.jit
def _augment_batch(key, images):
    """Paper §3.2 augmentation pipeline, on the device."""
    return augment.augment(key, images, out_hw=images.shape[1:3])


def resnet_job(cfg: resnet.ResNetConfig, *, per_chip_batches=(256,),
               steps_per_stage: int = 3, strategy: str = "torus2d",
               mesh=None, log_every: int = 1):
    """The paper's ResNet-50 job as ``(trainer, initial_state, data_fn)``.

    One batch-control stage per entry of ``per_chip_batches``, each
    ``steps_per_stage`` steps long, counted in epochs of ImageNet-1k so the
    LR and momentum schedules (config B) see the start of a real run.
    Synthetic ImageNet at ``cfg.image_size`` with ``cfg.num_classes``
    classes, augmented on the device; label smoothing 0.1, LARS, and a
    bf16 gradient exchange with ``strategy``. Images, labels and initial
    weights are all made from seed 0, so two jobs see the same ones.
    """
    mesh = device_mesh() if mesh is None else mesh
    dp_axes = tuple(mesh.axis_names)
    n_workers = mesh.devices.size
    data = SyntheticImageNet(num_classes=cfg.num_classes,
                             image_size=cfg.image_size)

    def data_fn(i, gb):
        images, labels = data.batch(i, gb)
        key = jax.random.fold_in(jax.random.key(0), i)
        return _augment_batch(key, images), labels

    def loss_fn(params, batch, dp_axes):
        images, labels = batch
        logits = resnet.apply(params, images, cfg, dp_axes=dp_axes)
        return (losses.label_smoothing_xent(logits, labels, 0.1),
                jnp.zeros((), jnp.float32))

    stages, epoch = [], 0.0
    for b in per_chip_batches:
        span = steps_per_stage * b * n_workers / IMAGENET_TRAIN_IMAGES
        stages.append(BatchStage(epoch, epoch + span, b))
        epoch += span
    plan = build_plan(BatchSchedule(tuple(stages)),
                      dataset_size=IMAGENET_TRAIN_IMAGES,
                      n_workers=n_workers)
    trainer = Trainer(
        mesh=mesh, dp_axes=dp_axes, loss_fn=loss_fn,
        cfg=TrainerConfig(schedule="B", label_smoothing=0.1,
                          grad_sync=GradSyncConfig(strategy=strategy,
                                                   comm_dtype=jnp.bfloat16),
                          log_every=log_every),
        plan=plan, data_fn=data_fn)
    state = TrainState.create(resnet.init(jax.random.key(0), cfg))
    return trainer, state, data_fn


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list(registry.ARCH_IDS))
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config on the devices present")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--sync", default="torus2d",
                    choices=["psum", "ring", "hierarchical", "torus2d"])
    ap.add_argument("--schedule", default="B", choices=["A", "B"])
    ap.add_argument("--label-smoothing", type=float, default=0.1)
    ap.add_argument("--batch-stages", default="2,4",
                    help="comma per-worker batch sizes, staged equally")
    ap.add_argument("--checkpoint-dir", default=None)
    args = ap.parse_args()

    use_compile_cache()
    if args.smoke:
        cfg = registry.get_smoke(args.arch)
        mesh = device_mesh()
        dp_axes = tuple(mesh.axis_names)
    else:
        from repro.launch.mesh import dp_axes_of, make_production_mesh
        cfg = registry.get(args.arch)
        mesh = make_production_mesh()
        dp_axes = dp_axes_of(mesh)
    n_workers = math.prod(mesh.shape[a] for a in dp_axes)

    data = SyntheticTokens(vocab=cfg.vocab)

    def loss_fn(params, batch, dp):
        tokens, labels = batch
        logits, aux = T.forward(params, tokens, cfg)
        return losses.label_smoothing_xent(
            logits, labels, args.label_smoothing), aux

    sizes = [int(s) for s in args.batch_stages.split(",")]
    span = 1.0
    stages = tuple(
        BatchStage(i * span, (i + 1) * span, s) for i, s in enumerate(sizes))
    plan = build_plan(BatchSchedule(stages), dataset_size=n_workers * 512,
                      n_workers=n_workers, max_steps=args.steps)

    trainer = Trainer(
        mesh=mesh, dp_axes=dp_axes, loss_fn=loss_fn,
        cfg=TrainerConfig(
            schedule=args.schedule, label_smoothing=args.label_smoothing,
            grad_sync=GradSyncConfig(strategy=args.sync, fuse=False,
                                     comm_dtype=jnp.bfloat16),
            log_every=5),
        plan=plan, data_fn=lambda i, gb: data.batch(i, gb, args.seq),
        checkpoint_dir=args.checkpoint_dir)

    print(f"training {cfg.name} ({cfg.num_params() / 1e6:.1f}M params) "
          f"with sync={args.sync} schedule={args.schedule}")
    state = TrainState.create(T.init(jax.random.key(0), cfg))
    state, history = trainer.run(state)
    print(f"done: loss {history[0]['loss']:.3f} -> {history[-1]['loss']:.3f}")


if __name__ == "__main__":
    main()
