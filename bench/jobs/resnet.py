"""ResNet jobs: the program's own ``launch.train.resnet_job``."""

from __future__ import annotations

import jax.numpy as jnp

from bench import flops
from bench.reference import resnet50 as ref

SAMPLE = "images"
#: the XLA program of the train step (``make_train_step``'s ``step``)
STEP_PROGRAM = "jit_step"
#: BatchNorm couples the rows: the reference differentiates the whole batch
REFERENCE_ROWS = None


def build(config, traffic, mesh):
    """``(trainer, data_fn)`` of ``resnet_job`` at the traffic's per-chip
    batch, one batch-control stage long enough for any window. The job's
    own seed-0 state is dropped: the harness makes the weights."""
    from repro.launch.train import resnet_job
    from repro.models import resnet

    cfg = resnet.ResNetConfig(
        stage_sizes=tuple(config["stage_sizes"]), width=config["width"],
        num_classes=config["num_classes"], image_size=config["image_size"],
        compute_dtype=jnp.dtype(config["compute_dtype"]))
    trainer, _, data_fn = resnet_job(
        cfg, per_chip_batches=(traffic["per_chip_batch"],),
        steps_per_stage=traffic["plan_steps"],
        strategy=config["recipe"]["exchange"]["strategy"], mesh=mesh,
        log_every=traffic["log_every"])
    return trainer, data_fn


def samples_per_step(config, traffic, chips):
    return traffic["per_chip_batch"] * chips


def epoch_samples(config, traffic, chips):
    return config["recipe"]["epoch_samples"]


def flops_per_step(config, traffic, chips):
    return (flops.TRAIN_FACTOR * flops.resnet_forward(config)
            * samples_per_step(config, traffic, chips))


def init(key, config):
    return ref.init(key, config)


def reference_loss(config, quant=None):
    smoothing = config["recipe"]["label_smoothing"]
    return lambda params, batch: ref.loss(params, batch, smoothing, quant)
