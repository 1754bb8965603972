"""Language-model jobs, built as ``repro.launch.train.main`` builds its
job (the program has no job function for them): ``Trainer`` over
``transformer.forward``, label-smoothed cross-entropy, schedule B, LARS,
the configured exchange in bf16 with ``fuse=False``, and ``SyntheticTokens``
batches. One departure from ``main``: the batch function is jitted with the
step index as an argument. Called eagerly, as ``main`` calls it, it traces
and compiles its ``lax.scan`` anew on every step."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import flops
from bench.reference import mamba2 as ref

SAMPLE = "tokens"
STEP_PROGRAM = "jit_step"
#: rows the reference differentiates at a time (the loss is a mean over
#: independent sequences), so that 8 x 2048 tokens fit beside the weights
REFERENCE_ROWS = 2


def arch(config):
    from repro.models import transformer as T

    d, p = config["d_model"], config["ssm_head_dim"]
    heads = config["expand"] * d // p
    return T.ArchConfig(
        name=config["model"].split(" (")[0], arch_type="ssm",
        n_layers=config["n_layers"], d_model=d, n_heads=heads, n_kv_heads=1,
        head_dim=p, d_ff=0, vocab=config["vocab"], pattern=("ssd",),
        mlp="none", ssm_state=config["ssm_state"], ssm_head_dim=p,
        ssm_chunk=config["ssm_chunk"], norm=config["norm"],
        tie_embeddings=config["tie_embeddings"],
        compute_dtype=jnp.dtype(config["compute_dtype"]),
        remat=config["remat"])


def build(config, traffic, mesh):
    from repro.core import losses
    from repro.core.batch_control import build_plan
    from repro.core.grad_sync import GradSyncConfig
    from repro.core.schedules import BatchSchedule, BatchStage
    from repro.data.synthetic import SyntheticTokens
    from repro.models import transformer as T
    from repro.train.trainer import Trainer, TrainerConfig

    cfg = arch(config)
    recipe = config["recipe"]
    dp_axes = tuple(mesh.axis_names)
    chips = mesh.devices.size
    seq = traffic["seq_len"]
    smoothing = recipe["label_smoothing"]
    data = SyntheticTokens(vocab=cfg.vocab)

    def loss_fn(params, batch, dp):
        tokens, labels = batch
        logits, aux = T.forward(params, tokens, cfg)
        return losses.label_smoothing_xent(logits, labels, smoothing), aux

    gb = traffic["per_chip_batch"] * chips
    size = epoch_samples(config, traffic, chips)
    plan = build_plan(
        BatchSchedule((BatchStage(0.0, traffic["plan_steps"] * gb / size,
                                  traffic["per_chip_batch"]),)),
        dataset_size=size, n_workers=chips)
    ex = recipe["exchange"]
    trainer = Trainer(
        mesh=mesh, dp_axes=dp_axes, loss_fn=loss_fn,
        cfg=TrainerConfig(
            schedule="B", label_smoothing=smoothing,
            grad_sync=GradSyncConfig(strategy=ex["strategy"],
                                     fuse=ex["fuse"],
                                     comm_dtype=jnp.dtype(ex["dtype"])),
            log_every=traffic["log_every"]),
        plan=plan,
        data_fn=jax.jit(lambda i, b: data.batch(i, b, seq), static_argnums=1))
    return trainer, trainer.data_fn


def samples_per_step(config, traffic, chips):
    return traffic["per_chip_batch"] * chips * traffic["seq_len"]


def epoch_samples(config, traffic, chips):
    """Sequences per epoch, as ``launch.train.main`` sizes its plan."""
    return config["recipe"]["epoch_samples_per_chip"] * chips


def flops_per_step(config, traffic, chips):
    return (flops.TRAIN_FACTOR * flops.mamba2_forward_per_token(config)
            * samples_per_step(config, traffic, chips))


def init(key, config):
    return ref.init(key, config)


def reference_loss(config, quant=None):
    smoothing = config["recipe"]["label_smoothing"]
    return lambda params, batch: ref.loss(params, batch, config, smoothing,
                                          quant)
