"""Smoke run of the paper's ResNet-50 trainer on TPU chips.

    python chip_smoke.py               # phases 1-3 on the chips present
    python chip_smoke.py --four-chip   # phase 4 only, on a 2x2 host

The quickest proof that the system still starts, trains and computes right
on the chip. Every time it prints is a smoke timing, not a benchmark.

1. train: ResNet-50 v1.5 at 224 px with 1000 classes, random weights and
   synthetic ImageNet made from a seed, the paper's recipe (augmentation on
   the device, LARS, label smoothing 0.1, LR schedule B, bf16 torus2d
   exchange) through ``Trainer``, on a mesh of every chip present. Two
   batch-control stages of three steps, 128 then 256 images per chip, so
   the step recompiles once. Fails on a non-finite loss, a skipped step, or
   a downgrade or checkpoint-failure event.
2. logits: the trained weights' forward pass for 8 images on the chip, in
   bf16, against ``resnet.apply`` in float32 on the host CPU.
3. kernels: the Pallas LARS update over every ResNet-50 leaf that LARS
   scales, and the label-smoothed cross-entropy at (256, 1000), compiled
   by Mosaic and compared with ``kernels/ref.py``.
4. ``--four-chip``: the train job at 256 images per chip on a 2x2 mesh,
   once with torus2d and once with psum, from the same weights and
   batches; fails unless the parameters agree to bf16-exchange precision.
   Prints each program's collectives before and after compilation.

Exits nonzero, printing no result, unless JAX's first device is a TPU. The
last line of standard output is ``{"ok": true, "device": {...}}``.
"""

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.core import lars  # noqa: E402
from repro.launch import hlo_stats  # noqa: E402
from repro.launch.train import (device_mesh, resnet_job,  # noqa: E402
                                use_compile_cache)
from repro.models import resnet  # noqa: E402
from repro.train.trainer import make_train_step  # noqa: E402

BAD_EVENTS = ("grad_sync_downgrade", "grad_sync_strategy_rejected",
              "checkpoint_failed")


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def rel_err(a, b) -> float:
    """Relative Frobenius distance of ``a`` from ``b``."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def run_job(trainer, state):
    """``trainer.run`` with each step's wall time taken from its log line
    (one per step at ``log_every=1``, after the step's result is read)."""
    stamps = []

    def log(msg):
        if msg.startswith("step "):
            stamps.append(time.monotonic())
        print(f"  {msg}")

    t0 = time.monotonic()
    state, history = trainer.run(state, log=log)
    walls = np.diff([t0] + stamps)
    rows = [h for h in history if h["kind"] == "metric"]
    events = [h["event"] for h in history if h["kind"] == "event"]
    check(len(rows) == len(walls) == trainer.plan.total_steps,
          f"{len(rows)} step rows for {trainer.plan.total_steps} steps")
    check(all(np.isfinite(h["loss"]) for h in rows),
          f"non-finite loss: {[h['loss'] for h in rows]}")
    check(all(h["skipped"] == 0 for h in rows), "a step was skipped")
    bad = [e for e in events if e in BAD_EVENTS]
    check(not bad, f"events {bad}")
    check(int(state.step) == trainer.plan.total_steps,
          f"state.step {int(state.step)}")
    return state, rows, walls


def train_phase(cfg, per_chip_batches, steps):
    trainer, state, data_fn = resnet_job(
        cfg, per_chip_batches=per_chip_batches, steps_per_stage=steps,
        strategy="torus2d")
    print(f"[train] mesh {dict(trainer.mesh.shape)}, strategy "
          f"{trainer.cfg.grad_sync.strategy}, per-chip batches "
          f"{list(per_chip_batches)}, {steps} steps each")
    state, rows, walls = run_job(trainer, state)
    for k, stage in enumerate(trainer.plan.stages):
        w = walls[k * steps:(k + 1) * steps]
        print(f"[train] global batch {stage.global_batch}: first step "
              f"{w[0]:.2f} s (compile included), then "
              f"{', '.join(f'{x:.3f}' for x in w[1:])} s per step "
              "(smoke timing, not a benchmark)")
    print(f"[train] losses {[round(h['loss'], 4) for h in rows]}")
    stats = jax.devices()[0].memory_stats() or {}
    print(f"[train] peak_bytes_in_use {stats.get('peak_bytes_in_use')}")
    return state, data_fn


def logits_phase(cfg, params, data_fn, n=8):
    """Chip bf16 forward vs CPU float32 forward of the same weights and
    images. BN uses the 8 images' own statistics in both."""
    images, _ = data_fn(10**6, n)
    host = (jax.device_get(params), np.asarray(images))
    chip, cpu = jax.devices()[0], jax.devices("cpu")[0]

    def forward(c, device):
        p, x = jax.device_put(host, device)
        return np.asarray(jax.jit(
            lambda p, x: resnet.apply(p, x, c))(p, x))

    f32 = dataclasses.replace(cfg, compute_dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        ref = forward(f32, cpu)
    got = forward(cfg, chip)
    check(got.shape == (n, cfg.num_classes) and np.isfinite(got).all(),
          f"chip logits shape {got.shape} or non-finite")
    err = rel_err(got, ref)
    # Tolerance from bf16 compute: the same bf16 forward on the CPU is as
    # far from float32 as bf16's 8 significant bits put it on these weights
    # and images (each of 53 convs rounds its inputs; the error grows with
    # the residual branches' BN scales). The chip rounds at the same
    # points but accumulates in another order, so allow twice that, plus
    # one bf16 unit roundoff (2**-8) for weights where it is near zero.
    bf16_err = rel_err(forward(cfg, cpu), ref)
    tol = 2 * bf16_err + 2.0 ** -8
    print(f"[logits] chip bf16 vs cpu f32: rel err {err:.3e} "
          f"(cpu bf16: {bf16_err:.3e}, tolerance {tol:.3e})")
    check(err <= tol, f"logits rel err {err:.3e} > {tol:.3e}")


def kernel_phase(params):
    from repro.kernels import ops, ref
    check(not ops.INTERPRET, "kernels/ops.py would run the interpreter")
    params = jax.device_put(jax.device_get(params), jax.devices()[0])
    cfg = lars.LARSConfig()
    leaves = [p for path, p in jax.tree_util.tree_flatten_with_path(params)[0]
              if not lars._is_skip(path, cfg)]
    keys = jax.random.split(jax.random.key(1), len(leaves))
    grads = [0.01 * jax.random.normal(k, p.shape) for k, p in
             zip(keys, leaves)]
    moms = [0.1 * g for g in grads]
    kw = dict(lr=0.2, mom=0.9, eta=cfg.eta, weight_decay=cfg.weight_decay,
              eps=cfg.eps)

    def each(update):
        return jax.jit(lambda ps, gs, vs: [
            update(p, g, v, **kw) for p, g, v in zip(ps, gs, vs)])

    compiled = each(ops.lars_update).lower(leaves, grads, moms).compile()
    check("tpu_custom_call" in compiled.as_text(), "no tpu_custom_call in "
          "the compiled LARS update")
    got = compiled(leaves, grads, moms)
    want = each(ref.lars_update_ref)(leaves, grads, moms)
    # both sides are f32 elementwise math on the chip, with norms from XLA
    # reductions: they differ by a few f32 ulps
    worst = 0.0
    for (p, v), (pr, vr) in zip(got, want):
        for a, b in ((p, pr), (v, vr)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)
            worst = max(worst, float(jnp.max(jnp.abs(a - b))))
    print(f"[kernels] lars_update over {len(leaves)} ResNet-50 leaves: "
          f"max abs diff vs ref {worst:.3e}")

    k1, k2 = jax.random.split(jax.random.key(2))
    logits = 4.0 * jax.random.normal(k1, (256, 1000))
    labels = jax.random.randint(k2, (256,), 0, 1000)
    compiled = jax.jit(lambda x, y: ops.ls_xent(x, y, smoothing=0.1)).lower(
        logits, labels).compile()
    check("tpu_custom_call" in compiled.as_text(), "no tpu_custom_call in "
          "the compiled ls_xent")
    got = compiled(logits, labels)
    want = jax.jit(lambda x, y: ref.ls_xent_ref(x, y, 0.1))(logits, labels)
    # f32 exp/log in the kernel vs XLA's log_softmax: a few ulps of a loss
    # near 10
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)
    print(f"[kernels] ls_xent (256, 1000): max abs diff vs ref "
          f"{float(jnp.max(jnp.abs(got - want))):.3e}")


def stablehlo_counts(text: str) -> dict[str, int]:
    return {k: text.count(f"stablehlo.{k}")
            for k in ("all_reduce", "reduce_scatter", "all_gather")}


def four_chip_phase(cfg, per_chip, steps):
    check(len(jax.devices()) == 4,
          f"--four-chip needs 4 chips, found {len(jax.devices())}")
    mesh = device_mesh()
    params, p0 = {}, None
    for strategy in ("torus2d", "psum"):
        trainer, state, data_fn = resnet_job(
            cfg, per_chip_batches=(per_chip,), steps_per_stage=steps,
            strategy=strategy, mesh=mesh)
        if p0 is None:
            p0 = jax.device_get(state.params)
        # the program Trainer runs: same function, same arguments placed as
        # Trainer places them; its first step loads this compile from the
        # persistent cache
        state = jax.device_put(state, NamedSharding(mesh, P()))
        gb = trainer.plan.stages[0].global_batch
        step = make_train_step(trainer.loss_fn, mesh, trainer.dp_axes,
                               trainer.cfg)
        t0 = time.monotonic()
        lowered = step.lower(state, data_fn(0, gb),
                             jnp.asarray(0.0, jnp.float32),
                             jnp.asarray(gb, jnp.float32))
        compiled = lowered.compile()
        print(f"[four-chip] {strategy}: compiled in "
              f"{time.monotonic() - t0:.1f} s; lowered "
              f"{stablehlo_counts(lowered.as_text())}, compiled "
              f"{hlo_stats.collective_counts(compiled.as_text())}")
        state, rows, walls = run_job(trainer, state)
        print(f"[four-chip] {strategy}: losses "
              f"{[round(h['loss'], 4) for h in rows]}, step walls "
              f"{[round(float(w), 3) for w in walls]} s "
              "(smoke timing, not a benchmark)")
        params[strategy] = jax.device_get(state.params)

    flat = {s: np.concatenate([np.ravel(x) for x in jax.tree.leaves(p)])
            for s, p in params.items()}
    start = np.concatenate([np.ravel(x) for x in jax.tree.leaves(p0)])
    diff = rel_err(flat["torus2d"] - start, flat["psum"] - start)
    # Both strategies sum four chips' bf16 gradients (8 significant bits)
    # in a different order, so each step's update differs by about one bf16
    # roundoff (2**-8), and three steps add up and compound it: 1.3e-2 for
    # ResNetConfig.tiny on 4 CPU devices. An exchange that drops or double
    # counts a chip's share moves the update by tens of percent, since the
    # chips' gradients point different ways. 0.1 lies between the two.
    tol = 0.1
    print(f"[four-chip] torus2d vs psum after {steps} steps: update rel "
          f"diff {diff:.3e} (tolerance {tol:.3e})")
    check(diff <= tol, f"torus2d and psum updates differ by {diff:.3e}")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="only the torus2d-vs-psum phase, on four chips")
    args = ap.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX's first device is "
                         f"{dev.platform!r}")
    print(f"[setup] {len(jax.devices())} x {dev.device_kind}; compile "
          f"cache {use_compile_cache()}")
    cfg = resnet.ResNetConfig.resnet50()
    if args.four_chip:
        four_chip_phase(cfg, per_chip=256, steps=3)
    else:
        state, data_fn = train_phase(cfg, (128, 256), steps=3)
        logits_phase(cfg, state.params, data_fn)
        kernel_phase(state.params)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
