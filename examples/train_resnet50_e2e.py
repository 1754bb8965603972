"""End-to-end driver: the paper's exact model (ResNet-50, 25.6M params)
trained with the complete recipe -- 2D-torus grad sync, LARS, label
smoothing, batch-size control, SyncBN, bf16 compute -- on every device
present.

    PYTHONPATH=src python examples/train_resnet50_e2e.py [--steps 100]
        [--batches 128,256] [--image-size 224] [--classes 1000]

The defaults are the paper's: 224 px, 1000 classes, per-chip batches that
grow from 128 to 256 (one batch-control stage each, ``--steps`` steps per
stage). On a CPU, pass a small ``--image-size`` and ``--batches``, and
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` for eight virtual
devices. History is printed and written to
experiments/e2e_resnet50_history.json.
"""

import argparse
import json
import os

from repro.launch.train import resnet_job, use_compile_cache
from repro.models import resnet


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=100,
                    help="steps per batch-control stage")
    ap.add_argument("--batches", default="128,256",
                    help="comma per-chip batch sizes, one stage each")
    ap.add_argument("--image-size", type=int, default=224)
    ap.add_argument("--classes", type=int, default=1000)
    args = ap.parse_args()

    use_compile_cache()
    cfg = resnet.ResNetConfig.resnet50(num_classes=args.classes,
                                       image_size=args.image_size)
    trainer, state, _ = resnet_job(
        cfg, per_chip_batches=[int(b) for b in args.batches.split(",")],
        steps_per_stage=args.steps, log_every=10)
    print(f"ResNet-50: {resnet.num_params(state.params) / 1e6:.1f}M params, "
          f"{args.image_size}px, mesh {dict(trainer.mesh.shape)}, "
          f"plan {trainer.plan.total_steps} steps")
    state, history = trainer.run(state)

    os.makedirs("experiments", exist_ok=True)
    with open("experiments/e2e_resnet50_history.json", "w") as f:
        json.dump(history, f, indent=1)
    steps = [h for h in history if h.get("kind") == "metric"]
    print(f"loss {steps[0]['loss']:.3f} -> {steps[-1]['loss']:.3f} "
          f"({int(state.step)} steps)")


if __name__ == "__main__":
    main()
