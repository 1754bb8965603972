"""Serving example: batched requests through prefill + KV-cache decode.

    PYTHONPATH=src python examples/serve_lm.py --arch gemma2-27b

Uses the smoke variant of the selected arch (full configs need a pod).
Shows the RequestBatcher packing variable-length prompts into one compiled
shape and greedy decode over the rolling/sliding-window caches.
"""

import argparse
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax

from repro.configs import registry
from repro.models import transformer as T
from repro.serve.decode import RequestBatcher, generate


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2-27b",
                    choices=list(registry.ARCH_IDS))
    ap.add_argument("--new-tokens", type=int, default=12)
    args = ap.parse_args()

    cfg = registry.get_smoke(args.arch)
    print(f"serving {cfg.name} ({cfg.num_params() / 1e6:.1f}M params, "
          f"pattern={cfg.pattern})")
    params = T.init(jax.random.key(0), cfg)

    batcher = RequestBatcher(batch_size=4, seq_len=16)
    requests = [
        [3, 1, 4, 1, 5, 9, 2, 6],
        [2, 7, 1, 8],
        [1, 1, 2, 3, 5, 8, 13],
    ]
    prompts, lens, n = batcher.pack(requests)

    vision = None
    if cfg.vision_tokens:
        vision = jax.random.normal(
            jax.random.key(1), (4, cfg.vision_tokens, cfg.cross_kv_dim))

    toks = generate(params, prompts, cfg, max_new_tokens=args.new_tokens,
                    vision=vision)
    for i, out in enumerate(batcher.unpack(toks, n)):
        print(f"request {i}: prompt={requests[i]} -> generated={out}")


if __name__ == "__main__":
    main()
