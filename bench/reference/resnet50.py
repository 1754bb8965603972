"""Plain float32 ResNet-50 v1.5 (He et al. 2016; the stride of a
down-sampling block on its 3x3 convolution), as the paper trains it.

The parameter tree has the layout the program's step takes (``stem``,
``stages`` of bottleneck blocks, ``head``), so one set of weights made here
feeds both. Departures from the published network, each shared with the
program and tested against it at a small size on the CPU:

- BatchNorm uses the statistics of the whole global batch (the paper's BN
  "without moving average"), with the biased variance and eps 1e-5.
- The last BN scale of every block starts at zero (Goyal et al.), so at
  the first step only the BN parameters, the shortcuts, the stem and the
  head receive a gradient.

``quant="fp8"`` computes every convolution and the head in float8, as fp8
training does (operands in e4m3, the gradient into each product in e5m2,
per-tensor scales): the control.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from bench.reference import common

EPS = 1e-5


def init(key, cfg):
    """He-normal (fan-in) convolutions and head, BN scale 1 and bias 0
    (scale 0 on each block's last BN), zero head bias."""
    width, classes = cfg["width"], cfg["num_classes"]
    keys = iter(jax.random.split(key, 64))

    def conv(kh, cin, cout):
        std = (2.0 / (kh * kh * cin)) ** 0.5
        return {"kernel": std * jax.random.normal(next(keys),
                                                  (kh, kh, cin, cout))}

    def bn(n, zero=False):
        return {"bn_scale": (jnp.zeros if zero else jnp.ones)((n,)),
                "bn_bias": jnp.zeros((n,))}

    params = {"stem": {"conv": conv(7, 3, width), "bn": bn(width)},
              "stages": []}
    cin = width
    for s, n in enumerate(cfg["stage_sizes"]):
        inner = width * 2 ** s
        blocks = []
        for _ in range(n):
            b = {"conv1": conv(1, cin, inner), "bn1": bn(inner),
                 "conv2": conv(3, inner, inner), "bn2": bn(inner),
                 "conv3": conv(1, inner, 4 * inner),
                 "bn3": bn(4 * inner, zero=True)}
            if cin != 4 * inner:
                b["proj"] = conv(1, cin, 4 * inner)
                b["bn_proj"] = bn(4 * inner)
            blocks.append(b)
            cin = 4 * inner
        params["stages"].append(blocks)
    std = (2.0 / cin) ** 0.5
    params["head"] = {"kernel": std * jax.random.normal(next(keys),
                                                        (cin, classes)),
                      "bias": jnp.zeros((classes,))}
    return params


def _q(x, quant):
    return common.fp8(x) if quant == "fp8" else x


def _out(y, quant):
    return common.fp8_grad(y) if quant == "fp8" else y


def _conv(p, x, stride, quant):
    return _out(lax.conv_general_dilated(
        _q(x, quant), _q(p["kernel"], quant), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=common.HIGHEST), quant)


def _bn(p, x):
    mean = x.mean((0, 1, 2))
    var = jnp.square(x - mean).mean((0, 1, 2))
    return (x - mean) / jnp.sqrt(var + EPS) * p["bn_scale"] + p["bn_bias"]


def _block(p, x, stride, quant):
    h = jax.nn.relu(_bn(p["bn1"], _conv(p["conv1"], x, 1, quant)))
    h = jax.nn.relu(_bn(p["bn2"], _conv(p["conv2"], h, stride, quant)))
    h = _bn(p["bn3"], _conv(p["conv3"], h, 1, quant))
    sc = _bn(p["bn_proj"], _conv(p["proj"], x, stride, quant)) \
        if "proj" in p else x
    return jax.nn.relu(h + sc)


def logits(params, images, quant=None):
    """(B, H, W, 3) float images -> (B, classes) float32 logits. Each block
    is recomputed in the backward pass, so the whole batch fits one chip."""
    x = images.astype(jnp.float32)
    h = jax.nn.relu(_bn(params["stem"]["bn"],
                        _conv(params["stem"]["conv"], x, 2, quant)))
    h = lax.reduce_window(h, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          "SAME")
    for s, blocks in enumerate(params["stages"]):
        for b, p in enumerate(blocks):
            stride = 2 if (s > 0 and b == 0) else 1
            h = jax.checkpoint(_block, static_argnums=(2, 3))(
                p, h, stride, quant)
    h = h.mean((1, 2))
    return _out(jnp.dot(_q(h, quant), _q(params["head"]["kernel"], quant),
                        precision=common.HIGHEST), quant) + params["head"]["bias"]


def loss(params, batch, smoothing, quant=None):
    images, labels = batch
    return common.ls_xent(logits(params, images, quant), labels,
                          smoothing).mean()
