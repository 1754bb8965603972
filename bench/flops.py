"""Model FLOPs from shapes: the multiply-adds that the published model
needs, counted as two operations each. Elementwise work (norms,
activations, pooling, the loss) is left out, as is anything recomputed.
A training step needs three times the forward pass (forward, and the
backward's two products per forward product)."""

from __future__ import annotations

TRAIN_FACTOR = 3


def resnet_forward(cfg) -> float:
    """Forward FLOPs of one image through ResNet v1.5 (convolutions and
    the head; the stride of a down-sampling block on its 3x3 conv)."""
    size, width = cfg["image_size"], cfg["width"]
    hw = -(-size // 2)                       # stem: 7x7, stride 2
    flops = 2 * hw * hw * 7 * 7 * 3 * width
    hw = -(-hw // 2)                         # 3x3 max-pool, stride 2
    cin = width
    for s, n in enumerate(cfg["stage_sizes"]):
        inner = width * 2 ** s
        for b in range(n):
            stride = 2 if (s > 0 and b == 0) else 1
            out = -(-hw // stride)
            flops += 2 * hw * hw * cin * inner               # 1x1
            flops += 2 * out * out * 9 * inner * inner       # 3x3, strided
            flops += 2 * out * out * inner * 4 * inner       # 1x1
            if cin != 4 * inner:
                flops += 2 * out * out * cin * 4 * inner     # projection
            cin, hw = 4 * inner, out
    return float(flops + 2 * cin * cfg["num_classes"])


def mamba2_forward_per_token(cfg) -> float:
    """Forward FLOPs per token of a Mamba-2 LM with chunked SSD: the
    projections, the depthwise convolution, the SSD's products (within a
    chunk only the causal half, Q/2 positions on average), and the tied
    output head."""
    d, N, P, Q = cfg["d_model"], cfg["ssm_state"], cfg["ssm_head_dim"], \
        cfg["ssm_chunk"]
    di = cfg["expand"] * d
    H = di // P
    layer = (2 * d * (2 * di + 2 * N + H)            # in_proj
             + 2 * di * d                            # out_proj
             + 2 * cfg["conv_width"] * (di + 2 * N)  # causal conv
             + Q * N + Q * H * P                     # intra-chunk, causal
             + 2 * H * P * N                         # chunk states
             + 2 * H * P * N)                        # inter-chunk output
    return float(cfg["n_layers"] * layer + 2 * d * cfg["vocab"])
