import json
import os

from bench import flops

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs")


def _config(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


def test_resnet50_forward_is_8_2_gflop():
    # 4.09 G multiply-adds at 224 px (v1.5: stride on the 3x3 conv)
    got = flops.resnet_forward(_config("resnet50"))
    assert abs(got - 8.18e9) / 8.18e9 < 0.005, got


def test_mamba2_forward_per_token():
    cfg = _config("mamba2-2.7b-8l")
    got = flops.mamba2_forward_per_token(cfg)
    d, di, N, H, P, Q, V = 2560, 5120, 128, 80, 64, 256, 50280
    layer = (2 * d * (2 * di + 2 * N + H) + 2 * di * d + 2 * 4 * (di + 2 * N)
             + Q * N + Q * H * P + 4 * H * P * N)
    assert got == 8 * layer + 2 * d * V
    # the projections and the head are 6 x 450M parameters per token in
    # training; the SSD adds about 5 %
    assert 2.75e9 < flops.TRAIN_FACTOR * got < 2.85e9
