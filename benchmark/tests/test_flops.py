"""``flops_per_item`` against arithmetic done by hand."""

import json
import os

import pytest

from benchmark.families import resnet_vd, transformer_lm

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(__file__)), "configs")


def load(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


def test_resnet50_vd_forward_is_the_published_4_35_gmacs():
    config = load("resnet50_vd")
    convs, dense = resnet_vd._conv_shapes(config)
    assert len(convs) == 3 + 16 * 3 + 4          # stem, 16 bottlenecks, 4 shortcuts
    macs = sum(k * k * cin * cout * h * h for k, cin, cout, h in convs)
    macs += dense[0] * dense[1]
    # ResNet-50-D: 4.3 GFLOPs (multiply-adds) in the paper's table 5
    assert macs == pytest.approx(4.35e9, rel=0.02)
    # forward + backward, 2 operations a multiply-add: about 26 GFLOP an image
    assert resnet_vd.flops_per_item(config) == pytest.approx(26.0e9, rel=0.03)


def test_resnet_first_convolution_by_hand():
    config = load("resnet50_vd")
    convs, _ = resnet_vd._conv_shapes(config)
    assert convs[0] == (3, 3, 32, 112)           # 3x3, 3 -> 32, stride 2 on 224
    assert convs[3] == (1, 64, 64, 56)           # first bottleneck after the pool


def test_mistral_by_hand():
    config = load("mistral_7b")
    d, f, v, t = 4096, 14336, 32000, 4096
    layer = d * d + 2 * d * 1024 + d * d + 3 * d * f     # q, k+v, o, SwiGLU
    assert layer == 218_103_808
    layers = config["num_hidden_layers"]
    assert transformer_lm.matmul_params(config) == layers * layer + d * v
    attention = 3 * 2 * t * 32 * 128 * layers            # causal, forward x 3
    want = 6 * (layers * layer + d * v) + attention
    assert transformer_lm.flops_per_item(config) == pytest.approx(want)
    one = dict(config, num_hidden_layers=1)
    assert transformer_lm.flops_per_item(one) == pytest.approx(2.196e9, rel=0.002)
    assert transformer_lm.flops_per_item(dict(config, num_hidden_layers=2)) == (
        pytest.approx(3.605e9, rel=0.002)
    )
    # the kernels' own work: forward 2*B*H*T^2*D, backward 2.5 times that
    assert transformer_lm.kernel_flops(one, 2) == pytest.approx(
        3.5 * 2 * 2 * 32 * t * t * 128
    )
