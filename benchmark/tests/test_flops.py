"""``required_flops_per_item`` and the kernel's work against hand
arithmetic, for the three configurations as they are run."""

import json
import os

import pytest

from benchmark.families import decoder_lm, resnet

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load(kind, name):
    with open(os.path.join(HERE, kind, name + ".json")) as f:
        return json.load(f)


def test_lm_d768_token():
    # 12 layers of 12*768^2 matrix parameters, a 768 x 50304 head, 6 FLOPs
    # a parameter; attention 3 * (4*2048*768)/2 a layer
    hand = 6 * (12 * 12 * 768 ** 2 + 768 * 50304) + 12 * 6 * 2048 * 768
    got = decoder_lm.required_flops_per_item(
        load("configs", "lm-d768"), load("traffic", "b8-s2048"))
    assert got == hand
    assert got / 1e9 == pytest.approx(0.855, abs=0.001)


def test_lm_d2048_token_at_the_depth_run():
    config = load("configs", "lm-d2048")
    layers = config["num_hidden_layers"]
    hand = (6 * (layers * 12 * 2048 ** 2 + 2048 * 50304)
            + layers * 6 * 2048 * 2048)
    got = decoder_lm.required_flops_per_item(
        config, load("traffic", "b8-s2048"))
    assert got == hand
    if layers == 6:
        assert got / 1e9 == pytest.approx(2.581, abs=0.001)


def test_flash_kernel_work_d768():
    # 12 layers x 8 sequences x 12 heads; forward 2*S^2*D (causal half of
    # 4*S^2*D), backward twice that
    work = decoder_lm.kernel_work(load("configs", "lm-d768"),
                                  load("traffic", "b8-s2048"))
    calls = 12 * 8 * 12
    assert work["flops"] == calls * 3 * 2 * 2048 ** 2 * 64
    assert work["bytes"] == calls * (12 * 2048 * 64 * 2 + 3 * 2048 * 4)
    # S/4 = 512 FLOPs a byte against the v5e's 197e12 / 819e9 = 240: the
    # FLOPs bind, by about two to one
    assert work["flops"] / work["bytes"] == pytest.approx(512, rel=0.01)
    assert work["flops"] / 197e12 > 2 * work["bytes"] / 819e9


def test_resnet101_image():
    config, traffic = (load("configs", "resnet101"),
                       load("traffic", "b256-synthetic"))
    macs = resnet.conv_macs_per_image(config, traffic)
    # by hand, v1.5 at 224: stem 118.0 M; stages 0.68, 1.04, 5.09 and
    # 0.81 G (first blocks 231.2 / 376.4 / 373.9 / 373.2 M, the others
    # 218.4 / 218.8 each); classifier 2.0 M
    stem = 49 * 3 * 64 * 112 ** 2
    s1 = (64 * 64 + 9 * 64 * 64 + 64 * 256 + 64 * 256) * 56 ** 2 \
        + 2 * (256 * 64 + 9 * 64 * 64 + 64 * 256) * 56 ** 2
    assert stem == 118013952
    assert macs > stem + s1
    assert macs / 1e9 == pytest.approx(7.80, abs=0.02)
    assert resnet.required_flops_per_item(config, traffic) == 6 * macs
    # ResNet-50 the same way lands on the known 4.09 GMAC of v1.5
    r50 = dict(config, stage_sizes=[3, 4, 6, 3])
    assert resnet.conv_macs_per_image(r50, traffic) / 1e9 == pytest.approx(
        4.09, abs=0.02)
