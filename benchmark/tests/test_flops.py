"""flops.py against hand-worked values."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import flops  # noqa: E402

CONFIGS = os.path.join(os.path.dirname(HERE), "configs")
V1 = json.load(open(os.path.join(CONFIGS, "resnet50_v1.json")))
PREACT = json.load(open(os.path.join(CONFIGS, "resnet50_preact.json")))
GPT2_XL = json.load(open(os.path.join(CONFIGS, "gpt2_xl.json")))


def test_resnet_layer_counts():
    # 1 stem + 16 units x 3 + 4 projection shortcuts + the classifier = 54
    for config in (PREACT, V1):
        assert len(flops.resnet_convs(config)) == 54


def test_resnet_first_layers_by_hand():
    convs = flops.resnet_convs(PREACT)
    assert convs[0] == (3, 64, 7, 112, 112)        # 7x7/2 on 224 -> 112
    assert convs[1] == (64, 64, 1, 56, 56)         # after the 3x3/2 pool
    assert convs[2] == (64, 64, 3, 56, 56)
    assert convs[3] == (64, 256, 1, 56, 56)
    assert convs[4] == (64, 256, 1, 56, 56)        # projection shortcut
    # stem by hand: 2 * 3*64*49 * 112*112 = 236,027,904
    ci, co, k, h, w = convs[0]
    assert 2 * ci * co * k * k * h * w == 236027904
    # stage 2, unit 1: the symbol strides the 3x3, gluon v1 the first 1x1
    sym = flops.resnet_convs(PREACT)
    glu = flops.resnet_convs(V1)
    i = 1 + 3 * 3 + 1                               # first conv of stage 2
    assert sym[i] == (256, 128, 1, 56, 56) and sym[i + 1] == (128, 128, 3, 28, 28)
    assert glu[i] == (256, 128, 1, 28, 28) and glu[i + 1] == (128, 128, 3, 28, 28)


def test_resnet_totals_match_the_published_figures():
    # torchvision's ResNet-50 (stride on the 3x3, "v1.5") is quoted at 4.09
    # GMACs = 8.2 GFLOPs forward; the paper's placement (stride on the first
    # 1x1) at 3.8 GMACs (He et al. 2015, table 1: "3.8 x 10^9 FLOPs", where a
    # multiply-add counts once).
    sym = flops.resnet_forward_flops(PREACT)
    glu = flops.resnet_forward_flops(V1)
    assert sym / 2 == pytest.approx(4.09e9, rel=0.01)
    assert glu / 2 == pytest.approx(3.8e9, rel=0.02)
    assert flops.resnet_train_flops_per_image(PREACT) == 3 * sym
    assert 3 * sym == pytest.approx(24.6e9, rel=0.01)
    assert 3 * glu == pytest.approx(23.1e9, rel=0.01)


def test_the_configurations_differ_only_in_source_and_unit_order():
    same = set(V1) - {"source", "source_part", "arch", "departures"}
    assert all(V1[k] == PREACT[k] for k in same)
    assert (V1["arch"], PREACT["arch"]) == ("v1_gluon", "preact_symbol")


def test_gpt2_xl_by_hand():
    # per layer: 1600*4800 + 1600*1600 + 1600*6400 + 6400 + 6400*1600 + 1600
    #            + 4*1600 = 30,734,400 ; 48 layers = 1,475,251,200
    # embeddings 50257*1600 + 1024*1600 = 82,049,600 ; final LN 3,200
    assert flops.gpt2_param_count(GPT2_XL) == 1475251200 + 82049600 + 3200
    assert flops.gpt2_weight_bytes(GPT2_XL) == 2 * 1557304000
    # K and V of one position: 2 * 48 layers * 1600 * 2 bytes
    assert flops.gpt2_kv_bytes_per_position(GPT2_XL) == 307200
    # a tick with 7,500 live positions: weights without the position table
    least = flops.gpt2_decode_tick_min_bytes(GPT2_XL, 7500)
    assert least == 2 * (1557304000 - 1024 * 1600) + 7500 * 307200
