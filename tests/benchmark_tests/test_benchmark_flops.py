"""The FLOP and byte counts behind `mfu` and the attention roofline, against
numbers worked by hand."""

import json
from pathlib import Path

import pytest

from benchmarks.manifest import Manifest

REPO = Path(__file__).resolve().parents[2]
MANIFEST = Manifest()
RESNET = MANIFEST.module("flops", "resnet")
DECODER = MANIFEST.module("flops", "decoder")
ATTENTION = MANIFEST.module("flops", "attention")


def test_resnet50_forward_is_the_published_4_09_gmacs():
    config = MANIFEST.config("resnet50")
    # torchvision's resnet50 (v1.5) at 224 px: 4.09 G multiply-adds.
    assert RESNET.forward_macs(config) == 4_089_184_256
    first_conv_input_grad = 2 * 7 * 7 * 3 * 64 * 112 * 112
    assert RESNET.per_example(config, {}) == 6 * 4_089_184_256 - first_conv_input_grad


def test_resnet_count_by_hand_on_one_block():
    config = {"image_size": 32, "num_filters": 8, "stage_sizes": [1], "num_classes": 10}
    stem = 7 * 7 * 3 * 8 * 16 * 16  # 32 -> 16, then the pool -> 8
    block = 8 * 8 * 8 * 8 + 9 * 8 * 8 * 8 * 8 + 8 * 32 * 8 * 8 + 8 * 32 * 8 * 8
    assert RESNET.forward_macs(config) == stem + block + 32 * 10


@pytest.mark.parametrize("layers, want", [(32, 7_113_539_584), (5, 1_224_736_768), (1, 352_321_536)])
def test_mistral_matmul_weights(layers, want):
    config = dict(MANIFEST.config("mistral-7b-v0.3"), num_hidden_layers=layers)
    # One layer: q and o 2 x 4096 x 4096, k and v 2 x 4096 x 1024, three
    # feed-forward matrices of 4096 x 14336 = 218,103,808; the head 134,217,728.
    assert 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336 == 218_103_808
    assert DECODER.matmul_weights(config) == want == layers * 218_103_808 + 134_217_728


def test_the_published_model_has_its_7_25_billion_parameters():
    config = dict(MANIFEST.config("mistral-7b-v0.3"), num_hidden_layers=32)
    embedding = 32768 * 4096
    norms = 65 * 4096
    assert DECODER.matmul_weights(config) + embedding + norms == 7_248_023_552


def test_decoder_flops_per_sequence_by_hand():
    config = MANIFEST.config("mistral-7b-v0.3")
    traffic = MANIFEST.json("traffic", "train-s4096")
    layers = config["num_hidden_layers"]
    weights = 6 * DECODER.matmul_weights(config) * 4096
    attention = 6 * 4096 * 4096 * 32 * 128 * layers
    assert DECODER.per_example(config, traffic) == weights + attention
    # No embedding lookup in the count: a fifth of the program's own 6N at
    # this depth would come from a table no matmul touches.
    assert attention / weights < 0.1


def test_flash_forward_flops_and_bytes_by_hand():
    # 2 sequences, 4096 tokens, 32 query heads on 8 key/value heads of 128.
    assert ATTENTION.flops(2, 4096, 32, 128) == 2 * 2 * 128 * 2 * 32 * 4096 * 4096 / 2
    q_and_o = 2 * 2 * 4096 * 32 * 128 * 2
    k_and_v = 2 * 2 * 4096 * 8 * 128 * 2
    lse = 2 * 32 * 4096 * 4
    assert ATTENTION.bytes_moved(2, 4096, 32, 8, 128) == q_and_o + k_and_v + lse
    peaks = json.loads((REPO / "benchmarks/peaks.json").read_text())["TPU v5 lite"]
    compute = ATTENTION.flops(2, 4096, 32, 128) / peaks["bf16_flops_per_s"]
    memory = ATTENTION.bytes_moved(2, 4096, 32, 8, 128) / peaks["hbm_bytes_per_s"]
    assert compute > 5 * memory  # the kernel is compute-bound at this length
