"""FLOP and byte counts against figures worked by hand."""
import json
import os

import pytest

from bench import arch, counts

CONFIGS = os.path.join(os.path.dirname(__file__), "..", "configs")


def model(name):
    with open(os.path.join(CONFIGS, f"{name}.json")) as f:
        return json.load(f)["model"]


dense = arch.load("dense")


def test_olmo_1b_by_hand():
    m = model("olmo-1b")
    # attention 4 x 2048 x (16 x 128) = 16,777,216; SwiGLU 3 x 2048 x 8192
    # = 50,331,648
    assert dense.layer_matmul_params(m) == 67_108_864
    # (16 x 67,108,864 + tied head 2048 x 50304 = 103,022,592) x 2 bytes;
    # a non-parametric LayerNorm has no weights
    assert dense.weight_bytes(m) == 2_353_528_832
    # decode, contexts 100 and 2048: 2 x 1,176,764,416 x 2 sequences
    # + 4 x 16 x 128 x (100 + 2048) x 16 layers = 281,542,656
    flops, byts = dense.decode_step(m, [100, 2048])
    assert flops == 4_988_600_320
    # weights + 2148 positions x 131,072 B (K and V, 16 layers)
    # + 2 new rows x 131,072 + 2 x 50304 fp32 logits
    assert byts == 2_353_528_832 + 281_542_656 + 262_144 + 402_432
    # prefill of 512: 2 x 16 x 67,108,864 x 512 + causal attention over
    # 512 x 513 / 2 = 131,328 keys (x 8192 x 16) + the last token's head
    flops, byts = dense.prefill(m, 512)
    assert flops == 1_099_511_627_776 + 17_213_423_616 + 206_045_184
    assert byts == 2_353_528_832 + 512 * 131_072 + 50_304 * 4


def test_starcoder2_3b_by_hand_with_the_window():
    m = model("starcoder2-3b")
    # attention 2 x 3072 x 3072 + 2 x 3072 x 256 = 20,447,232;
    # GELU MLP 2 x 3072 x 12288 = 75,497,472
    assert dense.layer_matmul_params(m) == 95_944_704
    # (30 x 95,944,704 + 3072 x 49152) x 2 B + 61 LayerNorms x 2 x 3072 x 4 B
    assert dense.weight_bytes(m) == 6_058_672_128 + 1_499_136
    # decode, contexts 100 and 6000: the windowed layers read at most 4096
    flops, byts = dense.decode_step(m, [100, 6000])
    assert flops == 2 * 2 * 3_029_336_064 + 12_288 * (100 + 4096) * 30
    # K and V of one position over 30 layers: 2 x 2 x 128 x 2 B x 30
    assert byts == (6_060_171_264 + (100 + 4096) * 30_720 + 2 * 30_720
                    + 2 * 49_152 * 4)
    # prefill of 5000: each query sees at most 4096 keys:
    # 4096 x 4097 / 2 + 904 x 4096 = 12,093,440; the ring keeps 4096 rows
    flops, byts = dense.prefill(m, 5000)
    assert flops == (5_756_682_240 * 5000 + 12_288 * 12_093_440 * 30
                     + 2 * 150_994_944)
    assert byts == 6_060_171_264 + 4096 * 30_720 + 49_152 * 4


def test_least_time_is_the_larger_bound():
    peak = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert counts.least_time(1000.0, 50.0, peak) == 10.0
    assert counts.least_time(100.0, 50.0, peak) == 5.0


def test_decode_share_only_rises_when_less_is_read():
    # empty slots and positions past a sequence's length are not counted,
    # so the count for 2 live sequences is the same at any max_len
    m = model("olmo-1b")
    assert dense.decode_step(m, [10, 20]) == dense.decode_step(m, [20, 10])
    assert dense.decode_step(m, [10])[1] < dense.decode_step(m, [10, 20])[1]


@pytest.mark.parametrize("name", ["olmo-1b", "starcoder2-3b"])
def test_counts_grow_with_length(name):
    m = model(name)
    assert dense.prefill(m, 1024)[0] < dense.prefill(m, 2048)[0]
