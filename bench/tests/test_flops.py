"""Operation and byte counts against hand-computed numbers at both
configurations' widths."""
import pytest

import flops
from model import dims, load_config


@pytest.fixture(params=["qwen3-8b-L12", "qwen3-4b"])
def dm(request):
    return dims(load_config(request.param))


def test_matmul_flops_per_token_by_hand():
    d8 = dims(load_config("qwen3-8b-L12"))
    # 8B: q 4096x4096, k/v 4096x1024 each, o 4096x4096, mlp 3x4096x12288
    per_layer = 2 * (4096 * 4096 + 2 * 4096 * 1024 + 4096 * 4096
                     + 3 * 4096 * 12288)
    assert flops.matmul_flops_per_token(d8) == 12 * per_layer \
        + 2 * 4096 * 151936
    d4 = dims(load_config("qwen3-4b"))
    # 4B: q 2560x4096, k/v 2560x1024, o 4096x2560, mlp 3x2560x9728
    per_layer = 2 * (2560 * 4096 + 2 * 2560 * 1024 + 4096 * 2560
                     + 3 * 2560 * 9728)
    assert flops.matmul_flops_per_token(d4) == 36 * per_layer \
        + 2 * 2560 * 151936


def test_parameter_counts(dm):
    # Qwen3-4B: 4.02 B with the tied embedding; the 8B cut: 12 layers
    total = dm.layers * dm.layer_params() + dm.vocab * dm.d * (
        1 if dm.tied else 2) + dm.d
    want = 4.02e9 if dm.tied else 12 * 193e6 + 2 * 622e6
    assert abs(total - want) / want < 0.01


def test_attention_counts_by_hand(dm):
    # 61 queries over 20,000 keys: QK^T and PV, 2 flops a MAC each, in
    # every layer (all of a dense model's layers attend)
    assert flops.attn_flops(dm, 61, 20000) == \
        dm.layers * 4 * 61 * 20000 * 32 * 128
    # K and V of 20,000 keys over 8 kv heads of 128, bf16, + q and out
    assert flops.attn_bytes(dm, 61, 20000) == dm.layers * (
        2 * 20000 * 8 * 128 * 2 + 2 * 61 * 32 * 128 * 2)


def test_kv_bytes_per_token():
    assert dims(load_config("qwen3-8b-L12")).kv_bytes_per_token() == \
        2 * 12 * 1024 * 2
    assert dims(load_config("qwen3-4b")).kv_bytes_per_token() == \
        2 * 36 * 1024 * 2


def test_least_time_picks_the_binding_roof():
    pk = flops.peaks("TPU v5 lite")
    t, bound = flops.least_time(197e12, 1.0, pk)
    assert bound == "compute" and abs(t - 1.0) < 1e-12
    t, bound = flops.least_time(1.0, 819e9, pk)
    assert bound == "memory" and abs(t - 1.0) < 1e-12
    with pytest.raises(KeyError):
        flops.peaks("TPU v9 imaginary")
