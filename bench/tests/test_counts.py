"""The peak table and each configuration's operation and byte counts,
against hand counts at small sizes."""
import pytest

import harness
import peaks
import tiny

GRANITE = harness.load_module(
    f"{harness.BENCH}/configs/granite-3-2b.py", "granite_counts")
RESNET = harness.load_module(
    f"{harness.BENCH}/configs/resnet18-ddp.py", "resnet_counts")

SMALL = {"num_hidden_layers": 2, "hidden_size": 8, "num_attention_heads": 2,
         "num_key_value_heads": 1, "intermediate_size": 16, "vocab_size": 10,
         "torch_dtype": "bfloat16", "compute_dtype": "bfloat16",
         "cache_dtype": "bfloat16"}


def test_peaks_of_v5e_and_unknown_kind():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9
    assert p["ici_bits_per_s"] == 1600e9
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")


def test_layer_matmul_params_by_hand():
    # wq 8x8, wk 8x4, wv 8x4, wo 8x8, mlp 3 * 8 * 16
    assert GRANITE.layer_matmul_params(SMALL) == 64 + 32 + 32 + 64 + 384


def test_attention_counts_by_hand():
    # 2 layers, batch 3, 2 heads of 4, seq 5: 15 causal pairs per head,
    # 4 * head_dim FLOPs per pair
    assert GRANITE.attention_flops(SMALL, 3, 5) == 2 * 3 * 2 * 15 * 16
    # q and out at 2 heads, k and v at 1 head, 4 wide, 2 bytes
    assert GRANITE.attention_bytes(SMALL, 3, 5) == 2 * 3 * 5 * 24 * 2


def test_prefill_and_decode_flops_by_hand():
    mm = GRANITE.layer_matmul_params(SMALL)
    attn = GRANITE.attention_flops(SMALL, 3, 5)
    assert GRANITE.prefill_flops(SMALL, 3, 5) == (
        2 * 3 * 5 * 2 * mm + attn + 2 * 3 * 8 * 10)
    # token at position 5 attends to 6 keys
    assert GRANITE.decode_flops(SMALL, 3, 5) == (
        2 * 3 * (2 * mm + 80) + 2 * 3 * 2 * 4 * 4 * 6)


def test_decode_bytes_count_the_cache_only_up_to_the_position():
    entry = 2 * 3 * 2 * 1 * 4 * 2          # layers, batch, k+v, kv, dh, 2 B
    weights = (2 * (GRANITE.layer_matmul_params(SMALL) + 16) + 80 + 8
               + 3 * 8) * 2
    assert GRANITE.decode_bytes(SMALL, 3, 0) == weights + entry
    assert GRANITE.decode_bytes(SMALL, 3, 7) == weights + 8 * entry
    step = GRANITE.decode_bytes(SMALL, 3, 8) - GRANITE.decode_bytes(
        SMALL, 3, 7)
    assert step == entry


def test_resnet_flops_by_hand():
    c = {"stages": [1, 1], "widths": [4, 8], "num_classes": 3,
         "image_size": 4, "in_channels": 2}
    # stem 4x4x9x2x4; stage 0 block: two 3x3 4->4 at 4x4; stage 1 block
    # (stride 2, 2x2): 3x3 4->8, 3x3 8->8, 1x1 projection 4->8; fc 8x3
    macs = (16 * 9 * 2 * 4 + 2 * 16 * 9 * 4 * 4
            + 4 * 9 * 4 * 8 + 4 * 9 * 8 * 8 + 4 * 4 * 8 + 8 * 3)
    assert RESNET.forward_flops_per_sample(c) == 2 * macs
    assert RESNET.train_flops_per_sample(c) == 6 * macs


def test_resnet18_allreduce_payload():
    c = tiny.load_cell("resnet18-ddp.4chip").config
    # 11,269,640 float32 gradients and the loss
    assert RESNET.allreduce_payload_bytes(c) == 45_078_564
