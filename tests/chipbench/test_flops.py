"""The dense decoder's counts (``arch/dense_gqa.py``) against hand counts
for one Mistral-7B layer, and ``flops.py``'s peaks and least time."""

import pytest

import tiny
from chipbench import flops
from chipbench.cell import load_arch

arch = load_arch("dense_gqa", tiny.BENCH)
SZ = arch.Sizes(vocab=32768, d_model=4096, n_layers=1, n_heads=32,
                n_kv_heads=8, head_dim=128, d_ff=14336, rope_theta=1e6,
                rms_eps=1e-6, lora_rank=16)


def test_one_layers_weights_by_hand():
    q = 4096 * 4096
    kv = 2 * 4096 * 1024
    o = 4096 * 4096
    mlp = 3 * 4096 * 14336
    assert arch.layer_matmul_params(SZ) == q + kv + o + mlp == 218_103_808
    # LoRA r16 on Q and V: A [4096,16] + B [16,4096]; A [4096,16] + B [16,1024]
    assert arch.layer_adapter_params(SZ) == (65536 + 65536) + (65536 + 16384)
    assert arch.head_params(SZ) == 4096 * 32768
    assert arch.weight_bytes(SZ) == 2 * (218_103_808 + 134_217_728)


def test_attention_and_forward_flops_by_hand():
    # One query over 2048 keys: QK^T 2*32*128*2048, PV the same.
    assert arch.attention_flops(SZ, 1, 2048) == 2 * (2 * 32 * 128 * 2048)
    fwd = arch.forward_flops_per_token(SZ, 2048)
    by_hand = 2 * (218_103_808 + 212_992 + 134_217_728) + 2 * (
        2 * 32 * 128 * 2048)
    assert fwd == by_hand


def test_lora_step_requires_no_frozen_weight_gradient():
    per_token = arch.train_flops_per_token(SZ, 4096)
    frozen = 2 * (218_103_808 + 134_217_728)
    adapters = 2 * 212_992
    attn = 4 * 32 * 128 * 2048
    assert per_token == 2 * frozen + 3 * adapters + 3 * attn
    # A full fine-tune would need 3x the frozen products; LoRA needs 2x.
    assert per_token < 3 * frozen + 3 * attn


def test_flash_cost_and_roofline_bound():
    c = arch.flash_attention_cost(SZ, 2, 4096)
    product = 2 * 32 * 128 * 2 * 4096 * 2048
    assert c["fwd_flops"] == 2 * product and c["bwd_flops"] == 5 * product
    q_el, kv_el = 2 * 4096 * 32 * 128, 2 * 4096 * 8 * 128
    assert c["fwd_bytes"] == 2 * (2 * q_el + 2 * kv_el)
    peak = flops.peaks("TPU v5 lite")
    t, bound = flops.least_seconds(c["fwd_flops"], c["fwd_bytes"], peak)
    assert bound == "compute" and t == pytest.approx(c["fwd_flops"] / 197e12)


def test_decode_is_memory_bound_and_unknown_chips_are_refused():
    c = arch.decode_step_cost(SZ, rows=8, mean_context=512)
    assert c["bytes"] == arch.weight_bytes(SZ) + 8 * 512 * (2 * 8 * 128 * 2)
    t, bound = flops.least_seconds(c["flops"], c["bytes"],
                                   flops.peaks("TPU v5 lite"))
    assert bound == "memory"
    with pytest.raises(KeyError):
        flops.peaks("TPU v9 imaginary")
