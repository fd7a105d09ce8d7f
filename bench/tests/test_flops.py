"""FLOP and byte counts against hand counts at small shapes."""

import pytest

from harness import flops

DENSE = {"family": "dense", "d_model": 4, "n_layers": 2, "n_heads": 2,
         "n_kv_heads": 1, "head_dim": 2, "d_ff": 3, "vocab_size": 10}
SSM = {"family": "ssm", "d_model": 4, "n_layers": 3, "d_inner": 8,
       "ssm_heads": 2, "ssm_headdim": 4, "ssm_state": 2, "ssm_groups": 1,
       "conv_kernel": 4, "vocab_size": 10}


def test_dense_position():
    # q 4x4, k 4x2, v 4x2, o 4x4, gate/up 4x3 each, down 3x4
    per_layer = 16 + 8 + 8 + 16 + 12 + 12 + 12
    assert flops.dense_layer_params(DENSE) == per_layer
    assert flops.body_flops_per_position(DENSE) == 2 * 2 * per_layer
    assert flops.head_flops(DENSE) == 2 * 4 * 10


def test_attention_over_real_context():
    # positions 5, 6, 7 attend to 6, 7, 8 keys; QK and PV, 2 heads of 2
    keys = 6 + 7 + 8
    assert flops.attention_flops(DENSE, 5, 3) == 2 * 4 * 2 * 2 * keys
    assert flops.attention_flops(SSM, 5, 3) == 0


def test_ssm_position():
    # in_proj 4 x (2*8 + 2*1*2 + 2), out_proj 8 x 4
    assert flops.ssm_layer_params(SSM) == 4 * 22 + 32
    conv = 2 * 4 * (8 + 2 * 2)
    rec = 4 * 2 * 2 * 4
    assert flops.body_flops_per_position(SSM) == \
        3 * (2 * (4 * 22 + 32) + conv + rec)


def test_launch_counts_head_only_where_a_slot_samples():
    body = flops.body_flops_per_position(DENSE)
    slots = [(0, 4, True), (9, 1, False)]
    assert flops.launch_model_flops(DENSE, slots) == \
        4 * body + flops.attention_flops(DENSE, 0, 4) + flops.head_flops(
            DENSE) + body + flops.attention_flops(DENSE, 9, 1)


def test_paged_attention_needs_real_lengths():
    f, b = flops.paged_attention_need(DENSE, [(10, 2, True)])
    assert f == 4 * 2 * 2 * (11 + 12)
    # K and V of 12 positions (1 kv head of 2, bf16), q and o of 2 positions
    assert b == 12 * 2 * 1 * 2 * 2 + 2 * 2 * 2 * 2 * 2


def test_ssd_chunk_and_apply():
    (cf, cb), (af, ab) = flops.ssd_need(SSM, [(0, 3, True)])
    tri = 6
    assert cf == 2 * (2 * tri * 2 + 2 * tri * 4 + 2 * 3 * 2 * 4)
    assert af == 2 * 2 * 3 * 2 * 4
    assert cb > 0 and ab > 0


def test_peak_table():
    assert flops.peak("TPU v5 lite")["flops"] == 197e12
    with pytest.raises(KeyError):
        flops.peak("some other chip")
    assert flops.least_time(197e12, 0, flops.peak("TPU v5 lite")) == 1.0
