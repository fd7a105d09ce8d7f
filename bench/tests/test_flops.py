"""FLOP and byte counts against hand counts at small shapes.  What one
position costs through the layers is each reference's ``position_flops``;
``flops`` adds attention over the real context in the attention layers
only, and the head where a slot samples."""

import sys
import types

import pytest

from harness import files, flops
from reference import mamba2, qwen2

DENSE = {"d_model": 4, "n_layers": 2, "n_heads": 2, "n_kv_heads": 1,
         "head_dim": 2, "d_ff": 3, "vocab_size": 10,
         "layer_pattern": (("attn", "mlp"),)}
SSM = {"d_model": 4, "n_layers": 3, "d_inner": 8, "ssm_heads": 2,
       "ssm_headdim": 4, "ssm_state": 2, "ssm_groups": 1, "conv_kernel": 4,
       "vocab_size": 10, "layer_pattern": (("mamba", "none"),)}
# q 4x4, k 4x2, v 4x2, o 4x4, gate/up 4x3 each, down 3x4
DENSE_LAYER = 16 + 8 + 8 + 16 + 12 + 12 + 12
# in_proj 4 x (2*8 + 2*1*2 + 2), out_proj 8 x 4; conv 2 x 4 x (8 + 2*2);
# recurrence 4 x heads x state x headdim
SSM_LAYER = 4 * 22 + 32
SSM_POSITION = 2 * SSM_LAYER + 2 * 4 * (8 + 2 * 2) + 4 * 2 * 2 * 4


@pytest.mark.parametrize("ref,s,layer,position", [
    (qwen2, DENSE, DENSE_LAYER, 2 * 2 * DENSE_LAYER),
    (mamba2, SSM, SSM_LAYER, 3 * SSM_POSITION),
], ids=["qwen2", "mamba2"])
def test_position_flops_by_hand(ref, s, layer, position):
    assert ref.layer_params(s) == layer
    assert ref.position_flops(s) == position
    assert flops.head_flops(s) == 2 * 4 * 10


@pytest.mark.parametrize("s,attn", [(DENSE, 2), (SSM, 0)],
                         ids=["qwen2", "mamba2"])
def test_attention_over_real_context(s, attn):
    # positions 5, 6, 7 attend to 6, 7, 8 keys; QK and PV, 2 heads of 2
    keys = 6 + 7 + 8
    assert flops.layers(s, "attn") == attn
    assert flops.attention_flops(s, 5, 3) == attn * 4 * 2 * 2 * keys


def test_launch_counts_head_only_where_a_slot_samples():
    body = qwen2.position_flops(DENSE)
    slots = [(0, 4, True), (9, 1, False)]
    assert flops.launch_model_flops(DENSE, slots, qwen2) == \
        4 * body + flops.attention_flops(DENSE, 0, 4) + flops.head_flops(
            DENSE) + body + flops.attention_flops(DENSE, 9, 1)


# a hybrid block of two layers, an attention layer and a Mamba-2 layer,
# each with a mixture of experts, repeated twice: 4 layers, 2 of attention
HYBRID = {**DENSE, **SSM, "n_layers": 4, "n_experts": 8, "top_k": 2,
          "d_ff_expert": 5,
          "layer_pattern": (("attn", "moe"), ("mamba", "moe"))}
# per position: top_k experts of gate/up/down 4x5 each, and the router
MOE_LAYER = 2 * (2 * 3 * 4 * 5) + 2 * 4 * 8


def _hybrid_position_flops(s):
    attn = 2 * (DENSE_LAYER - 3 * 4 * 3)        # Q, K, V, O only
    return s["n_layers"] // 2 * (attn + SSM_POSITION + 2 * MOE_LAYER)


@pytest.fixture
def hybrid(monkeypatch):
    """A reference the harness finds by name, as a new configuration's."""
    stub = types.ModuleType("reference.hybrid")
    stub.position_flops = _hybrid_position_flops
    monkeypatch.setitem(sys.modules, "reference.hybrid", stub)
    return files.module("reference", "hybrid")


def test_hybrid_pattern_counts_attention_in_its_attention_layers(hybrid):
    assert flops.layers(HYBRID, "attn") == 2
    assert flops.layers(HYBRID, "mamba") == 2
    assert flops.layers(HYBRID, "moe") == 0
    keys = 6 + 7 + 8
    assert flops.attention_flops(HYBRID, 5, 3) == 2 * 4 * 2 * 2 * keys
    slots = [(5, 3, True), (0, 2, False)]
    body = 2 * (2 * (16 + 8 + 8 + 16) + SSM_POSITION + 2 * MOE_LAYER)
    assert flops.launch_model_flops(HYBRID, slots, hybrid) == \
        3 * body + 2 * 4 * 2 * 2 * keys + 2 * 4 * 10 \
        + 2 * body + 2 * 4 * 2 * 2 * (1 + 2)


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
