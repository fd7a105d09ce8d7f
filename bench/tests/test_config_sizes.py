"""A configuration reaches the program and the reference from its file
alone: every ``model`` key that names a ``ModelConfig`` field is served
(the tiny sibling's value in a rehearsal), every other key is the
reference's and must be listed in ``reference_only``, and the reference
reads them all, hashable, with ``head_dim``, ``layer_pattern`` and
``dtype``."""

import copy
import sys
import types

import numpy as np
import pytest

from harness import check, files, program

# a hybrid MoE model of a kind no file of the benchmark names: attention
# in one layer of four, experts in every layer, and a reference-only width
HYBRID = {
    "name": "toy-hybrid-moe",
    "reference": "toy_hybrid",
    "reference_only": ["rms_norm_eps", "shared_expert_width"],
    "model": {
        "family": "hybrid", "d_model": 512, "n_layers": 8, "n_heads": 8,
        "n_kv_heads": 2, "head_dim": 64, "vocab_size": 1000,
        "n_experts": 32, "top_k": 4, "d_ff_expert": 256, "d_inner": 1024,
        "ssm_heads": 16, "ssm_headdim": 64, "ssm_state": 64,
        "layer_pattern": [["attn", "moe"], ["mamba", "moe"],
                          ["mamba", "moe"], ["mamba", "moe"]],
        "rms_norm_eps": 1e-5, "shared_expert_width": 96,
        "dtype": "bfloat16",
    },
}
PATTERN = (("attn", "moe"), ("mamba", "moe"), ("mamba", "moe"),
           ("mamba", "moe"))


@pytest.mark.parametrize("rehearse,served", [
    (False, {"n_layers": 8, "n_experts": 32, "top_k": 4, "d_ff_expert": 256,
             "d_model": 512, "head_dim": 64, "dtype": "bfloat16"}),
    (True, {"n_layers": 4, "n_experts": 16, "top_k": 2, "d_ff_expert": 32,
            "d_model": 64, "head_dim": 8, "dtype": "float32"}),
], ids=["served", "rehearsed"])
def test_a_hybrid_moe_block_reaches_the_reference(rehearse, served,
                                                  monkeypatch):
    seen = []

    def init_weights(s, key):
        seen.append(s)
        return {"embed": np.zeros((2, 2), np.float32)}

    stub = types.ModuleType("reference.toy_hybrid")
    stub.init_weights = init_weights
    monkeypatch.setitem(sys.modules, "reference.toy_hybrid", stub)
    cfg = program.model_config(HYBRID, rehearse)
    s = program.sizes(cfg, HYBRID, rehearse)
    check.reference_weights(HYBRID["reference"], s, 4100000053)
    assert seen == [s]
    for k, v in served.items():
        assert s[k] == v, k
    assert s["layer_pattern"] == cfg.layer_pattern == PATTERN
    assert s["shared_expert_width"] == 96 and s["rms_norm_eps"] == 1e-5
    assert set(s) == set(HYBRID["model"]) | {"head_dim"}
    hash(tuple(sorted(s.items())))


def test_an_unlisted_key_raises():
    conf = copy.deepcopy(HYBRID)
    conf["model"]["shared_expert_widht"] = 96
    with pytest.raises(KeyError, match="shared_expert_widht"):
        program.model_config(conf, False)
    with pytest.raises(KeyError, match="shared_expert_widht"):
        program.sizes(program.model_config(HYBRID, False), conf)


@pytest.mark.parametrize("name", ["qwen2-0.5b", "mamba2-780m"])
@pytest.mark.parametrize("rehearse", [False, True],
                         ids=["served", "rehearsed"])
def test_todays_configurations(name, rehearse):
    conf = files.config(name)
    cfg = program.model_config(conf, rehearse)
    s = program.sizes(cfg, conf, rehearse)
    assert set(s) == set(conf["model"]) | {"head_dim", "layer_pattern"}
    assert s["layer_pattern"] == cfg.pattern()
    want = {k: conf["model"][k] for k in conf["reference_only"]}
    if rehearse:
        want.update((k, v) for k, v in conf.get("rehearsal", {}).items()
                    if k in want)
    assert {k: s[k] for k in want} == want
    hash(tuple(sorted(s.items())))


def test_an_untied_head_is_the_one_scored(monkeypatch):
    rng = np.random.default_rng(0)
    w = {"embed": rng.standard_normal((16, 4)).astype(np.float32),
         "lm_head": rng.standard_normal((16, 4)).astype(np.float32)}
    stub = types.ModuleType("reference.toy_untied")
    stub.hidden = lambda s, w, t, mode: w["embed"][t]
    stub.head = lambda s, w: w["lm_head"]
    monkeypatch.setitem(sys.modules, "reference.toy_untied", stub)
    prompt, tokens = [3, 1, 4], [1, 5, 9]
    got = check.reference_logits("toy_untied", {"d": 4}, w, prompt, tokens)
    h = w["embed"][np.asarray([4, 1, 5])]
    np.testing.assert_allclose(got, h @ w["lm_head"].T, rtol=1e-5, atol=1e-5)
    assert not np.allclose(got, h @ w["embed"].T)
