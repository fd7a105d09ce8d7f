"""The plain Granite-4.0-H share weights of ``reference/granite.py`` in the
program's stored parameter layout on a 1x1 PE grid.

The program keeps one parameter tree per position of the layer pattern,
each stacked over the pattern's repeats; on one PE a blocked matrix is
``(repeats, 1, K, N)``.  It splits the Mamba-2 input projection into its
five parts (z, x, B, C, dt), stores ``A`` itself rather than ``log(-A)``,
joins each expert's gate and up projections (gate first), and stores the
output head apart: the tied embedding, transposed.  The head's division
by ``logits_scaling`` happens in the program's step.
"""

from __future__ import annotations

import jax.numpy as jnp

from reference.granite import _mixers


def _splits(s):
    di, GN, H = s["d_inner"], s["ssm_groups"] * s["ssm_state"], \
        s["ssm_heads"]
    return [("wz", di), ("wx", di), ("wb", GN), ("wc", GN), ("wdt", H)]


def _mamba(s, w, i):
    mixer, ofs = {}, 0
    for name, n in _splits(s):
        mixer[name] = w["in_proj"][i, :, ofs:ofs + n]
        ofs += n
    mixer.update(conv_w=w["conv_w"][i], conv_b=w["conv_b"][i],
                 A=-jnp.exp(w["A_log"][i]), dt_bias=w["dt_bias"][i],
                 D=w["D"][i], ssm_norm=w["gate_norm"][i],
                 wo=w["out_proj"][i])
    return mixer


def _ffn(w, li):
    return {
        "router": w["router"][li],
        "w1": jnp.concatenate([w["w_gate"][li], w["w_up"][li]], axis=-1),
        "w2": w["w_down"][li],
        "shared": {"w_gate": w["s_gate"][li], "w_up": w["s_up"][li],
                   "w_down": w["s_down"][li]},
    }


BLOCKED = ("wz", "wx", "wb", "wc", "wdt", "wo", "wq", "wk", "wv",
           "w_gate", "w_up", "w_down")


def _stack(layers, pattern_len):
    """Layer ``l`` is pattern position ``l % len`` of repeat ``l // len``;
    blocked matrices gain the one-PE block dimension."""
    def stack(name, leaves):
        a = jnp.stack(leaves)
        return a[:, None] if name in BLOCKED else a

    def merge(trees, name=None):
        if isinstance(trees[0], dict):
            return {k: merge([t[k] for t in trees], k) for k in trees[0]}
        return stack(name, trees)

    return [merge(layers[p::pattern_len]) for p in range(pattern_len)]


def to_program(s, w):
    n = {"mamba": 0, "attn": 0}
    layers = []
    for li, mixer in enumerate(_mixers(s)):
        i = n[mixer]
        n[mixer] += 1
        mix = _mamba(s, w, i) if mixer == "mamba" else {
            k: w[k][i] for k in ("wq", "wk", "wv", "wo")}
        layers.append({"norm1": {"scale": w["norm1"][li]}, "mixer": mix,
                       "norm2": {"scale": w["norm2"][li]},
                       "ffn": _ffn(w, li)})
    return {
        "embed": w["embed"][None],
        "lm_head": w["embed"].T[None],
        "final_norm": {"scale": w["final_norm"]},
        "layers": _stack(layers, len(s["layer_pattern"])),
    }
