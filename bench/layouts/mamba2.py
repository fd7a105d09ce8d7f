"""The plain Mamba-2 weights of ``reference/mamba2.py`` in the program's
stored parameter layout on a 1x1 PE grid.

The program splits the input projection into its five parts (z, x, B, C,
dt), stores ``A`` itself rather than ``log(-A)``, and stores the output
head apart (the published model ties it to the embedding).
"""

from __future__ import annotations

import jax.numpy as jnp


def _splits(s):
    di, GN, H = s["d_inner"], s["ssm_groups"] * s["ssm_state"], \
        s["ssm_heads"]
    return [("wz", di), ("wx", di), ("wb", GN), ("wc", GN), ("wdt", H)]


def to_program(s, w):
    mixer, ofs = {}, 0
    for name, n in _splits(s):
        mixer[name] = w["in_proj"][:, None, :, ofs:ofs + n]
        ofs += n
    mixer.update(conv_w=w["conv_w"], conv_b=w["conv_b"],
                 A=-jnp.exp(w["A_log"]), dt_bias=w["dt_bias"], D=w["D"],
                 ssm_norm=w["gate_norm"], wo=w["out_proj"][:, None])
    return {
        "embed": w["embed"][None],
        "lm_head": w["embed"].T[None],
        "final_norm": {"scale": w["final_norm"]},
        "layers": [{"norm1": {"scale": w["norm"]}, "mixer": mixer}],
    }

