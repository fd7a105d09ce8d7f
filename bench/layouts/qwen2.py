"""The plain Qwen2 weights of ``reference/qwen2.py`` in the program's stored
parameter layout on a 1x1 PE grid.

On one PE every blocked matrix is stored as ``(layers, 1, K, N)`` and the
embedding as ``(1, V, D)``; the program stores the output head apart, so it
gets the transposed embedding (the published model ties them).
"""

from __future__ import annotations


def to_program(s, w):
    blk = lambda a: a[:, None]
    return {
        "embed": w["embed"][None],
        "lm_head": w["embed"].T[None],
        "final_norm": {"scale": w["final_norm"]},
        "layers": [{
            "norm1": {"scale": w["norm1"]},
            "mixer": {"wq": blk(w["wq"]), "wk": blk(w["wk"]),
                      "wv": blk(w["wv"]), "wo": blk(w["wo"]),
                      "bq": w["bq"], "bk": w["bk"], "bv": w["bv"]},
            "norm2": {"scale": w["norm2"]},
            "ffn": {"w_gate": blk(w["w_gate"]), "w_up": blk(w["w_up"]),
                    "w_down": blk(w["w_down"])},
        }],
    }

