"""Plain reference of a Qwen2 decoder (arXiv:2407.10671; the
``Qwen2ForCausalLM`` layer equations), in float32 ``jax.numpy``.

Per layer: ``x += W_o · attn(RoPE(W_q h + b_q), RoPE(W_k h + b_k),
W_v h + b_v)`` with ``h = RMSNorm(x)``, grouped-query attention (query head
``i`` reads key/value head ``i // (n_heads / n_kv_heads)``), causal; then
``x += W_down (silu(W_gate h) * W_up h)`` with ``h = RMSNorm(x)``.  RoPE
rotates the two halves of each head (theta ``rope_theta``).  A final RMSNorm
and the output head tied to the embedding give the logits.

Weights are made here from a key, with the published initialisation
(``initializer_range``, 0.02, for every matrix and the embedding).  Biases and
norm scales, zero and one at initialisation, are drawn near those values
instead, so that a program that drops them reads differently.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from reference.ops import HIGHEST, mm, rms_norm


def shapes(s):
    D, L, F, V = s["d_model"], s["n_layers"], s["d_ff"], s["vocab_size"]
    hd = s["head_dim"]
    nq, nk = s["n_heads"] * hd, s["n_kv_heads"] * hd
    return {
        "embed": (V, D), "final_norm": (D,),
        "norm1": (L, D), "wq": (L, D, nq), "bq": (L, nq), "wk": (L, D, nk),
        "bk": (L, nk), "wv": (L, D, nk), "bv": (L, nk), "wo": (L, nq, D),
        "norm2": (L, D), "w_gate": (L, D, F), "w_up": (L, D, F),
        "w_down": (L, F, D),
    }


NORMS = ("final_norm", "norm1", "norm2")


def layer_params(s) -> int:
    """Weights of one layer's matmuls: Q, K, V, O and the SwiGLU MLP."""
    D, F, hd = s["d_model"], s["d_ff"], s["head_dim"]
    nq, nk = s["n_heads"] * hd, s["n_kv_heads"] * hd
    return D * nq + 2 * D * nk + nq * D + 3 * D * F


def position_flops(s) -> int:
    """Matmul FLOPs of one position through every layer (2 per weight);
    attention over the context is counted apart."""
    return s["n_layers"] * 2 * layer_params(s)


def init_weights(s, key):
    """Seeded weights: norm scales float32, everything else in ``s["dtype"]``."""
    dt = jnp.dtype(s["dtype"])
    out = {}
    for i, (name, shp) in enumerate(sorted(shapes(s).items())):
        k = jax.random.fold_in(key, i)
        if name in NORMS:
            out[name] = 1.0 + 0.1 * jax.random.normal(k, shp, jnp.float32)
        else:
            out[name] = (s["initializer_range"]
                         * jax.random.normal(k, shp, jnp.float32)).astype(dt)
    return out


def _rope(x, pos, theta):
    """x (S, H, hd): rotate-half RoPE at integer positions ``pos`` (S,)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs
    c, s_ = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s_, x2 * c + x1 * s_], axis=-1)


def hidden(s, w, tokens, mode: str = "f32"):
    """Final normed hidden states ``(S, D)`` float32 of one sequence."""
    S = tokens.shape[0]
    hd, nh, nkv = s["head_dim"], s["n_heads"], s["n_kv_heads"]
    eps = s["rms_norm_eps"]
    pos = jnp.arange(S)
    causal = pos[:, None] >= pos[None, :]
    x = w["embed"][tokens].astype(jnp.float32)

    def layer(x, p):
        h = rms_norm(x, p["norm1"], eps)
        q = (mm(h, p["wq"], mode) + p["bq"].astype(jnp.float32))
        k = (mm(h, p["wk"], mode) + p["bk"].astype(jnp.float32))
        v = (mm(h, p["wv"], mode) + p["bv"].astype(jnp.float32))
        q = _rope(q.reshape(S, nh, hd), pos, s["rope_theta"])
        k = _rope(k.reshape(S, nkv, hd), pos, s["rope_theta"])
        v = v.reshape(S, nkv, hd)
        rep = nh // nkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
        sc = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) / jnp.sqrt(
            jnp.float32(hd))
        sc = jnp.where(causal[None], sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("hqk,khd->qhd", pr, v, precision=HIGHEST)
        x = x + mm(o.reshape(S, nh * hd), p["wo"], mode)
        h = rms_norm(x, p["norm2"], eps)
        g = mm(h, p["w_gate"], mode)
        u = mm(h, p["w_up"], mode)
        x = x + mm(jax.nn.silu(g) * u, p["w_down"], mode)
        return x, None

    per_layer = {k: w[k] for k in shapes(s) if k not in ("embed",
                                                          "final_norm")}
    x, _ = jax.lax.scan(layer, x, per_layer)
    return rms_norm(x, w["final_norm"], eps)
