"""Plain reference of one chip's share of a Granite-4.0-H stage
(``granitemoehybrid``: the ``GraniteMoeHybridForCausalLM`` layer equations),
in float32 ``jax.numpy``.

The stage's layers follow ``s["layer_pattern"]``: Mamba-2 or attention as
the mixer, a mixture of experts in every layer.  With ``h = RMSNorm(x)``
(epsilon ``rms_norm_eps``) each layer does

* ``x += m_r * mixer(h)``, where ``m_r`` is ``residual_multiplier``;
* ``x += m_r * (moe(h) + shared(h))`` with ``h = RMSNorm(x)``.

The Mamba-2 mixer is that of ``reference/mamba2.py`` (one group of B/C,
a causal convolution with bias, ``y * silu(z)`` through a gated RMSNorm,
no projection bias).  Attention is grouped-query (query head ``i`` reads
key/value head ``i // (n_heads / n_kv_heads)``), causal, with no position
embedding (NoPE) and the scores scaled by ``attn_scale`` (the published
``attention_multiplier``, 1/128), with no bias.  The router scores all
``n_experts`` in float32; a token's experts are the ``top_k`` largest
logits, gated by a softmax over those k.  Each expert and the shared
expert are SwiGLU MLPs: ``W_down (silu(W_gate h) * W_up h)``.

The embedding is multiplied by ``embedding_multiplier`` and the logits,
read from the tied embedding, are divided by ``logits_scaling``
(``head(s, w)``).

Departure from the published model, matched to the program under test and
stated in the configuration: this is one chip's share of an
expert-parallel stage.  Only the ``experts_held`` experts from
``expert_first`` exist here; the router still scores all ``n_experts`` and
picks its top ``k`` among them, and the assignments to experts held
elsewhere add nothing (in the deployment the chips that hold them add
theirs).  Only ``n_layers`` layers, the first stage, are served.

Weights are made here from a key (the configuration's ``assumed``):
``embedding_std`` for the embedding, fan-in-scaled normals with
``qk_gain`` (queries, keys), ``router_gain`` (router) and ``branch_gain``
(each residual branch's output projection), 1 elsewhere, and the Mamba-2
input side as ``mamba_ssm`` initialises it.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from reference.mamba2 import ssd
from reference.ops import HIGHEST, mm, rms_norm


def _mixers(s):
    pat = s["layer_pattern"]
    return [pat[i % len(pat)][0] for i in range(s["n_layers"])]


def _widths(s):
    di, G, N, H = s["d_inner"], s["ssm_groups"], s["ssm_state"], \
        s["ssm_heads"]
    return di, G, N, H, di + 2 * G * N


def shapes(s):
    D, L, V, K = s["d_model"], s["n_layers"], s["vocab_size"], \
        s["conv_kernel"]
    mix = _mixers(s)
    nm, na = mix.count("mamba"), mix.count("attn")
    di, G, N, H, conv_ch = _widths(s)
    hd = s["head_dim"]
    nq, nk = s["n_heads"] * hd, s["n_kv_heads"] * hd
    E, Eh, F, Fs = s["n_experts"], s["experts_held"], s["d_ff_expert"], \
        s["d_ff_shared"]
    return {
        "embed": (V, D), "final_norm": (D,), "norm1": (L, D),
        "norm2": (L, D),
        "in_proj": (nm, D, 2 * di + 2 * G * N + H),
        "conv_w": (nm, K, conv_ch), "conv_b": (nm, conv_ch),
        "dt_bias": (nm, H), "A_log": (nm, H), "D": (nm, H),
        "gate_norm": (nm, di), "out_proj": (nm, di, D),
        "wq": (na, D, nq), "wk": (na, D, nk), "wv": (na, D, nk),
        "wo": (na, nq, D),
        "router": (L, D, E), "w_gate": (L, Eh, D, F), "w_up": (L, Eh, D, F),
        "w_down": (L, Eh, F, D),
        "s_gate": (L, D, Fs), "s_up": (L, D, Fs), "s_down": (L, Fs, D),
    }


def expert_flops(s) -> float:
    """FLOPs of one row through one expert: gate, up and down."""
    return 2.0 * 3 * s["d_model"] * s["d_ff_expert"]


def position_flops(s) -> float:
    """FLOPs of one position through every layer, 2 per weight: the
    Mamba-2 projections, convolution and recurrence; the attention
    projections (attention over the context is counted apart); the router,
    the shared expert, and the held experts at their EXPECTED work, ``top_k
    x experts_held / n_experts`` rows a position (a uniform router), since
    the rows a position really sends depend on the weights and the token."""
    D, H, P, N = s["d_model"], s["ssm_heads"], s["ssm_headdim"], \
        s["ssm_state"]
    di, G, _, _, conv_ch = _widths(s)
    hd = s["head_dim"]
    nq, nk = s["n_heads"] * hd, s["n_kv_heads"] * hd
    mix = _mixers(s)
    mamba = 2 * (D * (2 * di + 2 * G * N + H) + di * D) \
        + 2 * s["conv_kernel"] * conv_ch + 4 * H * N * P
    attn = 2 * (D * nq + 2 * D * nk + nq * D)
    moe = 2 * D * s["n_experts"] + 2 * 3 * D * s["d_ff_shared"] \
        + s["top_k"] * s["experts_held"] / s["n_experts"] * expert_flops(s)
    return mix.count("mamba") * mamba + mix.count("attn") * attn \
        + s["n_layers"] * moe


F32_LEAVES = ("final_norm", "norm1", "norm2", "dt_bias", "A_log", "D",
              "gate_norm", "router")
NORMS = ("final_norm", "norm1", "norm2", "gate_norm")
# fan-in-scaled normal leaves: name -> the gain key (None: gain 1)
GAINS = {"wq": "qk_gain", "wk": "qk_gain", "wv": None, "wo": "branch_gain",
         "out_proj": "branch_gain", "router": "router_gain",
         "w_gate": None, "w_up": None, "w_down": "branch_gain",
         "s_gate": None, "s_up": None, "s_down": "branch_gain"}


def init_weights(s, key):
    dt = jnp.dtype(s["dtype"])
    shp = shapes(s)
    D, K = s["d_model"], s["conv_kernel"]
    k = {name: jax.random.fold_in(key, i)
         for i, name in enumerate(sorted(shp))}
    out = {"embed": s["embedding_std"]
           * jax.random.normal(k["embed"], shp["embed"])}
    for name in NORMS:
        out[name] = 1.0 + 0.1 * jax.random.normal(k[name], shp[name])
    for name, gain in GAINS.items():
        g = 1.0 if gain is None else s[gain]
        out[name] = g / math.sqrt(shp[name][-2]) \
            * jax.random.normal(k[name], shp[name])

    def unif(name, bound):
        return jax.random.uniform(k[name], shp[name], jnp.float32,
                                  -bound, bound)

    lo, hi = math.log(1e-3), math.log(1e-1)
    dtv = jnp.maximum(jnp.exp(jax.random.uniform(
        k["dt_bias"], shp["dt_bias"], jnp.float32, lo, hi)), 1e-4)
    out.update(
        in_proj=unif("in_proj", 1.0 / math.sqrt(D)),
        conv_w=unif("conv_w", 1.0 / math.sqrt(K)),
        conv_b=unif("conv_b", 1.0 / math.sqrt(K)),
        dt_bias=dtv + jnp.log(-jnp.expm1(-dtv)),          # inverse softplus
        A_log=jnp.log(jax.random.uniform(k["A_log"], shp["A_log"],
                                         jnp.float32, 1.0, 16.0)),
        D=jnp.ones(shp["D"], jnp.float32))
    return {n: (a if n in F32_LEAVES else a.astype(dt))
            for n, a in out.items()}


def head(s, w):
    """The output head: the tied embedding over ``logits_scaling``."""
    return w["embed"].astype(jnp.float32) / s["logits_scaling"]


def _mamba(s, p, h, mode):
    S = h.shape[0]
    di, G, N, H, conv_ch = _widths(s)
    P, K = s["ssm_headdim"], s["conv_kernel"]
    zxbcdt = mm(h, p["in_proj"], mode)
    z = zxbcdt[:, :di]
    xBC = zxbcdt[:, di:di + conv_ch]
    dt = zxbcdt[:, di + conv_ch:]
    cw = p["conv_w"].astype(jnp.float32)
    xp = jnp.concatenate([jnp.zeros((K - 1, conv_ch)), xBC], axis=0)
    xBC = jax.nn.silu(sum(xp[i:i + S] * cw[i] for i in range(K))
                      + p["conv_b"].astype(jnp.float32))
    xs = xBC[:, :di].reshape(S, H, P)
    rep = H // G
    Bm = jnp.repeat(xBC[:, di:di + G * N].reshape(S, G, N), rep, axis=1)
    Cm = jnp.repeat(xBC[:, di + G * N:].reshape(S, G, N), rep, axis=1)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = ssd(xs, dt, -jnp.exp(p["A_log"]), Bm, Cm) \
        + p["D"][None, :, None] * xs
    y = rms_norm(y.reshape(S, di) * jax.nn.silu(z), p["gate_norm"],
                 s["rms_norm_eps"])
    return mm(y, p["out_proj"], mode)


def _attention(s, p, h, mode):
    S = h.shape[0]
    hd, nh, nkv = s["head_dim"], s["n_heads"], s["n_kv_heads"]
    q = mm(h, p["wq"], mode).reshape(S, nh, hd)
    k = jnp.repeat(mm(h, p["wk"], mode).reshape(S, nkv, hd), nh // nkv, 1)
    v = jnp.repeat(mm(h, p["wv"], mode).reshape(S, nkv, hd), nh // nkv, 1)
    sc = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) \
        * s["attn_scale"]
    pos = jnp.arange(S)
    sc = jnp.where((pos[:, None] >= pos[None, :])[None], sc, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1), v,
                   precision=HIGHEST)
    return mm(o.reshape(S, nh * hd), p["wo"], mode)


def _swiglu(h, g, u, d, mode):
    return mm(jax.nn.silu(mm(h, g, mode)) * mm(h, u, mode), d, mode)


def moe(s, p, h, mode="f32"):
    """The held experts' part of one layer's expert mixture, and the
    shared expert: ``h`` (S, D) -> (S, D)."""
    logits = mm(h, p["router"], mode)                      # (S, E)
    top_l, top_e = jax.lax.top_k(logits, s["top_k"])
    gate = jax.nn.softmax(top_l, axis=-1)
    held = s["expert_first"] + jnp.arange(s["experts_held"])
    g = jnp.sum(jnp.where(top_e[None] == held[:, None, None], gate[None],
                          0.0), axis=-1)                   # (Eh, S)
    ye = jax.vmap(lambda wg, wu, wd: _swiglu(h, wg, wu, wd, mode))(
        p["w_gate"], p["w_up"], p["w_down"])               # (Eh, S, D)
    return jnp.einsum("es,esd->sd", g, ye, precision=HIGHEST) \
        + _swiglu(h, p["s_gate"], p["s_up"], p["s_down"], mode)


MAMBA = ("in_proj", "conv_w", "conv_b", "dt_bias", "A_log", "D",
         "gate_norm", "out_proj")
ATTN = ("wq", "wk", "wv", "wo")
MOE = ("router", "w_gate", "w_up", "w_down", "s_gate", "s_up", "s_down")


def hidden(s, w, tokens, mode: str = "f32"):
    """Final normed hidden states ``(S, D)`` float32 of one sequence; S must
    be a multiple of ``mamba2.SSD_CHUNK`` (pad at the end: the model is
    causal)."""
    eps, m_r = s["rms_norm_eps"], s["residual_multiplier"]
    x = w["embed"][tokens].astype(jnp.float32) * s["embedding_multiplier"]
    n = {"mamba": 0, "attn": 0}
    for li, mixer in enumerate(_mixers(s)):
        i = n[mixer]
        n[mixer] += 1
        h = rms_norm(x, w["norm1"][li], eps)
        if mixer == "mamba":
            x = x + m_r * _mamba(s, {k: w[k][i] for k in MAMBA}, h, mode)
        else:
            x = x + m_r * _attention(s, {k: w[k][i] for k in ATTN}, h, mode)
        h = rms_norm(x, w["norm2"][li], eps)
        x = x + m_r * moe(s, {k: w[k][li] for k in MOE}, h, mode)
    return rms_norm(x, w["final_norm"], eps)
