"""Plain reference of a Mamba-2 language model (arXiv:2405.21060, the
``Mamba2`` mixer of ``mamba_ssm`` with ``ngroups`` B/C groups), in float32
``jax.numpy``.

Per layer, with ``h = RMSNorm(x)``:
``[z, xBC, dt] = h · W_in``; ``xBC`` goes through a causal depthwise
convolution of width ``conv_kernel`` (plus bias) and silu and splits into
``x`` (``d_inner``), ``B`` and ``C`` (``ssm_groups x ssm_state`` each);
``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; the state of each
head follows ``s_t = exp(dt_t A) s_{t-1} + dt_t B_t x_t^T`` and
``y_t = C_t s_t + D x_t`` (head ``i`` reads group ``i // (heads /
groups)``); then ``x += W_out · RMSNorm(y * silu(z))``.  A final RMSNorm and
the output head tied to the embedding give the logits.  The recurrence is
evaluated in its chunked (state-space-dual) form, exactly.

Weights follow the published initialisation (``mamba_ssm``'s
``_init_weights`` and ``Mamba2.__init__``): the embedding N(0, 0.02); the
input projection and the convolution at PyTorch's default uniform
initialisation; the output projection the same, divided by
``sqrt(n_layers)`` (``rescale_prenorm_residual``); ``dt`` log-uniform in
[1e-3, 1e-1] through the inverse softplus; ``A`` uniform in [1, 16];
``D = 1``.  Norm scales, one at initialisation, are drawn near one.

Departure from the published model, matched to the program under test and
listed in the configuration's ``reduced``: RMSNorm epsilon is
``rms_norm_eps`` (1e-6) where ``mamba_ssm`` uses 1e-5.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from reference.ops import HIGHEST, mm, rms_norm

SSD_CHUNK = 256


def _dims(s):
    di, G, N, H = s["d_inner"], s["ssm_groups"], s["ssm_state"], \
        s["ssm_heads"]
    return di, G, N, H, di + 2 * G * N


def shapes(s):
    D, L, V, K = s["d_model"], s["n_layers"], s["vocab_size"], \
        s["conv_kernel"]
    di, G, N, H, conv_ch = _dims(s)
    return {
        "embed": (V, D), "final_norm": (D,), "norm": (L, D),
        "in_proj": (L, D, 2 * di + 2 * G * N + H),
        "conv_w": (L, K, conv_ch), "conv_b": (L, conv_ch),
        "dt_bias": (L, H), "A_log": (L, H), "D": (L, H),
        "gate_norm": (L, di), "out_proj": (L, di, D),
    }


def layer_params(s) -> int:
    """Weights of one layer's matmuls: the input and output projections."""
    D, di, H = s["d_model"], s["d_inner"], s["ssm_heads"]
    GN = s["ssm_groups"] * s["ssm_state"]
    return D * (2 * di + 2 * GN + H) + di * D


def position_flops(s) -> int:
    """FLOPs of one position through every layer: 2 per weight, the
    depthwise convolution, and the recurrence (state update and
    read-out)."""
    H, P, N = s["ssm_heads"], s["ssm_headdim"], s["ssm_state"]
    conv = 2 * s["conv_kernel"] * (s["d_inner"] + 2 * s["ssm_groups"] * N)
    return s["n_layers"] * (2 * layer_params(s) + conv + 4 * H * N * P)


F32_LEAVES = ("final_norm", "norm", "dt_bias", "A_log", "D", "gate_norm")


def init_weights(s, key):
    dt = jnp.dtype(s["dtype"])
    shp = shapes(s)
    D, L, K = s["d_model"], s["n_layers"], s["conv_kernel"]
    di = s["d_inner"]
    k = {name: jax.random.fold_in(key, i)
         for i, name in enumerate(sorted(shp))}

    def unif(name, bound):
        return jax.random.uniform(k[name], shp[name], jnp.float32,
                                  -bound, bound)

    lo, hi = math.log(1e-3), math.log(1e-1)
    dtv = jnp.exp(jax.random.uniform(k["dt_bias"], shp["dt_bias"],
                                     jnp.float32, lo, hi))
    dtv = jnp.maximum(dtv, 1e-4)
    out = {
        "embed": 0.02 * jax.random.normal(k["embed"], shp["embed"]),
        "final_norm": 1.0 + 0.1 * jax.random.normal(k["final_norm"],
                                                    shp["final_norm"]),
        "norm": 1.0 + 0.1 * jax.random.normal(k["norm"], shp["norm"]),
        "in_proj": unif("in_proj", 1.0 / math.sqrt(D)),
        "conv_w": unif("conv_w", 1.0 / math.sqrt(K)),
        "conv_b": unif("conv_b", 1.0 / math.sqrt(K)),
        "dt_bias": dtv + jnp.log(-jnp.expm1(-dtv)),      # inverse softplus
        "A_log": jnp.log(jax.random.uniform(k["A_log"], shp["A_log"],
                                            jnp.float32, 1.0, 16.0)),
        "D": jnp.ones(shp["D"], jnp.float32),
        "gate_norm": 1.0 + 0.1 * jax.random.normal(k["gate_norm"],
                                                   shp["gate_norm"]),
        "out_proj": unif("out_proj", 1.0 / math.sqrt(di)) / math.sqrt(L),
    }
    return {n: (a if n in F32_LEAVES else a.astype(dt))
            for n, a in out.items()}


def _segsum(a):
    """a (..., T) -> (..., T, T): sum of a[j+1..i] for i >= j, else -inf."""
    T = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    seg = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((T, T), bool))
    return jnp.where(mask, seg, -jnp.inf)


def ssd(x, dt, A, B, C):
    """Chunked exact scan.  x (S, H, P), dt (S, H), A (H,), B/C (S, H, N)
    with S a multiple of ``SSD_CHUNK``; returns y (S, H, P) without D."""
    S, H, P = x.shape
    Lc = SSD_CHUNK
    nc = S // Lc
    X = (x * dt[..., None]).reshape(nc, Lc, H, P)
    a = (dt * A[None]).reshape(nc, Lc, H).transpose(0, 2, 1)   # (nc, H, Lc)
    B = B.reshape(nc, Lc, H, -1)
    C = C.reshape(nc, Lc, H, -1)
    acs = jnp.cumsum(a, axis=-1)                                 # (nc, H, Lc)
    Lmat = jnp.exp(_segsum(a))                                   # (nc,H,Lc,Lc)
    ein = lambda eq, *ops: jnp.einsum(eq, *ops, precision=HIGHEST)
    scores = ein("clhn,cshn->chls", C, B)
    y_diag = ein("chls,cshp->clhp", scores * Lmat, X)
    decay = jnp.exp(acs[..., -1:] - acs)                         # (nc, H, Lc)
    states = ein("clhn,chl,clhp->chnp", B, decay, X)

    def carry(s, inp):
        st, tot = inp
        return jnp.exp(tot)[:, None, None] * s + st, s

    _, s_in = jax.lax.scan(carry, jnp.zeros(states.shape[1:], jnp.float32),
                           (states, acs[..., -1]))
    y_off = ein("clhn,chnp,chl->clhp", C, s_in, jnp.exp(acs))
    return (y_diag + y_off).reshape(S, H, P)


def hidden(s, w, tokens, mode: str = "f32"):
    """Final normed hidden states ``(S, D)`` float32 of one sequence; S must
    be a multiple of ``SSD_CHUNK`` (pad at the end: the model is causal)."""
    S = tokens.shape[0]
    di, G, N, H, conv_ch = _dims(s)
    P = s["ssm_headdim"]
    K = s["conv_kernel"]
    eps = s["rms_norm_eps"]
    x = w["embed"][tokens].astype(jnp.float32)

    def layer(x, p):
        h = rms_norm(x, p["norm"], eps)
        zxbcdt = mm(h, p["in_proj"], mode)
        z = zxbcdt[:, :di]
        xBC = zxbcdt[:, di:di + conv_ch]
        dt = zxbcdt[:, di + conv_ch:]
        cw = p["conv_w"].astype(jnp.float32)
        xp = jnp.concatenate([jnp.zeros((K - 1, conv_ch)), xBC], axis=0)
        conv = sum(xp[i:i + S] * cw[i] for i in range(K)) \
            + p["conv_b"].astype(jnp.float32)
        xBC = jax.nn.silu(conv)
        xs = xBC[:, :di].reshape(S, H, P)
        rep = H // G
        Bm = jnp.repeat(xBC[:, di:di + G * N].reshape(S, G, N), rep, axis=1)
        Cm = jnp.repeat(xBC[:, di + G * N:].reshape(S, G, N), rep, axis=1)
        dt = jax.nn.softplus(dt + p["dt_bias"])
        A = -jnp.exp(p["A_log"])
        y = ssd(xs, dt, A, Bm, Cm) + p["D"][None, :, None] * xs
        y = y.reshape(S, di) * jax.nn.silu(z)
        y = rms_norm(y, p["gate_norm"], eps)
        return x + mm(y, p["out_proj"], mode), None

    per_layer = {k: w[k] for k in shapes(s) if k not in ("embed",
                                                          "final_norm")}
    x, _ = jax.lax.scan(layer, x, per_layer)
    return rms_norm(x, w["final_norm"], eps)
