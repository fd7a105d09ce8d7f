"""Decoder-LM assembly: param specs, embedding, grouped layer scan, loss.

Everything here executes inside the step's shard_map ("the OpenCL kernel"),
on SHMEM-blocked arrays.  The layer stack is scanned over repeating groups
(params stacked on a leading group dim) so HLO size is O(pattern), not
O(n_layers) — essential for 61..94-layer configs at compile time.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import params as pm
from repro.models.attention import attention_block, cross_attention_block
from repro.models.config import ModelConfig, attn_static
from repro.models.layers import (ParallelContext, col_slice, dense,
                                 fused_dense, gelu, layer_norm, rms_norm,
                                 row_slice_tokens, swiglu)
from repro.core.cannon import skew_activation, unskew_activation
from repro.models.moe import moe_block
from repro.models.ssm import mamba_block


# ---------------------------------------------------------------------------
# Parameter specs.
# ---------------------------------------------------------------------------

def _attn_specs(cfg: ModelConfig, q: int, r: int, groups: int,
                sk=True) -> Dict:
    hd = cfg.hd()
    hp = cfg.heads_padded(r)
    kvs, kvrep = cfg.kv_stored(r)
    dt = cfg.param_dtype
    D = cfg.d_model
    sk_in = True if sk == "opt" else sk       # arot inputs: standard preskew
    sk_out = "crot" if sk == "opt" else sk    # crot outputs: stationary-A
    s = dict(
        wq=pm.blocked2d(D, hp * hd, q, r, dtype=dt, skew=sk_in, groups=groups),
        wk=pm.blocked2d(D, kvs * hd, q, r, dtype=dt, skew=sk_in, groups=groups,
                        col_replicas=kvrep),
        wv=pm.blocked2d(D, kvs * hd, q, r, dtype=dt, skew=sk_in, groups=groups,
                        col_replicas=kvrep),
        wo=pm.blocked2d(hp * hd, D, q, r, dtype=dt, skew=sk_out,
                        groups=groups),
    )
    if cfg.qkv_bias:
        s["bq"] = pm.replicated((hp * hd,), dtype=dt, groups=groups)
        s["bk"] = pm.replicated((kvs * hd,), dtype=dt, groups=groups)
        s["bv"] = pm.replicated((kvs * hd,), dtype=dt, groups=groups)
    if cfg.qk_norm:
        s["q_norm"] = pm.replicated((hd,), dtype=jnp.float32, init="ones",
                                    groups=groups)
        s["k_norm"] = pm.replicated((hd,), dtype=jnp.float32, init="ones",
                                    groups=groups)
    return s


def _mlp_specs(cfg: ModelConfig, q: int, r: int, groups: int,
               sk=True, width: Optional[int] = None) -> Dict:
    dt = cfg.param_dtype
    D, F = cfg.d_model, width or cfg.d_ff
    sk_in = True if sk == "opt" else sk
    sk_out = "crot" if sk == "opt" else sk
    s = dict(
        w_up=pm.blocked2d(D, F, q, r, dtype=dt, skew=sk_in, groups=groups),
        w_down=pm.blocked2d(F, D, q, r, dtype=dt, skew=sk_out, groups=groups),
    )
    if cfg.act == "swiglu":
        s["w_gate"] = pm.blocked2d(D, F, q, r, dtype=dt, skew=sk_in,
                                   groups=groups)
    if cfg.mlp_bias:
        s["b_up"] = pm.replicated((F,), dtype=dt, groups=groups)
        s["b_down"] = pm.replicated((D,), dtype=dt, groups=groups)
    return s


def _moe_specs(cfg: ModelConfig, q: int, r: int, groups: int,
               sk=True) -> Dict:
    """Router and experts; a share layer (``experts_held``) keeps its held
    experts whole on every PE, the grid-wide layer spreads ``n_experts``
    over the flat PE space.  A shared expert is a SwiGLU MLP."""
    dt = cfg.param_dtype
    D, F, E = cfg.d_model, cfg.d_ff_expert, cfg.n_experts
    n_pes = q * r
    s = dict(router=pm.replicated((D, E), dtype=jnp.float32, init="normal",
                                  fan_in=D, groups=groups))
    if cfg.experts_held:
        Eh = cfg.experts_held
        assert 0 <= cfg.expert_first <= E - Eh, (cfg.expert_first, Eh, E)
        s.update(
            w1=pm.replicated((Eh, D, 2 * F), dtype=dt, init="normal",
                             fan_in=D, groups=groups),
            w2=pm.replicated((Eh, F, D), dtype=dt, init="normal", fan_in=F,
                             groups=groups))
    else:
        assert E % n_pes == 0, (E, n_pes)
        e_loc = E // n_pes

        def flat(shape):
            spec = pm.ParamSpec((n_pes,) + shape, dt, pm.P(pm.MODEL),
                                fan_in=D, meta=(("layout", "expert_flat"),))
            return pm._stack(spec, groups)
        s.update(w1=flat((e_loc, D, 2 * F)), w2=flat((e_loc, F, D)))
    if cfg.d_ff_shared:
        s["shared"] = _mlp_specs(cfg, q, r, groups, sk,
                                 width=cfg.d_ff_shared)
    return s


def _mamba_specs(cfg: ModelConfig, q: int, r: int, groups: int,
                 sk=True) -> Dict:
    sk_in = True if sk == "opt" else sk
    sk_out = "crot" if sk == "opt" else sk
    sk = sk_in
    dt = cfg.param_dtype
    D = cfg.d_model
    di, H, P = cfg.d_inner, cfg.ssm_heads, cfg.ssm_headdim
    G, N, K = cfg.ssm_groups, cfg.ssm_state, cfg.conv_kernel
    conv_ch = di + 2 * G * N
    return dict(
        wz=pm.blocked2d(D, di, q, r, dtype=dt, skew=sk, groups=groups),
        wx=pm.blocked2d(D, di, q, r, dtype=dt, skew=sk, groups=groups),
        wb=pm.blocked2d(D, G * N, q, r, dtype=dt, skew=sk, groups=groups),
        wc=pm.blocked2d(D, G * N, q, r, dtype=dt, skew=sk, groups=groups),
        wdt=pm.blocked2d(D, H, q, r, dtype=dt, skew=sk, groups=groups),
        conv_w=pm.replicated((K, conv_ch), dtype=dt, init="normal",
                             fan_in=K, groups=groups),
        conv_b=pm.replicated((conv_ch,), dtype=dt, groups=groups),
        A=pm.replicated((H,), dtype=jnp.float32, init="ssm_a", groups=groups),
        dt_bias=pm.replicated((H,), dtype=jnp.float32, groups=groups),
        D=pm.replicated((H,), dtype=jnp.float32, init="ones", groups=groups),
        ssm_norm=pm.replicated((di,), dtype=jnp.float32, init="ones",
                               groups=groups),
        wo=pm.blocked2d(di, D, q, r, dtype=dt, skew=sk_out, groups=groups),
    )


def _norm_specs(cfg: ModelConfig, groups: Optional[int]) -> Dict:
    D = cfg.d_model
    s = {"scale": pm.replicated((D,), dtype=jnp.float32, init="ones",
                                groups=groups)}
    if cfg.norm == "layernorm":
        s["bias"] = pm.replicated((D,), dtype=jnp.float32, groups=groups)
    return s


def _layer_specs(cfg: ModelConfig, q: int, r: int, groups: int,
                 cross: bool = False, sk=True) -> list:
    """One spec dict per pattern position, each stacked over groups."""
    out = []
    for mixer, ffn in cfg.pattern():
        entry: Dict[str, Any] = {"norm1": _norm_specs(cfg, groups)}
        if mixer == "attn":
            entry["mixer"] = _attn_specs(cfg, q, r, groups, sk)
        elif mixer == "mamba":
            entry["mixer"] = _mamba_specs(cfg, q, r, groups, sk)
        else:
            raise ValueError(mixer)
        if cross:
            entry["cross"] = _attn_specs(cfg, q, r, groups, sk)
            entry["norm_cross"] = _norm_specs(cfg, groups)
        if ffn == "mlp":
            entry["ffn"] = _mlp_specs(cfg, q, r, groups, sk)
            entry["norm2"] = _norm_specs(cfg, groups)
        elif ffn == "moe":
            entry["ffn"] = _moe_specs(cfg, q, r, groups, sk)
            entry["norm2"] = _norm_specs(cfg, groups)
        elif ffn != "none":
            raise ValueError(ffn)
        out.append(entry)
    return out


def param_specs(cfg: ModelConfig, q: int, r: int,
                preskew=True) -> Dict:
    """Full parameter-spec pytree for one architecture on a q x r grid.

    ``preskew``: True (Cannon training default), False (natural blocks:
    allgather/summa baselines, decode deployments), or "opt" (the
    alternating arot/crot storage for tp_strategy="cannon_opt").  An
    init/export-time choice — shapes are identical in every mode."""
    V, D = cfg.vocab_size, cfg.d_model
    groups = cfg.n_groups()
    lm_sk = True if preskew == "opt" else preskew
    specs: Dict[str, Any] = {
        "embed": pm.vocab2d(pm.pad_to_multiple(V, q * r), D, q, r,
                            dtype=cfg.param_dtype),
        "lm_head": pm.blocked2d(D, pm.pad_to_multiple(V, q * r), q, r,
                                dtype=cfg.param_dtype, skew=lm_sk),
        "final_norm": _norm_specs(cfg, None),
        "layers": _layer_specs(cfg, q, r, groups, sk=preskew),
    }
    if cfg.enc_layers:   # whisper encoder stack + cross-attn decoder
        enc_cfg = dataclasses.replace(cfg, layer_pattern=(("attn", "mlp"),),
                                      n_layers=cfg.enc_layers, causal=False)
        specs["enc_layers"] = _layer_specs(enc_cfg, q, r, cfg.enc_layers,
                                           sk=preskew)
        specs["enc_pos"] = pm.replicated((cfg.enc_seq, D), dtype=cfg.param_dtype,
                                         init="normal", fan_in=D)
        specs["enc_final_norm"] = _norm_specs(cfg, None)
        specs["layers"] = _layer_specs(cfg, q, r, groups, cross=True,
                                       sk=preskew)
    if cfg.vis_patches:  # pixtral: projected patch embeddings enter directly
        specs["vis_proj"] = pm.blocked2d(
            D, D, q, r, dtype=cfg.param_dtype,
            skew=True if preskew == "opt" else preskew)
    return specs


# ---------------------------------------------------------------------------
# Embedding + LM head / loss.
# ---------------------------------------------------------------------------

def embed_tokens(pctx: ParallelContext, embed_blk: jax.Array,
                 tokens: jax.Array, compute_dtype) -> jax.Array:
    """tokens (B, S) replicated -> x (B, S/q, D/r) blocked.

    Each PE looks up all S positions against its (V_i, D_j) table block, then
    a row reduce-scatter simultaneously sums over vocab blocks and scatters
    the sequence — one collective for the whole lookup.
    """
    vb = embed_blk[0]                                   # (V_loc, D_loc)
    V_loc = vb.shape[0]
    i, _ = pctx.grid.my_coords()
    loc = tokens - i * V_loc
    hit = (loc >= 0) & (loc < V_loc)
    part = jnp.take(vb, jnp.clip(loc, 0, V_loc - 1), axis=0)
    part = jnp.where(hit[..., None], part, 0).astype(compute_dtype)
    return pctx.grid.reduce_scatter_rows(part, axis=1)  # (B, S/q, D_loc)


def lm_loss(pctx: ParallelContext, lm_head_blk: jax.Array, x: jax.Array,
            labels: jax.Array, vocab_padded: int, chunk: int = 1024
            ) -> Tuple[jax.Array, jax.Array]:
    """Chunked cross-entropy with col-sharded vocab; logits never fully live.

    x (B, S_loc, D_loc); labels (B, S) replicated (shifted by caller; -100 =
    masked).  Returns (sum_loss, n_valid) — caller averages globally.
    """
    B, S_loc, _ = x.shape
    labels_loc = row_slice_tokens(pctx, labels, axis=1)  # (B, S_loc)
    V_loc = vocab_padded // pctx.r
    _, j = pctx.grid.my_coords()
    nchunk = max(1, S_loc // min(chunk, S_loc))
    cs = S_loc // nchunk

    def chunk_loss(carry, idx):
        xs = lax.dynamic_slice_in_dim(x, idx * cs, cs, axis=1)
        ls = lax.dynamic_slice_in_dim(labels_loc, idx * cs, cs, axis=1)
        logits = dense(pctx, xs, lm_head_blk, out_dtype=jnp.float32)
        # max-shift is gradient-neutral (cancels in lse - tgt); pmax has no
        # JVP rule, so the grid provides a zero-tangent variant.
        m = pctx.grid.pmax_cols_sg(jnp.max(logits, axis=-1))
        lse = jnp.log(pctx.grid.psum_cols(
            jnp.sum(jnp.exp(logits - m[..., None]), axis=-1))) + m
        loc = ls - j * V_loc
        hit = (loc >= 0) & (loc < V_loc)
        tgt = jnp.take_along_axis(
            logits, jnp.clip(loc, 0, V_loc - 1)[..., None], axis=-1)[..., 0]
        tgt = pctx.grid.psum_cols(jnp.where(hit, tgt, 0.0))
        valid = (ls >= 0) & (ls < vocab_padded)
        tok_loss = jnp.where(valid, lse - tgt, 0.0)
        s, n = carry
        return (s + jnp.sum(tok_loss), n + jnp.sum(valid)), None

    (s, n), _ = lax.scan(jax.checkpoint(chunk_loss),
                         (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32)),
                         jnp.arange(nchunk))
    return s, n


# ---------------------------------------------------------------------------
# Layer application.
# ---------------------------------------------------------------------------

def _norm(pctx, cfg, p, x):
    if cfg.norm == "layernorm":
        return layer_norm(pctx, x, p["scale"], p["bias"])
    return rms_norm(pctx, x, p["scale"], eps=cfg.rms_norm_eps)


def mlp_apply(pctx: ParallelContext, cfg, p: Dict, x: jax.Array) -> jax.Array:
    if cfg.act == "swiglu":
        g, u = fused_dense(pctx, x, [p["w_gate"], p["w_up"]])
        h = swiglu(g, u)
    else:
        (u,) = fused_dense(pctx, x, [p["w_up"]],
                           biases=[p.get("b_up")] if cfg.mlp_bias else None)
        h = gelu(u)
    # down-projection is the C-rotating GEMM under cannon_opt (kind ignored
    # by every other strategy)
    return dense(pctx, h, p["w_down"],
                 bias=p.get("b_down") if cfg.mlp_bias else None, kind="crot")


def apply_layer(pctx: ParallelContext, cfg: ModelConfig, mixer: str, ffn: str,
                p: Dict, x: jax.Array, pos_offset=0,
                cross_kv=None) -> Tuple[jax.Array, Any, Dict]:
    """One (mixer, ffn) layer; returns (x, cache_entry, metrics)."""
    metrics: Dict[str, jax.Array] = {}
    h = _norm(pctx, cfg, p["norm1"], x)
    if mixer == "attn":
        h, cache = attention_block(pctx, p["mixer"], h,
                                   attn_static(cfg, pctx.r), pos_offset)
    elif mixer == "mamba":
        # mamba_block consumes the residual layout directly: in_proj is an
        # arot GEMM (skewed in, natural internals), out_proj a crot GEMM
        # (natural in, skewed out) — no adapter ppermutes needed.
        h, cache = mamba_block(pctx, p["mixer"], h, cfg)
    else:
        raise ValueError(mixer)
    x = x + h
    if cross_kv is not None:
        h = _norm(pctx, cfg, p["norm_cross"], x)
        h = cross_attention_block(pctx, p["cross"], h, cross_kv,
                                  attn_static(cfg, pctx.r, causal=False))
        x = x + h
    if ffn == "mlp":
        h = _norm(pctx, cfg, p["norm2"], x)
        x = x + mlp_apply(pctx, cfg, p["ffn"], h)
    elif ffn == "moe":
        h = _norm(pctx, cfg, p["norm2"], x)
        y, metrics = moe_block(pctx, p["ffn"], h, _moe_cfg(cfg))
        x = x + y
    return x, cache, metrics


def _moe_cfg(cfg: ModelConfig):
    return cfg  # moe_block reads n_experts/top_k/... straight off ModelConfig


def stack_forward(pctx: ParallelContext, cfg: ModelConfig, layers_p: list,
                  x: jax.Array, pos_offset=0, cross_kv=None,
                  collect_cache: bool = False):
    """Scan the layer-group stack.  layers_p: list (pattern position) of
    pytrees with leaves stacked over groups."""
    pattern = cfg.pattern()

    def group_body(carry, group_params):
        x, aux = carry
        caches = []
        for pos, (mixer, ffn) in enumerate(pattern):
            x, cache, metrics = apply_layer(
                pctx, cfg, mixer, ffn, group_params[pos], x, pos_offset,
                cross_kv=cross_kv if "cross" in group_params[pos] else None)
            caches.append(cache if collect_cache else None)
            if "moe_aux" in metrics:
                aux = aux + metrics["moe_aux"]
        return (x, aux), caches

    body = jax.checkpoint(group_body) if pctx.remat else group_body
    (x, aux), caches = lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                layers_p)
    return x, aux, caches


# ---------------------------------------------------------------------------
# Full model forward + loss.
# ---------------------------------------------------------------------------

# fields the serving step programs (repro.serve.decode) compute and the
# training forward does not, with the values at which they do nothing
_SERVED_ONLY = {"experts_held": 0, "d_ff_shared": 0, "rope": True,
                "attn_scale": None, "embedding_multiplier": 1.0,
                "residual_multiplier": 1.0, "logits_scaling": 1.0}


def _check_trainable(cfg: ModelConfig) -> None:
    off = sorted(k for k, v in _SERVED_ONLY.items() if getattr(cfg, k) != v)
    if off:
        raise NotImplementedError(
            f"{cfg.name}: {off} are served by the engine's step programs; "
            f"the training forward does not compute them")


def forward(pctx: ParallelContext, cfg: ModelConfig, params: Dict,
            batch: Dict, collect_cache: bool = False):
    """batch: tokens (B, S) [+ labels; + frames/patches for encdec/vlm].
    Returns (x_final (B, S_loc, D_loc), aux, caches)."""
    _check_trainable(cfg)
    cd = cfg.compute_dtype
    tokens = batch["tokens"]   # VLM: (B, P+S_text) with -1 at patch positions
    x = embed_tokens(pctx, params["embed"], tokens, cd)

    cross_kv = None
    if cfg.enc_layers:
        assert pctx.tp_strategy != "cannon_opt", \
            "cannon_opt does not cover enc-dec cross attention"
        cross_kv = _encode(pctx, cfg, params, batch["frames"].astype(cd))
    if cfg.vis_patches:
        x = x + _patch_inject(pctx, params, batch["patches"], cd, x.shape[1])
    if pctx.tp_strategy == "cannon_opt":
        # enter the permanently-skewed residual layout (one ppermute/step)
        x = skew_activation(pctx.grid, x)

    x, aux, caches = stack_forward(pctx, cfg, params["layers"], x,
                                   cross_kv=cross_kv,
                                   collect_cache=collect_cache)
    x = _norm(pctx, cfg, params["final_norm"], x)
    return x, aux, caches


def _patch_inject(pctx, params, patches, cd, s_loc):
    """Vision stub (pixtral): precomputed patch embeddings (B, P, D) occupy
    the first P global positions (the driver marks them with token id -1, so
    embed_tokens left zeros there).  Requires P <= seq block (true for all
    assigned shapes): only grid-row 0's block receives patch content."""
    B, P, D = patches.shape
    assert P <= s_loc, (P, s_loc)
    i, _ = pctx.grid.my_coords()
    padded = jnp.pad(patches, ((0, 0), (0, s_loc - P), (0, 0)))
    blocked = col_slice(pctx, padded, layout="blocked").astype(cd)
    blocked = jnp.where(i == 0, blocked, jnp.zeros_like(blocked))
    # injection happens pre-skew: natural-in, natural-out classic Cannon
    return dense(pctx, blocked, params["vis_proj"], kind="std")


def _encode(pctx, cfg, params, frames):
    """Whisper encoder on stub frame embeddings (B, S_enc, D) replicated.
    Returns the blocked encoder output; each decoder layer projects its own
    cross K/V from it (see cross_attention_block)."""
    enc_cfg = dataclasses.replace(cfg, layer_pattern=(("attn", "mlp"),),
                                  n_layers=cfg.enc_layers, causal=False)
    pos = params["enc_pos"][None, :frames.shape[1]].astype(frames.dtype)
    x = col_slice(pctx, row_slice_tokens(pctx, frames + pos, axis=1))
    x, _, _ = stack_forward(pctx, enc_cfg, params["enc_layers"], x)
    return _norm(pctx, enc_cfg, params["enc_final_norm"], x)


def loss_fn(pctx: ParallelContext, cfg: ModelConfig, params: Dict,
            batch: Dict) -> Tuple[jax.Array, Dict]:
    """Labels (B, S) replicated, already shifted; -100 masks (incl. VLM patch
    positions — the driver builds full-length labels)."""
    x, aux, _ = forward(pctx, cfg, params, batch)
    vpad = pm.pad_to_multiple(cfg.vocab_size, pctx.q * pctx.r)
    s, n = lm_loss(pctx, params["lm_head"], x, batch["labels"], vpad)
    # global mean over all tokens (rows + data axes; cols are replicated)
    s = pctx.grid.psum_rows(s)
    n = pctx.grid.psum_rows(n)
    aux = pctx.grid.psum_rows(aux) / pctx.q
    for ax in pctx.data_axes:
        s = lax.psum(s, ax)
        n = lax.psum(n, ax)
        aux = lax.pmean(aux, ax)
    loss = s / jnp.maximum(n, 1).astype(jnp.float32)
    return loss + aux, {"ce_loss": loss, "aux": aux, "n_tokens": n}
