"""Model configuration shared by every architecture family."""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax.numpy as jnp

from repro.partition import pad_to_multiple

# (mixer, ffn) per layer within a repeating group; scan runs over groups.
LayerPattern = Tuple[Tuple[str, str], ...]


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                    # dense | moe | hybrid | ssm | encdec | vlm
    d_model: int
    n_layers: int
    n_heads: int = 0
    n_kv_heads: int = 0
    d_ff: int = 0
    vocab_size: int = 0
    head_dim: Optional[int] = None
    norm: str = "rmsnorm"          # rmsnorm | layernorm
    qk_norm: bool = False
    qkv_bias: bool = False
    mlp_bias: bool = False
    act: str = "swiglu"            # swiglu | gelu
    rope_theta: float = 1e6
    rope: bool = True              # False: no position embedding (NoPE)
    attn_scale: Optional[float] = None   # None: head_dim ** -0.5
    rms_norm_eps: float = 1e-6
    # muP multipliers: the embedding is multiplied by the first, every
    # residual branch by the second before it is added, and the logits are
    # divided by the third
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    causal: bool = True
    attn_block_kv: int = 1024
    # MoE
    n_experts: int = 0
    top_k: int = 0
    d_ff_expert: int = 0
    capacity_factor: float = 1.25
    router_renorm: bool = True
    moe_aux_coef: float = 1e-2
    moe_z_coef: float = 1e-3
    moe_wire_dtype: str = "native"   # native | int8  (dispatch/combine a2a)
    # One chip's share of an expert-parallel layer (serving): the router
    # scores all ``n_experts`` and picks ``top_k`` by its float32 logits,
    # gated by a softmax over those k; this PE computes the experts
    # [expert_first, expert_first + experts_held) for every token routed to
    # them, dropless, and what the absent experts would add is left to the
    # chips that hold them.  0 = the grid-wide all_to_all layer
    # (``n_experts // n_pes`` experts per PE, capacity-bounded).
    experts_held: int = 0
    expert_first: int = 0
    d_ff_shared: int = 0           # shared SwiGLU expert width; 0 = none
    # SSM (Mamba2 / SSD)
    d_inner: int = 0
    ssm_heads: int = 0
    ssm_headdim: int = 64
    ssm_state: int = 128
    ssm_groups: int = 1
    conv_kernel: int = 4
    ssd_chunk: int = 128
    # SSD scan kernel backend: "jnp" | "pallas" | "pallas-interpret"
    # (callers may override per-call; serving threads the engine's
    # kernel_backend through instead)
    ssd_backend: str = "jnp"
    # layer pattern; empty -> homogeneous ("attn", ffn_kind) x n_layers
    layer_pattern: LayerPattern = ()
    # encoder-decoder (whisper)
    enc_layers: int = 0
    enc_seq: int = 1500
    # VLM (pixtral): patches prepended to the text sequence
    vis_patches: int = 0
    # numerics
    param_dtype: Any = jnp.bfloat16
    compute_dtype: Any = jnp.bfloat16
    # bookkeeping
    tie_embeddings: bool = False   # recorded; storage is always untied (2D layouts)
    sub_quadratic: bool = False    # True for ssm/hybrid: long_500k runnable

    # ---- derived (grid-dependent) ----------------------------------------
    def hd(self) -> int:
        return self.head_dim or (self.d_model // max(self.n_heads, 1))

    def heads_padded(self, r: int) -> int:
        return pad_to_multiple(self.n_heads, r)

    def kv_stored(self, r: int) -> Tuple[int, int]:
        """(stored kv heads incl. column replication, replica count)."""
        if self.n_kv_heads >= r:
            assert self.n_kv_heads % r == 0, (self.n_kv_heads, r)
            return self.n_kv_heads, 1
        assert r % self.n_kv_heads == 0, (self.n_kv_heads, r)
        rep = r // self.n_kv_heads
        return self.n_kv_heads * rep, rep

    def pattern(self) -> LayerPattern:
        if self.layer_pattern:
            return self.layer_pattern
        ffn = "moe" if self.family == "moe" else "mlp"
        return (("attn", ffn),)

    def n_groups(self) -> int:
        plen = len(self.pattern())
        assert self.n_layers % plen == 0, (self.n_layers, plen)
        return self.n_layers // plen


@dataclasses.dataclass(frozen=True)
class AttnStatic:
    """Static attention geometry handed to attention_block (grid-resolved)."""
    n_heads_padded: int
    n_kv_stored: int
    head_dim: int
    rope_theta: float
    qk_norm: bool
    qkv_bias: bool
    causal: bool
    attn_block_kv: int
    rope: bool = True
    scale: Optional[float] = None


def attn_static(cfg: ModelConfig, r: int, causal: Optional[bool] = None
                ) -> AttnStatic:
    return AttnStatic(
        n_heads_padded=cfg.heads_padded(r),
        n_kv_stored=cfg.kv_stored(r)[0],
        head_dim=cfg.hd(),
        rope_theta=cfg.rope_theta,
        qk_norm=cfg.qk_norm,
        qkv_bias=cfg.qkv_bias,
        causal=cfg.causal if causal is None else causal,
        attn_block_kv=cfg.attn_block_kv,
        rope=cfg.rope,
        scale=cfg.attn_scale,
    )
