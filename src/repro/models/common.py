"""Shared model machinery: config schema, norms, RoPE, initializers.

One config class covers every architecture of the zoo; a model is a
``layer_pattern`` (the repeating period of block specs — Jamba's 1:7
Mamba/attention interleave, Gemma-2's local/global alternation, plain
``[attn]`` for dense models) times ``n_periods``, executed under
``jax.lax.scan`` with layer-stacked parameters so the compiled HLO stays
small at 72-layer scale.
"""

from __future__ import annotations

from dataclasses import dataclass, field, replace

import numpy as np

# jax is imported lazily inside the numerics helpers: the config schema
# (ModelConfig/BlockSpec) is consumed by the pure-NumPy DSE stack — and by
# its fork-based worker pools — which must not drag in the JAX runtime.

__all__ = ["BlockSpec", "ModelConfig", "rms_norm", "layer_norm", "rope",
           "make_dense", "softcap"]


@dataclass(frozen=True)
class BlockSpec:
    """One position in the repeating layer pattern."""

    kind: str = "attn"          # "attn" | "mla" | "mamba" | "rwkv"
    window: int | None = None   # sliding-window size for local attention
    moe: bool = False           # routed-FFN instead of dense FFN


@dataclass(frozen=True)
class ModelConfig:
    name: str = "model"
    vocab_size: int = 32000
    d_model: int = 1024
    layer_pattern: tuple[BlockSpec, ...] = (BlockSpec(),)
    n_periods: int = 4

    # attention
    n_heads: int = 8
    n_kv_heads: int = 8
    head_dim: int | None = None
    rope_theta: float = 10000.0
    attn_softcap: float | None = None
    final_softcap: float | None = None
    post_block_norm: bool = False   # Gemma-2 sandwich norms

    # multi-head latent attention (kind="mla", DeepSeek-V2/V3): queries pass
    # through a q_lora_rank latent, keys and values through a kv_lora_rank
    # latent that is all the cache holds, beside one RoPE key of
    # qk_rope_head_dim shared by the heads
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0

    # FFN
    d_ff: int = 4096
    activation: str = "silu"        # "silu" (SwiGLU) | "gelu" (GeGLU)
    glu: bool = True

    # MoE
    n_experts: int = 0
    top_k: int = 2
    n_shared_experts: int = 0
    d_ff_expert: int | None = None
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.01
    moe_impl: str = "gather"        # "gather" (GSPMD) | "ragged" (shard_map)
    # DeepSeek-V3 routing: sigmoid scores, experts chosen within the best
    # topk_groups of n_expert_groups groups, weights scaled by routed_scale
    router_score: str = "softmax"   # "softmax" | "sigmoid"
    n_expert_groups: int = 1
    topk_groups: int = 1
    routed_scale: float = 1.0

    # Mamba
    d_state: int = 16
    d_conv: int = 4
    mamba_expand: int = 2
    dt_rank: int | None = None

    # RWKV
    rwkv_head_dim: int = 64
    rwkv_decay_rank: int = 64

    # long-sequence execution strategy (beyond-paper §Perf optimizations):
    # chunked flash-style attention + chunked recurrences kick in above the
    # threshold; 0 disables (the naive paper-faithful baseline paths)
    chunk_threshold: int = 2048
    attn_kv_chunk: int = 1024
    scan_chunk: int = 256

    # embeddings / misc
    tie_embeddings: bool = False
    scale_embeddings: bool = False  # Gemma multiplies by sqrt(d_model)
    norm_eps: float = 1e-6
    dtype: str = "bfloat16"
    remat: bool = True

    # modality stubs
    prefix_len: int = 0             # VLM patch / audio frame prefix length
    is_encoder_decoder: bool = False
    n_enc_layers: int = 0
    enc_seq_len: int = 0

    # ---------------------------------------------------------------
    @property
    def n_layers(self) -> int:
        return len(self.layer_pattern) * self.n_periods

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.d_model

    @property
    def dtr(self) -> int:
        return self.dt_rank or max(1, self.d_model // 16)

    @property
    def jdtype(self):
        import jax.numpy as jnp
        return jnp.dtype(self.dtype)

    @property
    def d_ff_e(self) -> int:
        return self.d_ff_expert or self.d_ff

    def n_params(self) -> int:
        """Approximate parameter count (used for 6·N·D roofline terms)."""
        d, hd = self.d_model, self.hd
        n = self.vocab_size * d * (1 if self.tie_embeddings else 2)
        for spec in self.layer_pattern:
            if spec.kind == "attn":
                n_p = d * hd * (self.n_heads + 2 * self.n_kv_heads) \
                    + self.n_heads * hd * d
            elif spec.kind == "mla":
                H, dr = self.n_heads, self.qk_rope_head_dim
                n_p = d * self.q_lora_rank \
                    + self.q_lora_rank * H * (self.qk_nope_head_dim + dr) \
                    + d * (self.kv_lora_rank + dr) \
                    + self.kv_lora_rank * H * (self.qk_nope_head_dim
                                               + self.v_head_dim) \
                    + H * self.v_head_dim * d
            elif spec.kind == "mamba":
                di = self.d_inner
                n_p = d * 2 * di + di * (self.dtr + 2 * self.d_state) \
                    + self.dtr * di + di * self.d_state + di * d \
                    + self.d_conv * di
            else:  # rwkv: rkvwg 4d² + out d² + cr d² + lora + channel mix
                n_p = 6 * d * d + d * self.rwkv_decay_rank * 2 \
                    + 2 * d * self.d_ff
            if spec.kind != "rwkv":
                if spec.moe:
                    ff = self.d_ff_e
                    n_p += (self.n_experts + self.n_shared_experts) * 3 * d * ff \
                        + d * self.n_experts
                else:
                    n_p += (3 if self.glu else 2) * d * self.d_ff
            n += n_p * self.n_periods
        return n

    def n_active_params(self) -> int:
        """Active params per token (MoE top-k counting)."""
        if not any(s.moe for s in self.layer_pattern):
            return self.n_params()
        d = self.d_model
        n = self.n_params()
        for spec in self.layer_pattern:
            if spec.moe:
                ff = self.d_ff_e
                inactive = (self.n_experts - self.top_k) * 3 * d * ff
                n -= inactive * self.n_periods
        return n


# ---------------------------------------------------------------------------
# numerics
# ---------------------------------------------------------------------------

def rms_norm(x, scale, eps=1e-6):
    import jax
    import jax.numpy as jnp
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return ((xf * jax.lax.rsqrt(var + eps)) * (1.0 + scale.astype(jnp.float32))
            ).astype(x.dtype)


def layer_norm(x, scale, bias, eps=1e-6):
    import jax
    import jax.numpy as jnp
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps) * scale + bias).astype(x.dtype)


def softcap(x, cap: float | None):
    if cap is None:
        return x
    import jax.numpy as jnp
    return cap * jnp.tanh(x / cap)


def rope(x, positions, theta: float = 10000.0):
    """x (..., T, H, D) with D even; positions (..., T)."""
    import jax.numpy as jnp
    D = x.shape[-1]
    half = D // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., :, None, None].astype(jnp.float32) * freqs  # (..., T, 1, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def make_dense(key, shape, dtype, scale=None):
    import jax
    import jax.numpy as jnp
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    scale = scale if scale is not None else fan_in ** -0.5
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)
