"""The generic decoder LM driver: embed → scan(periods) → norm → logits.

A model is ``layer_pattern × n_periods``; parameters are stacked over the
period axis and the period body (the pattern, unrolled) runs under
``jax.lax.scan`` — 72-layer Jamba compiles as 9 scan steps of an 8-block
body, keeping HLO size and compile time flat across the zoo.  The period
body is rematerialized (``jax.checkpoint``) for training.

Decode: ``init_decode_state`` builds per-position state stacks (KV caches /
MLA latent caches / SSM states / RWKV states) and ``decode_step`` advances
one token, scanning over periods with the state slices as scan-carried
xs/ys.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..parallel.sharding import with_constraint
from . import blocks as B
from .common import BlockSpec, ModelConfig, rms_norm, softcap

__all__ = ["init_params", "forward", "loss_fn", "init_decode_state",
           "decode_step"]


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _block_init(cfg: ModelConfig, spec: BlockSpec, key) -> dict:
    k1, k2 = jax.random.split(key)
    if spec.kind == "attn":
        p = {"core": B.attn_init(cfg, k1)}
    elif spec.kind == "mla":
        p = {"core": B.mla_init(cfg, k1)}
    elif spec.kind == "mamba":
        p = {"core": B.mamba_init(cfg, k1)}
    elif spec.kind == "rwkv":
        return {"core": B.rwkv_init(cfg, k1)}  # rwkv includes channel-mix
    else:
        raise ValueError(spec.kind)
    p["ffn"] = B.moe_init(cfg, k2) if spec.moe else B.mlp_init(cfg, k2)
    return p


def init_params(cfg: ModelConfig, key) -> dict:
    keys = jax.random.split(key, 4)
    d = cfg.d_model
    emb_scale = 1.0  # embeddings init at 0.02-ish via fan-in of vocab
    params = {
        "embed": {"table": B.make_dense(keys[0], (cfg.vocab_size, d),
                                        cfg.jdtype, scale=0.02)},
        "final_norm": {"scale": jnp.zeros((d,), cfg.jdtype)},
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {"w": B.make_dense(keys[1], (d, cfg.vocab_size),
                                               cfg.jdtype)}

    def one_period(key):
        ks = jax.random.split(key, len(cfg.layer_pattern))
        return {f"pos{i}": _block_init(cfg, spec, ks[i])
                for i, spec in enumerate(cfg.layer_pattern)}

    pkeys = jax.random.split(keys[2], cfg.n_periods)
    stacked = jax.vmap(one_period)(pkeys)
    params["layers"] = stacked
    return params


# ---------------------------------------------------------------------------
# forward (full sequence)
# ---------------------------------------------------------------------------

def _block_fwd(cfg: ModelConfig, spec: BlockSpec, p, x, positions, mesh):
    if spec.kind == "attn":
        x = B.attn_fwd(cfg, spec, p["core"], x, positions, mesh)
    elif spec.kind == "mla":
        x = B.mla_fwd(cfg, spec, p["core"], x, positions, mesh)
    elif spec.kind == "mamba":
        x = B.mamba_fwd(cfg, p["core"], x, mesh)
    elif spec.kind == "rwkv":
        return B.rwkv_fwd(cfg, p["core"], x, mesh), 0.0
    aux = 0.0
    if spec.moe:
        x = B.moe_fwd(cfg, p["ffn"], x, mesh)
        aux = B.moe_fwd.aux
    else:
        x = B.mlp_fwd(cfg, p["ffn"], x, mesh)
    return x, aux


def forward(params, tokens, cfg: ModelConfig, mesh=None, prefix_embeds=None):
    """tokens (B, T) int32; prefix_embeds optional (B, P, d) modality stub.
    Returns logits (B, T_total, V) and the MoE aux loss."""
    x = params["embed"]["table"][tokens].astype(cfg.jdtype)
    if cfg.scale_embeddings:
        x = x * jnp.asarray(np.sqrt(cfg.d_model), cfg.jdtype)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    Bsz, T, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (Bsz, T))
    x = with_constraint(x, mesh, ("batch", "none", "none"))

    def period_body(carry, period_params):
        h, aux = carry
        for i, spec in enumerate(cfg.layer_pattern):
            h, a = _block_fwd(cfg, spec, period_params[f"pos{i}"], h,
                              positions, mesh)
            aux = aux + a
        # sequence-parallel residual stream: the scan carry (the only tensor
        # the backward pass must keep per period) is sharded over the model
        # axis too — Megatron-SP style — so 28–72-period residual stacks
        # stay at (B·T·d)/(dp·tp) per device instead of (B·T·d)/dp.
        h = with_constraint(h, mesh, ("batch", "seq_model", "none"))
        return (h, aux), None

    body = period_body
    if cfg.remat:
        # full rematerialization inside each period: backward recomputes the
        # period from its carry; nothing else is saved (the d_ff-wide dot
        # outputs would otherwise dominate device memory at 24k d_ff).
        body = jax.checkpoint(
            period_body, policy=jax.checkpoint_policies.nothing_saveable)
    (x, aux), _ = jax.lax.scan(body, (x, jnp.float32(0.0)), params["layers"])

    x = rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    head = (params["embed"]["table"].T if cfg.tie_embeddings
            else params["lm_head"]["w"])
    logits = x @ head.astype(x.dtype)
    # keep the (B, T, V) tensor vocab-sharded — unsharded logits dominate
    # activation memory at 256k vocab
    logits = with_constraint(logits, mesh, ("batch", "none", "vocab"))
    logits = softcap(logits.astype(jnp.float32), cfg.final_softcap)
    return logits, aux


def loss_fn(params, batch, cfg: ModelConfig, mesh=None):
    """Next-token CE.  batch: {tokens (B,T), labels (B,T)[, prefix_embeds]}.

    Computed as ``lse(logits) − logits[label]`` so the (B, T, V) log-prob
    tensor is never materialized — at 256k vocab that tensor alone is
    ~4 GB/device even vocab-sharded."""
    logits, aux = forward(params, batch["tokens"], cfg, mesh,
                          batch.get("prefix_embeds"))
    labels = batch["labels"]
    P = logits.shape[1] - labels.shape[1]
    if P:
        logits = logits[:, P:]
    lse = jax.nn.logsumexp(logits, axis=-1)                     # (B, T)
    ll = jnp.take_along_axis(logits, jnp.maximum(labels, 0)[..., None],
                             axis=-1)[..., 0]
    mask = (labels >= 0)
    loss = ((lse - ll) * mask).sum() / jnp.maximum(mask.sum(), 1)
    return loss + aux, {"ce": loss, "aux": aux}


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def _pos_state_init(cfg: ModelConfig, spec: BlockSpec, batch: int,
                    max_len: int):
    if spec.kind == "attn":
        cache_len = min(max_len, spec.window) if spec.window else max_len
        return B.attn_init_state(cfg, batch, max_len)
    if spec.kind == "mla":
        return B.mla_init_state(cfg, batch, max_len)
    if spec.kind == "mamba":
        return B.mamba_init_state(cfg, batch)
    return B.rwkv_init_state(cfg, batch)


def init_decode_state(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    def stack(spec):
        one = _pos_state_init(cfg, spec, batch, max_len)
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a, (cfg.n_periods,) + a.shape), one)
    return {f"pos{i}": stack(spec)
            for i, spec in enumerate(cfg.layer_pattern)}


def _block_step(cfg, spec, p, x, st, pos, mesh):
    if spec.kind == "attn":
        x, st = B.attn_step(cfg, spec, p["core"], x, st, pos, mesh)
    elif spec.kind == "mla":
        x, st = B.mla_step(cfg, spec, p["core"], x, st, pos, mesh)
    elif spec.kind == "mamba":
        x, st = B.mamba_step(cfg, p["core"], x, st, mesh)
    else:
        x, st = B.rwkv_step(cfg, p["core"], x, st, mesh)
        return x, st
    x = B.moe_fwd(cfg, p["ffn"], x, mesh) if spec.moe \
        else B.mlp_fwd(cfg, p["ffn"], x, mesh)
    return x, st


def decode_step(params, state, token, pos, cfg: ModelConfig, mesh=None):
    """token (B,) int32, pos scalar int32; returns (logits (B, V), state)."""
    x = params["embed"]["table"][token][:, None].astype(cfg.jdtype)
    if cfg.scale_embeddings:
        x = x * jnp.asarray(np.sqrt(cfg.d_model), cfg.jdtype)
    x = with_constraint(x, mesh, ("batch", "none", "none"))

    def period_body(x, xs):
        period_params, st_in = xs
        st_out = {}
        for i, spec in enumerate(cfg.layer_pattern):
            x, st = _block_step(cfg, spec, period_params[f"pos{i}"], x,
                                st_in[f"pos{i}"], pos, mesh)
            st_out[f"pos{i}"] = st
        return x, st_out

    x, new_state = jax.lax.scan(period_body, x, (params["layers"], state))
    x = rms_norm(x, params["final_norm"]["scale"], cfg.norm_eps)
    head = (params["embed"]["table"].T if cfg.tie_embeddings
            else params["lm_head"]["w"])
    logits = softcap((x[:, 0] @ head.astype(x.dtype)).astype(jnp.float32),
                     cfg.final_softcap)
    return logits, new_state
