"""Block implementations: GQA attention, multi-head latent attention,
dense/MoE FFN, Mamba, RWKV-6.

Every block provides ``init``, ``fwd`` (full-sequence) and ``step``
(single-token decode with explicit state).  CPU forward paths share exact
semantics with the Pallas kernels through :mod:`repro.kernels.ref` /
:mod:`repro.kernels.ops`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import ops
from ..parallel.sharding import with_constraint
from .common import BlockSpec, ModelConfig, make_dense, rms_norm, rope

KB = "ref"  # kernel backend for model execution (CPU default; TPU: "pallas")


def _dense(key, d_in, d_out, dtype):
    return {"w": make_dense(key, (d_in, d_out), dtype)}


# ===========================================================================
# attention (GQA + RoPE + sliding window + softcap)
# ===========================================================================

def attn_init(cfg: ModelConfig, key) -> dict:
    ks = jax.random.split(key, 4)
    d, hd = cfg.d_model, cfg.hd
    return {
        "norm": {"scale": jnp.zeros((d,), cfg.jdtype)},
        "wq": _dense(ks[0], d, cfg.n_heads * hd, cfg.jdtype),
        "wkv": _dense(ks[1], d, 2 * cfg.n_kv_heads * hd, cfg.jdtype),
        "wo": _dense(ks[2], cfg.n_heads * hd, d, cfg.jdtype),
        **({"post_norm": {"scale": jnp.zeros((d,), cfg.jdtype)}}
           if cfg.post_block_norm else {}),
    }


def _split_heads(x, n, hd):
    B, T, _ = x.shape
    return x.reshape(B, T, n, hd)


def _causal_attention(cfg: ModelConfig, spec: BlockSpec, qh, kh, vh):
    """Causal self-attention over (B, H, T, D); streamed over KV chunks on
    the reference backend at or above ``chunk_threshold`` tokens."""
    T = qh.shape[2]
    if cfg.chunk_threshold and T >= cfg.chunk_threshold and KB == "ref":
        from ..kernels.ref import chunked_attention_ref
        return chunked_attention_ref(qh, kh, vh, causal=True,
                                     window=spec.window,
                                     softcap=cfg.attn_softcap,
                                     kv_chunk=cfg.attn_kv_chunk)
    return ops.flash_attention(qh, kh, vh, causal=True, window=spec.window,
                               softcap=cfg.attn_softcap, backend=KB)


def attn_fwd(cfg: ModelConfig, spec: BlockSpec, p, x, positions, mesh=None):
    B, T, d = x.shape
    hd = cfg.hd
    h = rms_norm(x, p["norm"]["scale"], cfg.norm_eps)
    q = _split_heads(h @ p["wq"]["w"], cfg.n_heads, hd)
    kv = h @ p["wkv"]["w"]
    k, v = jnp.split(kv, 2, axis=-1)
    k = _split_heads(k, cfg.n_kv_heads, hd)
    v = _split_heads(v, cfg.n_kv_heads, hd)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    # (B, H, T, D) layout for the kernel
    qh, kh, vh = (t.swapaxes(1, 2) for t in (q, k, v))
    qh = with_constraint(qh, mesh, ("batch", "tensor", "none", "none"))
    o = _causal_attention(cfg, spec, qh, kh, vh)
    o = o.swapaxes(1, 2).reshape(B, T, cfg.n_heads * hd)
    o = o @ p["wo"]["w"]
    if cfg.post_block_norm:
        o = rms_norm(o, p["post_norm"]["scale"], cfg.norm_eps)
    return x + o


def attn_init_state(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    hd = cfg.hd
    return {
        "k": jnp.zeros((batch, cfg.n_kv_heads, max_len, hd), cfg.jdtype),
        "v": jnp.zeros((batch, cfg.n_kv_heads, max_len, hd), cfg.jdtype),
    }


def attn_step(cfg: ModelConfig, spec: BlockSpec, p, x, state, pos, mesh=None):
    """x (B, 1, d); state KV cache filled up to ``pos``; returns (x, state)."""
    B, _, d = x.shape
    hd = cfg.hd
    h = rms_norm(x, p["norm"]["scale"], cfg.norm_eps)
    q = _split_heads(h @ p["wq"]["w"], cfg.n_heads, hd)
    k, v = jnp.split(h @ p["wkv"]["w"], 2, axis=-1)
    k = _split_heads(k, cfg.n_kv_heads, hd)
    v = _split_heads(v, cfg.n_kv_heads, hd)
    pvec = jnp.full((B, 1), pos, dtype=jnp.int32)
    q = rope(q, pvec, cfg.rope_theta)
    k = rope(k, pvec, cfg.rope_theta)
    kc = jax.lax.dynamic_update_slice_in_dim(state["k"], k.swapaxes(1, 2),
                                             pos, axis=2)
    vc = jax.lax.dynamic_update_slice_in_dim(state["v"], v.swapaxes(1, 2),
                                             pos, axis=2)
    o = ops.decode_attention(q.swapaxes(1, 2), kc, vc, window=spec.window,
                             softcap=cfg.attn_softcap, pos=pos, backend=KB)
    o = o.swapaxes(1, 2).reshape(B, 1, cfg.n_heads * hd) @ p["wo"]["w"]
    if cfg.post_block_norm:
        o = rms_norm(o, p["post_norm"]["scale"], cfg.norm_eps)
    return x + o, {"k": kc, "v": vc}


# ===========================================================================
# multi-head latent attention (DeepSeek-V2/V3, arXiv:2405.04434 §2.1)
# ===========================================================================

def mla_init(cfg: ModelConfig, key) -> dict:
    ks = jax.random.split(key, 5)
    d, H, dt = cfg.d_model, cfg.n_heads, cfg.jdtype
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ql, kl = cfg.q_lora_rank, cfg.kv_lora_rank
    return {
        "norm": {"scale": jnp.zeros((d,), dt)},
        "wq_a": _dense(ks[0], d, ql, dt),
        "q_norm": {"scale": jnp.zeros((ql,), dt)},
        "wq_b": _dense(ks[1], ql, H * (dn + dr), dt),
        "wkv_a": _dense(ks[2], d, kl + dr, dt),
        "kv_norm": {"scale": jnp.zeros((kl,), dt)},
        "wkv_b": _dense(ks[3], kl, H * (dn + dv), dt),
        "wo": _dense(ks[4], H * dv, d, dt),
    }


def _mla_q(cfg: ModelConfig, p, h, positions):
    """Queries through the q latent: (B, T, H, nope) and (B, T, H, rope)
    with RoPE applied."""
    B, T, _ = h.shape
    dn = cfg.qk_nope_head_dim
    c_q = rms_norm(h @ p["wq_a"]["w"], p["q_norm"]["scale"], cfg.norm_eps)
    q = (c_q @ p["wq_b"]["w"]).reshape(B, T, cfg.n_heads, -1)
    return q[..., :dn], rope(q[..., dn:], positions, cfg.rope_theta)


def _mla_latent(cfg: ModelConfig, p, h, positions):
    """What the cache holds per position: the normalised kv latent (B, T,
    kv_lora_rank) and the RoPE key shared by the heads (B, T, rope)."""
    kl = cfg.kv_lora_rank
    kv = h @ p["wkv_a"]["w"]
    c_kv = rms_norm(kv[..., :kl], p["kv_norm"]["scale"], cfg.norm_eps)
    k_pe = rope(kv[..., None, kl:], positions, cfg.rope_theta)[:, :, 0]
    return c_kv, k_pe


def mla_fwd(cfg: ModelConfig, spec: BlockSpec, p, x, positions, mesh=None):
    """Naive form: the latent is up-projected to per-head keys and values;
    scores are nope + rope wide, the context v_head_dim."""
    B, T, _ = x.shape
    H, dn, dv = cfg.n_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    dqk = dn + cfg.qk_rope_head_dim
    h = rms_norm(x, p["norm"]["scale"], cfg.norm_eps)
    q_nope, q_pe = _mla_q(cfg, p, h, positions)
    c_kv, k_pe = _mla_latent(cfg, p, h, positions)
    kv = (c_kv @ p["wkv_b"]["w"]).reshape(B, T, H, dn + dv)
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_pe[:, :, None], q_pe.shape)],
        axis=-1)
    # the attention kernels take one head width: V is zero-padded to the
    # score width and the padding sliced off the context
    v = jnp.pad(kv[..., dn:], ((0, 0),) * 3 + ((0, dqk - dv),))
    qh, kh, vh = (t.swapaxes(1, 2) for t in (q, k, v))
    qh = with_constraint(qh, mesh, ("batch", "tensor", "none", "none"))
    o = _causal_attention(cfg, spec, qh, kh, vh)[..., :dv]
    o = o.swapaxes(1, 2).reshape(B, T, H * dv)
    return x + o @ p["wo"]["w"]


def mla_init_state(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    return {
        "c_kv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), cfg.jdtype),
        "k_rope": jnp.zeros((batch, max_len, cfg.qk_rope_head_dim),
                            cfg.jdtype),
    }


def mla_prefill_state(cfg: ModelConfig, p, x, positions, max_len: int) -> dict:
    """The latent cache a prefill of x (B, T, d) leaves for decode, padded
    to ``max_len`` positions."""
    h = rms_norm(x, p["norm"]["scale"], cfg.norm_eps)
    c_kv, k_pe = _mla_latent(cfg, p, h, positions)
    pad = ((0, 0), (0, max_len - x.shape[1]), (0, 0))
    return {"c_kv": jnp.pad(c_kv, pad), "k_rope": jnp.pad(k_pe, pad)}


def mla_step(cfg: ModelConfig, spec: BlockSpec, p, x, state, pos, mesh=None):
    """Absorbed form over the latent cache: W_UK folds into the query and
    W_UV into the context, so all heads attend to one shared latent key
    (kv latent + RoPE key) and value (kv latent)."""
    B = x.shape[0]
    H, dn, dv = cfg.n_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    kl, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    h = rms_norm(x, p["norm"]["scale"], cfg.norm_eps)
    pvec = jnp.full((B, 1), pos, dtype=jnp.int32)
    q_nope, q_pe = _mla_q(cfg, p, h, pvec)
    c_new, k_pe_new = _mla_latent(cfg, p, h, pvec)
    c_kv = jax.lax.dynamic_update_slice_in_dim(state["c_kv"], c_new, pos,
                                               axis=1)
    k_rope = jax.lax.dynamic_update_slice_in_dim(state["k_rope"], k_pe_new,
                                                 pos, axis=1)
    w_b = p["wkv_b"]["w"].reshape(kl, H, dn + dv)
    q_lat = jnp.einsum("bhn,lhn->bhl", q_nope[:, 0], w_b[..., :dn])
    q = jnp.concatenate([q_lat, q_pe[:, 0]], axis=-1)[:, :, None]
    k = jnp.concatenate([c_kv, k_rope], axis=-1)[:, None]
    v = jnp.concatenate([c_kv, jnp.zeros_like(k_rope)], axis=-1)[:, None]
    o = ops.decode_attention(q, k, v, scale=(dn + dr) ** -0.5, pos=pos,
                             backend=KB)                # (B, H, 1, kl + dr)
    o = jnp.einsum("bhl,lhv->bhv", o[:, :, 0, :kl], w_b[..., dn:])
    o = o.reshape(B, 1, H * dv) @ p["wo"]["w"]
    return x + o, {"c_kv": c_kv, "k_rope": k_rope}


# ===========================================================================
# dense FFN (SwiGLU / GeGLU)
# ===========================================================================

def mlp_init(cfg: ModelConfig, key, d_ff=None) -> dict:
    ks = jax.random.split(key, 3)
    d, f = cfg.d_model, d_ff or cfg.d_ff
    p = {
        "norm": {"scale": jnp.zeros((d,), cfg.jdtype)},
        "up": _dense(ks[0], d, f, cfg.jdtype),
        "down": _dense(ks[1], f, d, cfg.jdtype),
    }
    if cfg.glu:
        p["gate"] = _dense(ks[2], d, f, cfg.jdtype)
    if cfg.post_block_norm:
        p["post_norm"] = {"scale": jnp.zeros((d,), cfg.jdtype)}
    return p


def _act(cfg):
    return jax.nn.silu if cfg.activation == "silu" else \
        (lambda t: jax.nn.gelu(t, approximate=True))


def mlp_fwd(cfg: ModelConfig, p, x, mesh=None):
    h = rms_norm(x, p["norm"]["scale"], cfg.norm_eps)
    up = h @ p["up"]["w"]
    if cfg.glu:
        up = _act(cfg)(h @ p["gate"]["w"]) * up
    else:
        up = _act(cfg)(up)
    o = up @ p["down"]["w"]
    if cfg.post_block_norm:
        o = rms_norm(o, p["post_norm"]["scale"], cfg.norm_eps)
    return x + o


# ===========================================================================
# MoE FFN (shared + routed experts; GShard-style capacity dispatch)
# ===========================================================================

def moe_init(cfg: ModelConfig, key) -> dict:
    ks = jax.random.split(key, 6)
    d, f, E = cfg.d_model, cfg.d_ff_e, cfg.n_experts
    p = {
        "norm": {"scale": jnp.zeros((d,), cfg.jdtype)},
        "router": _dense(ks[0], d, E, cfg.jdtype),
        "experts": {
            "w_up": make_dense(ks[1], (E, d, f), cfg.jdtype),
            "w_gate": make_dense(ks[2], (E, d, f), cfg.jdtype),
            "w_down": make_dense(ks[3], (E, f, d), cfg.jdtype),
        },
    }
    if cfg.router_score == "sigmoid":
        # added to the scores for the choice of experts only (DeepSeek-V3's
        # auxiliary-loss-free balancing)
        p["router"]["bias"] = jnp.zeros((E,), jnp.float32)
    if cfg.n_shared_experts:
        fs = f * cfg.n_shared_experts
        p["shared"] = {
            "up": _dense(ks[4], d, fs, cfg.jdtype),
            "gate": _dense(ks[5], d, fs, cfg.jdtype),
            "down": _dense(jax.random.fold_in(key, 7), fs, d, cfg.jdtype),
        }
    return p


def moe_fwd(cfg: ModelConfig, p, x, mesh=None):
    """Dropless-ish token-choice top-k with capacity dispatch.

    With a mesh, dispatch runs under ``shard_map``: tokens are split over
    every mesh axis (batch axes from the outer sharding, the model axis by
    explicit slicing), expert weights are replicated per device (their
    all-gather is the ZeRO-3 transposition of the FSDP sharding), and the
    one-hot/scatter machinery operates on purely local (T_loc, ·) tensors —
    GSPMD's scatter fallback otherwise materializes replicated full-global
    (T, d) tuples and all-reduces them (observed: 216 GB/dev and a 414 s
    collective term for the DeepSeekMoE train cell; see EXPERIMENTS §Perf).
    Returns x + moe(x); router aux loss on the ``moe_fwd.aux`` side channel.
    """
    B, T, d = x.shape
    # shard_map dispatch pays a full expert-weight gather per device — a win
    # for train/prefill token volumes, a catastrophe for decode (B tokens vs
    # 19 GB/layer of Jamba experts); below the threshold the token-space
    # tensors are tiny and GSPMD's fallback is harmless.
    if (mesh is not None and getattr(mesh, "axis_names", None)
            and B * T >= 8192):
        return _moe_fwd_shardmap(cfg, p, x, mesh)
    h = rms_norm(x, p["norm"]["scale"], cfg.norm_eps)
    y, aux = _moe_local(cfg, p, h.reshape(B * T, d))
    moe_fwd.aux = aux
    return x + y.reshape(B, T, d)


def _moe_fwd_shardmap(cfg: ModelConfig, p, x, mesh):
    from jax.sharding import PartitionSpec as P
    try:
        from jax import shard_map
    except ImportError:  # older jax
        from jax.experimental.shard_map import shard_map

    B, T, d = x.shape
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    mdl = "model" if "model" in mesh.axis_names else None

    h = rms_norm(x, p["norm"]["scale"], cfg.norm_eps)
    # replicate the MoE weights (ZeRO-style gather, inserted by GSPMD from
    # the sharded parameters) so the local math needs no further resharding
    rep = lambda t: with_constraint(t, mesh, ("none",) * t.ndim)
    weights = {"router": rep(p["router"]["w"]),
               "w_up": rep(p["experts"]["w_up"]),
               "w_gate": rep(p["experts"]["w_gate"]),
               "w_down": rep(p["experts"]["w_down"])}
    if "bias" in p["router"]:
        weights["router_bias"] = rep(p["router"]["bias"])
    if cfg.n_shared_experts:
        weights["s_up"] = rep(p["shared"]["up"]["w"])
        weights["s_gate"] = rep(p["shared"]["gate"]["w"])
        weights["s_down"] = rep(p["shared"]["down"]["w"])

    def local_fn(h_loc, w):
        Bl, Tl, _ = h_loc.shape
        toks = h_loc.reshape(Bl * Tl, d)
        # split tokens across the model axis too — unless there are too few
        # (decode: one token per sequence), in which case that axis stays
        # redundant for the MoE block
        split = (mdl is not None and (Bl * Tl) % mesh.shape[mdl] == 0
                 and (Bl * Tl) >= mesh.shape[mdl])
        if split:
            M = mesh.shape[mdl]
            per = (Bl * Tl) // M
            i = jax.lax.axis_index(mdl)
            mine = jax.lax.dynamic_slice_in_dim(toks, i * per, per, axis=0)
        else:
            mine = toks
        y_my, aux = _moe_local(cfg, {"_flat": w}, mine, flat=True)
        if split:
            y = jax.lax.all_gather(y_my, mdl, axis=0, tiled=True)
        elif mdl is not None:
            # redundant compute across the model axis: keep one replica's
            # result deterministic
            y = jax.lax.pmean(y_my, mdl)
        else:
            y = y_my
        axes = batch_axes + ((mdl,) if mdl else ())
        aux = jax.lax.pmean(aux, axes)
        return y.reshape(Bl, Tl, d), aux

    wspecs = {k: P(*(None,) * v.ndim) for k, v in weights.items()}
    y, aux = shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(batch_axes if batch_axes else None, None, None), wspecs),
        out_specs=(P(batch_axes if batch_axes else None, None, None), P()),
        check_vma=False,
    )(h, weights)
    moe_fwd.aux = aux
    return x + y


def _route(cfg: ModelConfig, logits, bias):
    """Top-k expert ids (T, k), their weights and the router's scores.

    ``softmax``: the top k of the softmax, weights renormalised.
    ``sigmoid`` (DeepSeek-V3): sigmoid scores; ``bias`` shifts them for the
    choice only; experts are chosen within the ``topk_groups`` best of
    ``n_expert_groups`` groups, a group scored by its two best; weights are
    the chosen scores renormalised, times ``routed_scale``."""
    k = cfg.top_k
    if cfg.router_score == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, eids = jax.lax.top_k(probs, k)
    else:
        probs = jax.nn.sigmoid(logits)
        sel = probs + bias
        T, E = sel.shape
        G = cfg.n_expert_groups
        grouped = sel.reshape(T, G, E // G)
        best = jax.lax.top_k(grouped, min(2, E // G))[0].sum(-1)   # (T, G)
        _, gids = jax.lax.top_k(best, cfg.topk_groups)
        keep = jnp.zeros((T, G), bool).at[
            jnp.arange(T)[:, None], gids].set(True)
        sel = jnp.where(keep[:, :, None], grouped, -jnp.inf).reshape(T, E)
        _, eids = jax.lax.top_k(sel, k)
        gate_vals = jnp.take_along_axis(probs, eids, axis=-1)
    gate_vals = gate_vals / jnp.clip(gate_vals.sum(-1, keepdims=True), 1e-9)
    return gate_vals * cfg.routed_scale, eids, probs


def _moe_local(cfg: ModelConfig, p, ht, flat: bool = False):
    """Local-token MoE math (no sharding constraints): ht (n_tok, d)."""
    E, k = cfg.n_experts, cfg.top_k
    if flat:
        w = p["_flat"]
        router_w, router_bias = w["router"], w.get("router_bias")
        w_up, w_gate, w_down = w["w_up"], w["w_gate"], w["w_down"]
        shared = ({"up": {"w": w["s_up"]}, "gate": {"w": w["s_gate"]},
                   "down": {"w": w["s_down"]}}
                  if cfg.n_shared_experts else None)
    else:
        router_w = p["router"]["w"]
        router_bias = p["router"].get("bias")
        w_up = p["experts"]["w_up"]
        w_gate = p["experts"]["w_gate"]
        w_down = p["experts"]["w_down"]
        shared = p.get("shared")
    n_tok, d = ht.shape

    logits = (ht @ router_w).astype(jnp.float32)
    gate_vals, eids, probs = _route(cfg, logits, router_bias)

    # load-balancing aux loss (Switch-style)
    me = probs.mean(axis=0)
    ce = jnp.zeros((E,), jnp.float32).at[eids.reshape(-1)].add(1.0) / (n_tok * k)
    aux = cfg.router_aux_coef * E * jnp.sum(me * ce)

    C = int(np.ceil(n_tok * k * cfg.capacity_factor / E))
    C = max(1, min(C, n_tok))
    # positions within each expert's capacity, computed per top-k slot so
    # that every live dispatch tensor is (T, ·) rather than (T·k, ·) — the
    # §Perf memory iteration for the MoE train cells (k=6 for DeepSeekMoE)
    flat_e = eids.reshape(-1)                               # (T*k,)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
    pos_flat = jnp.take_along_axis(jnp.cumsum(onehot, axis=0) - 1,
                                   flat_e[:, None], axis=1)[:, 0]
    pos_k = pos_flat.reshape(n_tok, k)

    buf = jnp.zeros((E, C, d), ht.dtype)
    for j in range(k):
        e_j = eids[:, j]
        p_j = pos_k[:, j]
        keep_j = p_j < C
        buf = buf.at[e_j, jnp.where(keep_j, p_j, C - 1)].add(
            jnp.where(keep_j[:, None], ht, 0))

    up = jnp.einsum("ecd,edf->ecf", buf, w_up)
    gate = jnp.einsum("ecd,edf->ecf", buf, w_gate)
    act = _act(cfg)(gate) * up
    out_e = jnp.einsum("ecf,efd->ecd", act, w_down)

    y = jnp.zeros_like(ht)
    for j in range(k):
        e_j = eids[:, j]
        p_j = pos_k[:, j]
        keep_j = p_j < C
        g_j = out_e[e_j, jnp.where(keep_j, p_j, 0)]         # (T, d)
        g_j = jnp.where(keep_j[:, None], g_j, 0)
        y = y + g_j * gate_vals[:, j][:, None].astype(g_j.dtype)

    if shared is not None:
        y = y + (_act(cfg)(ht @ shared["gate"]["w"])
                 * (ht @ shared["up"]["w"])) @ shared["down"]["w"]

    return y, aux


moe_fwd.aux = 0.0


# ===========================================================================
# Mamba (S6 selective scan)
# ===========================================================================

def mamba_init(cfg: ModelConfig, key) -> dict:
    ks = jax.random.split(key, 6)
    d, di, N, r = cfg.d_model, cfg.d_inner, cfg.d_state, cfg.dtr
    A = jnp.tile(jnp.arange(1, N + 1, dtype=jnp.float32)[None], (di, 1))
    return {
        "norm": {"scale": jnp.zeros((d,), cfg.jdtype)},
        "in_proj": _dense(ks[0], d, 2 * di, cfg.jdtype),
        "conv1d": {"w": make_dense(ks[1], (cfg.d_conv, di), cfg.jdtype)},
        "x_proj": {"w": make_dense(ks[2], (di, r + 2 * N), cfg.jdtype)},
        "dt_proj": {"w": make_dense(ks[3], (r, di), cfg.jdtype),
                    "bias": jnp.full((di,), -3.0, cfg.jdtype)},
        "A_log": jnp.log(A),
        "D": jnp.ones((di,), jnp.float32),
        "out_proj": _dense(ks[4], di, d, cfg.jdtype),
    }


def _causal_conv(x, w):
    """x (B, T, D), w (K, D) depthwise causal."""
    K = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    out = sum(xp[:, i:i + x.shape[1]] * w[i][None, None] for i in range(K))
    return out


def mamba_fwd(cfg: ModelConfig, p, x, mesh=None):
    B, T, d = x.shape
    di, N, r = cfg.d_inner, cfg.d_state, cfg.dtr
    h = rms_norm(x, p["norm"]["scale"], cfg.norm_eps)
    xz = h @ p["in_proj"]["w"]
    xs, z = jnp.split(xz, 2, axis=-1)
    xs = jax.nn.silu(_causal_conv(xs, p["conv1d"]["w"]))
    dbc = xs @ p["x_proj"]["w"]
    dt, Bc, Cc = jnp.split(dbc, [r, r + N], axis=-1)
    dt = jax.nn.softplus(dt @ p["dt_proj"]["w"] + p["dt_proj"]["bias"])
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    if cfg.chunk_threshold and T >= cfg.chunk_threshold and KB == "ref":
        from ..kernels.ref import chunked_selective_scan_ref
        y, _ = chunked_selective_scan_ref(xs, dt, A, Bc, Cc, p["D"],
                                          chunk=cfg.scan_chunk)
    else:
        y, _ = ops.ssm_scan(xs, dt, A, Bc, Cc, p["D"], backend=KB)
    y = y * jax.nn.silu(z)
    return x + y @ p["out_proj"]["w"]


def mamba_init_state(cfg: ModelConfig, batch: int) -> dict:
    di, N = cfg.d_inner, cfg.d_state
    return {
        "conv": jnp.zeros((batch, cfg.d_conv - 1, di), cfg.jdtype),
        "ssm": jnp.zeros((batch, di, N), jnp.float32),
    }


def mamba_step(cfg: ModelConfig, p, x, state, mesh=None):
    B, _, d = x.shape
    di, N, r = cfg.d_inner, cfg.d_state, cfg.dtr
    h = rms_norm(x, p["norm"]["scale"], cfg.norm_eps)
    xz = h[:, 0] @ p["in_proj"]["w"]
    xs, z = jnp.split(xz, 2, axis=-1)
    window = jnp.concatenate([state["conv"], xs[:, None]], axis=1)  # (B,K,di)
    w = p["conv1d"]["w"]
    xs = jax.nn.silu(jnp.einsum("bkd,kd->bd", window, w))
    dbc = xs @ p["x_proj"]["w"]
    dt, Bc, Cc = jnp.split(dbc, [r, r + N], axis=-1)
    dt = jax.nn.softplus(dt @ p["dt_proj"]["w"] + p["dt_proj"]["bias"]
                         ).astype(jnp.float32)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    dA = jnp.exp(dt[..., None] * A[None])                    # (B, di, N)
    hnew = dA * state["ssm"] + (dt * xs.astype(jnp.float32))[..., None] \
        * Bc.astype(jnp.float32)[:, None, :]
    y = jnp.einsum("bdn,bn->bd", hnew, Cc.astype(jnp.float32)) \
        + xs.astype(jnp.float32) * p["D"]
    y = (y.astype(x.dtype) * jax.nn.silu(z))[:, None]
    out = x + y @ p["out_proj"]["w"]
    return out, {"conv": window[:, 1:], "ssm": hnew}


# ===========================================================================
# RWKV-6 (time mix + channel mix)
# ===========================================================================

def rwkv_init(cfg: ModelConfig, key) -> dict:
    ks = jax.random.split(key, 10)
    d = cfg.d_model
    H = d // cfg.rwkv_head_dim
    r = cfg.rwkv_decay_rank
    return {
        "norm": {"scale": jnp.zeros((d,), cfg.jdtype)},
        "mix": make_dense(ks[0], (5, d), cfg.jdtype, scale=0.02),
        "rkvwg": {"w": make_dense(ks[1], (d, 4 * d), cfg.jdtype)},
        "w_lora_a": make_dense(ks[2], (d, r), cfg.jdtype),
        "w_lora_b": make_dense(ks[3], (r, d), cfg.jdtype),
        "time_decay": jnp.full((d,), -4.0, cfg.jdtype),
        "u": make_dense(ks[4], (H, cfg.rwkv_head_dim), cfg.jdtype, scale=0.1),
        "out_proj": _dense(ks[5], d, d, cfg.jdtype),
        "cnorm": {"scale": jnp.zeros((d,), cfg.jdtype)},
        "ck": _dense(ks[6], d, cfg.d_ff, cfg.jdtype),
        "cv": _dense(ks[7], cfg.d_ff, d, cfg.jdtype),
        "cr": _dense(ks[8], d, d, cfg.jdtype),
    }


def _rwkv_mix(h, hprev, mix):
    """token-shift interpolation for (r, k, v, w, g)."""
    return [h + (hprev - h) * mix[i][None, None] for i in range(5)]


def rwkv_fwd(cfg: ModelConfig, p, x, mesh=None):
    B, T, d = x.shape
    H, hd = d // cfg.rwkv_head_dim, cfg.rwkv_head_dim
    h = rms_norm(x, p["norm"]["scale"], cfg.norm_eps)
    hprev = jnp.pad(h, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    xr, xk, xv, xw, xg = _rwkv_mix(h, hprev, p["mix"])
    w4 = p["rkvwg"]["w"].reshape(d, 4, d)
    r = xr @ w4[:, 0]
    k = xk @ w4[:, 1]
    v = xv @ w4[:, 2]
    g = xg @ w4[:, 3]
    w_raw = p["time_decay"] + jnp.tanh(xw @ p["w_lora_a"]) @ p["w_lora_b"]
    w = jnp.exp(-jnp.exp(w_raw.astype(jnp.float32)))  # (B, T, d) in (0,1)

    def heads(t):
        return t.reshape(B, T, H, hd).swapaxes(1, 2)
    if cfg.chunk_threshold and T >= cfg.chunk_threshold and KB == "ref":
        from ..kernels.ref import chunked_rwkv6_ref
        o, _ = chunked_rwkv6_ref(heads(r), heads(k), heads(v),
                                 heads(w.astype(x.dtype)), p["u"],
                                 chunk=cfg.scan_chunk)
    else:
        o, _ = ops.rwkv6(heads(r), heads(k), heads(v),
                         heads(w.astype(x.dtype)), p["u"], backend=KB)
    o = o.swapaxes(1, 2).reshape(B, T, d)
    o = o * jax.nn.silu(g)
    x = x + o @ p["out_proj"]["w"]

    # channel mix
    h2 = rms_norm(x, p["cnorm"]["scale"], cfg.norm_eps)
    h2prev = jnp.pad(h2, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    xk2 = h2 + (h2prev - h2) * p["mix"][1][None, None]
    xr2 = h2 + (h2prev - h2) * p["mix"][0][None, None]
    kk = jnp.square(jax.nn.relu(xk2 @ p["ck"]["w"]))
    out = (kk @ p["cv"]["w"]) * jax.nn.sigmoid(xr2 @ p["cr"]["w"])
    return x + out


def rwkv_init_state(cfg: ModelConfig, batch: int) -> dict:
    d = cfg.d_model
    H, hd = d // cfg.rwkv_head_dim, cfg.rwkv_head_dim
    return {
        "tshift": jnp.zeros((batch, d), cfg.jdtype),
        "cshift": jnp.zeros((batch, d), cfg.jdtype),
        "wkv": jnp.zeros((batch, H, hd, hd), jnp.float32),
    }


def rwkv_step(cfg: ModelConfig, p, x, state, mesh=None):
    B, _, d = x.shape
    H, hd = d // cfg.rwkv_head_dim, cfg.rwkv_head_dim
    h = rms_norm(x, p["norm"]["scale"], cfg.norm_eps)[:, 0]
    hprev = state["tshift"]
    xs = [h + (hprev - h) * p["mix"][i][None] for i in range(5)]
    xr, xk, xv, xw, xg = xs
    w4 = p["rkvwg"]["w"].reshape(d, 4, d)
    r, k, v, g = (xr @ w4[:, 0], xk @ w4[:, 1], xv @ w4[:, 2], xg @ w4[:, 3])
    w_raw = p["time_decay"] + jnp.tanh(xw @ p["w_lora_a"]) @ p["w_lora_b"]
    w = jnp.exp(-jnp.exp(w_raw.astype(jnp.float32)))

    rh = r.reshape(B, H, hd).astype(jnp.float32)
    kh = k.reshape(B, H, hd).astype(jnp.float32)
    vh = v.reshape(B, H, hd).astype(jnp.float32)
    wh = w.reshape(B, H, hd)
    u = p["u"].astype(jnp.float32)
    kv = kh[..., :, None] * vh[..., None, :]
    o = jnp.einsum("bhk,bhkv->bhv", rh, state["wkv"] + u[None, :, :, None] * kv)
    wkv = wh[..., :, None] * state["wkv"] + kv
    o = (o.reshape(B, d).astype(x.dtype) * jax.nn.silu(g))[:, None]
    x = x + o @ p["out_proj"]["w"]

    h2 = rms_norm(x, p["cnorm"]["scale"], cfg.norm_eps)[:, 0]
    h2prev = state["cshift"]
    xk2 = h2 + (h2prev - h2) * p["mix"][1][None]
    xr2 = h2 + (h2prev - h2) * p["mix"][0][None]
    kk = jnp.square(jax.nn.relu(xk2 @ p["ck"]["w"]))
    out = ((kk @ p["cv"]["w"]) * jax.nn.sigmoid(xr2 @ p["cr"]["w"]))[:, None]
    return x + out, {"tshift": h, "cshift": h2, "wkv": wkv}
