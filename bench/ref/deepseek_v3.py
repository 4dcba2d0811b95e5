"""Plain reference of DeepSeek-V3's forward pass (arXiv:2412.19437; MLA as
in arXiv:2405.04434 §2.1), in float32 ``jax.numpy`` at the highest matmul
precision.  It imports nothing of the program under test.

``cfg`` is a dict with the keys of the published ``config.json``
(``hidden_size``, ``q_lora_rank``, ``n_routed_experts``, ...), as the
benchmark's configuration file holds them.  Weights are a plain pytree
(:func:`param_shapes` gives its layout); a matrix maps ``x @ W``.

- Multi-head latent attention in both forms: the naive one, which
  up-projects the kv latent to per-head keys and values (prefill), and the
  absorbed one, which folds W_UK into the query and W_UV into the context
  so every head reads the cached latent (decode).  :func:`decode_step`
  runs either, over a cache of the normalised kv latent and the RoPE key.
- Sigmoid routing, limited to the ``topk_group`` best of ``n_group``
  expert groups (a group scored by the sum of its two best biased
  scores), top ``num_experts_per_tok``, the chosen scores renormalised and
  scaled by ``routed_scaling_factor``; only each token's routed experts
  are computed; one shared expert beside them.
- Dense SwiGLU FFN on the first ``first_k_dense_replace`` layers, RMSNorm,
  an untied LM head.

Departures from the published model: no multi-token-prediction module
(a training and self-drafting head plain inference does not run); no YaRN
context scaling (it rescales RoPE frequencies and the softmax scale and
changes no shape); RoPE in the rotate-half layout, which is the published
interleaved layout with the q and k RoPE projection columns permuted.

Each operator class runs under a ``jax.named_scope`` of its name
(:data:`CLASSES`), so :func:`dot_macs` counts the multiply-accumulates of a
traced pass per class.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

CLASSES = ("q_path", "kv_path", "attn_scores", "attn_context", "absorb",
           "o_proj", "router", "experts", "dense_ffn", "head")


def _highest(fn):
    @functools.wraps(fn)
    def run(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)
    return run


def _widths(cfg):
    return (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["kv_lora_rank"])


def is_moe_layer(cfg, i: int) -> bool:
    return i >= cfg["first_k_dense_replace"]


def param_shapes(cfg, dtype=jnp.float32) -> dict:
    """The weights' layout, as ``ShapeDtypeStruct`` leaves."""
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    H, dn, dr, dv, kl = _widths(cfg)
    ql, E = cfg["q_lora_rank"], cfg["n_routed_experts"]
    f, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    fs = fe * cfg["n_shared_experts"]

    def s(*shape):
        return jax.ShapeDtypeStruct(shape, dtype)

    def ffn(width):
        return {"gate_proj": s(d, width), "up_proj": s(d, width),
                "down_proj": s(width, d)}

    layers = []
    for i in range(cfg["num_hidden_layers"]):
        layer = {
            "input_layernorm": s(d), "q_a_proj": s(d, ql),
            "q_a_layernorm": s(ql), "q_b_proj": s(ql, H * (dn + dr)),
            "kv_a_proj_with_mqa": s(d, kl + dr), "kv_a_layernorm": s(kl),
            "kv_b_proj": s(kl, H * (dn + dv)), "o_proj": s(H * dv, d),
            "post_attention_layernorm": s(d),
        }
        if is_moe_layer(cfg, i):
            layer["mlp"] = {
                "gate": s(d, E), "e_score_correction_bias": s(E),
                "experts": {"gate_proj": s(E, d, fe), "up_proj": s(E, d, fe),
                            "down_proj": s(E, fe, d)},
                "shared_experts": ffn(fs)}
        else:
            layer["mlp"] = ffn(f)
        layers.append(layer)
    return {"embed_tokens": s(V, d), "layers": layers, "norm": s(d),
            "lm_head": s(d, V)}


# -- pieces -------------------------------------------------------------------

def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, pos, theta):
    """Rotate-half RoPE: x (..., T, [heads,] D) at positions pos (T,)."""
    D = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = pos.astype(jnp.float32)[:, None] * inv            # (T, D/2)
    if x.ndim == 4:                                          # (B, T, H, D)
        ang = ang[:, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _queries(cfg, p, h, pos):
    """(B, T, H, nope) and (B, T, H, rope) with RoPE."""
    B, T, _ = h.shape
    H, dn, _, _, _ = _widths(cfg)
    with jax.named_scope("q_path"):
        c_q = rms_norm(h @ p["q_a_proj"], p["q_a_layernorm"],
                       cfg["rms_norm_eps"])
        q = (c_q @ p["q_b_proj"]).reshape(B, T, H, -1)
    return q[..., :dn], rope(q[..., dn:], pos, cfg["rope_theta"])


def latent(cfg, p, h, pos):
    """What the cache holds per position: the normalised kv latent (B, T,
    kv_lora_rank) and the RoPE key shared by all heads (B, T, rope)."""
    kl = cfg["kv_lora_rank"]
    with jax.named_scope("kv_path"):
        kv = h @ p["kv_a_proj_with_mqa"]
    c_kv = rms_norm(kv[..., :kl], p["kv_a_layernorm"], cfg["rms_norm_eps"])
    return c_kv, rope(kv[..., kl:], pos, cfg["rope_theta"])


def _scale(cfg):
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5


def _up(cfg, p, c_kv):
    """Naive form: per-head keys (nope part) and values from the latent."""
    B, L, _ = c_kv.shape
    H, dn, _, _, _ = _widths(cfg)
    with jax.named_scope("kv_path"):
        kv = (c_kv @ p["kv_b_proj"]).reshape(B, L, H, -1)
    return kv[..., :dn], kv[..., dn:]


def _attend(cfg, p, q_nope, q_pe, c_kv, k_pe, mask, absorbed):
    """Context (B, Tq, H*v) of queries over the latent cache; ``mask``
    (Tq, L) marks the keys each query sees."""
    H, dn, _, _, _ = _widths(cfg)
    B, Tq = q_nope.shape[:2]
    if absorbed:
        w = p["kv_b_proj"].reshape(cfg["kv_lora_rank"], H, -1)
        with jax.named_scope("absorb"):
            q_lat = jnp.einsum("bqhn,lhn->bqhl", q_nope, w[..., :dn])
        with jax.named_scope("attn_scores"):
            s = (jnp.einsum("bqhl,bkl->bhqk", q_lat, c_kv)
                 + jnp.einsum("bqhr,bkr->bhqk", q_pe, k_pe))
    else:
        k_nope, v = _up(cfg, p, c_kv)
        with jax.named_scope("attn_scores"):
            s = (jnp.einsum("bqhn,bkhn->bhqk", q_nope, k_nope)
                 + jnp.einsum("bqhr,bkr->bhqk", q_pe, k_pe))
    s = jnp.where(mask, s * _scale(cfg), -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    if absorbed:
        with jax.named_scope("attn_context"):
            o_lat = jnp.einsum("bhqk,bkl->bqhl", a, c_kv)
        with jax.named_scope("absorb"):
            o = jnp.einsum("bqhl,lhv->bqhv", o_lat, w[..., dn:])
    else:
        with jax.named_scope("attn_context"):
            o = jnp.einsum("bhqk,bkhv->bqhv", a, v)
    return o.reshape(B, Tq, -1)


def _o_proj(p, o):
    with jax.named_scope("o_proj"):
        return o @ p["o_proj"]


def _swiglu(p, h):
    return (jax.nn.silu(h @ p["gate_proj"]) * (h @ p["up_proj"])) \
        @ p["down_proj"]


def route(cfg, p, h):
    """Expert ids (T, k) and weights (T, k) of tokens h (T, d)."""
    E, G = cfg["n_routed_experts"], cfg["n_group"]
    k = cfg["num_experts_per_tok"]
    with jax.named_scope("router"):
        scores = jax.nn.sigmoid(h @ p["gate"])
    choice = (scores + p["e_score_correction_bias"]).reshape(-1, G, E // G)
    group = -jnp.sort(-choice, axis=-1)[..., :2].sum(-1)            # (T, G)
    keep = jnp.argsort(-group, axis=-1)[:, :cfg["topk_group"]]
    kept = (jnp.arange(G)[None, :, None] == keep[:, None, :]).any(-1)
    choice = jnp.where(kept[:, :, None], choice, -jnp.inf).reshape(-1, E)
    ids = jnp.argsort(-choice, axis=-1)[:, :k]
    w = jnp.take_along_axis(scores, ids, axis=-1)
    w = w / w.sum(-1, keepdims=True) * cfg["routed_scaling_factor"]
    return ids, w


def moe(cfg, p, h):
    """Routed experts (only each token's own) plus the shared expert."""
    B, T, d = h.shape
    x = h.reshape(B * T, d)
    ids, w = route(cfg, p, x)
    e = p["experts"]
    with jax.named_scope("experts"):
        g = jnp.einsum("td,tkdf->tkf", x, e["gate_proj"][ids])
        u = jnp.einsum("td,tkdf->tkf", x, e["up_proj"][ids])
        y = jnp.einsum("tkf,tkfd->tkd", jax.nn.silu(g) * u,
                       e["down_proj"][ids])
        y = (y * w[..., None]).sum(1) + _swiglu(p["shared_experts"], x)
    return y.reshape(B, T, d)


def _ffn(cfg, i, p, x):
    h = rms_norm(x, p["post_attention_layernorm"], cfg["rms_norm_eps"])
    if is_moe_layer(cfg, i):
        return x + moe(cfg, p["mlp"], h)
    with jax.named_scope("dense_ffn"):
        return x + _swiglu(p["mlp"], h)


def _head(cfg, params, x):
    x = rms_norm(x, params["norm"], cfg["rms_norm_eps"])
    with jax.named_scope("head"):
        return x @ params["lm_head"]


# -- whole passes -------------------------------------------------------------

@_highest
def mla_layer(cfg, p, x):
    """One MLA sublayer over a whole sequence (naive form): x + attn(x)."""
    T = x.shape[1]
    pos = jnp.arange(T)
    h = rms_norm(x, p["input_layernorm"], cfg["rms_norm_eps"])
    q_nope, q_pe = _queries(cfg, p, h, pos)
    c_kv, k_pe = latent(cfg, p, h, pos)
    mask = pos[None, :] <= pos[:, None]
    return x + _o_proj(p, _attend(cfg, p, q_nope, q_pe, c_kv, k_pe, mask,
                                  absorbed=False))


@_highest
def forward(cfg, params, tokens):
    """Logits (B, S, V) of tokens (B, S), causal, naive MLA."""
    x = params["embed_tokens"][tokens]
    for i, p in enumerate(params["layers"]):
        x = _ffn(cfg, i, p, mla_layer(cfg, p, x))
    return _head(cfg, params, x)


def init_cache(cfg, batch: int, length: int) -> list:
    """Per layer, the latent cache: (kv latent, RoPE key)."""
    return [(jnp.zeros((batch, length, cfg["kv_lora_rank"]), jnp.float32),
             jnp.zeros((batch, length, cfg["qk_rope_head_dim"]),
                       jnp.float32))
            for _ in range(cfg["num_hidden_layers"])]


@_highest
def mla_step(cfg, p, x, pos, cache, absorbed: bool = True):
    """One token x (B, 1, d) at position ``pos`` through one MLA sublayer
    over ``cache`` (positions before ``pos`` filled); returns x + attn(x)
    and the cache with this token's entry written."""
    posv = jnp.full((1,), pos)
    h = rms_norm(x, p["input_layernorm"], cfg["rms_norm_eps"])
    q_nope, q_pe = _queries(cfg, p, h, posv)
    c_new, k_new = latent(cfg, p, h, posv)
    c_kv = jax.lax.dynamic_update_slice_in_dim(cache[0], c_new, pos, axis=1)
    k_pe = jax.lax.dynamic_update_slice_in_dim(cache[1], k_new, pos, axis=1)
    mask = (jnp.arange(c_kv.shape[1]) <= pos)[None, :]
    o = _attend(cfg, p, q_nope, q_pe, c_kv, k_pe, mask, absorbed)
    return x + _o_proj(p, o), (c_kv, k_pe)


@_highest
def decode_step(cfg, params, cache, token, pos, absorbed: bool = True):
    """Logits (B, V) of one token (B,) at position ``pos``, and the cache."""
    x = params["embed_tokens"][token][:, None]
    out = []
    for i, (p, c) in enumerate(zip(params["layers"], cache)):
        x, c = mla_step(cfg, p, x, pos, c, absorbed)
        x = _ffn(cfg, i, p, x)
        out.append(c)
    return _head(cfg, params, x)[:, 0], out


# -- multiply-accumulates of a traced pass ------------------------------------

def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for j in v if isinstance(v, (tuple, list)) else (v,):
            if hasattr(j, "eqns"):
                yield j
            elif hasattr(getattr(j, "jaxpr", None), "eqns"):
                yield j.jaxpr


def dot_macs(jaxpr, scope: str = "") -> dict:
    """Multiply-accumulates of every ``dot_general`` of ``jaxpr`` (a
    ``ClosedJaxpr`` or ``Jaxpr``), summed by the outermost named scope it
    runs under ("" for none), through ``jit`` and ``custom_jvp`` bodies.
    Loops are refused: their trip counts are not in the equations."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    out: dict = {}
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in ("scan", "while"):
            raise ValueError(f"cannot count MACs through {name}")
        sc = scope or str(eqn.source_info.name_stack).split("/")[0]
        if name == "dot_general":
            (_, rc), (_, rb) = eqn.params["dimension_numbers"]
            free = [v for i, v in enumerate(eqn.invars[1].aval.shape)
                    if i not in rc and i not in rb]
            n = math.prod(eqn.invars[0].aval.shape) * math.prod(free)
            out[sc] = out.get(sc, 0) + n
        for sub in _sub_jaxprs(eqn):
            for k, v in dot_macs(sub, sc).items():
                out[k] = out.get(k, 0) + v
    return out
