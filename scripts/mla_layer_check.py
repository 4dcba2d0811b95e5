"""One multi-head latent attention layer of ``repro.models`` against the
plain reference (``bench/ref/deepseek_v3.py``) at DeepSeek-V3's published
widths, in float32 at the highest matmul precision.

    python scripts/mla_layer_check.py [--prompt 2048] [--steps 16] [--seed 0]

Seeded random weights (norm scales too) and inputs.  The model runs a
prefill of ``--prompt`` tokens (naive form), keeps the latent cache it
leaves, then ``--steps`` decode steps (absorbed form); the reference runs
its naive layer over all ``prompt + steps`` tokens.  The error of a phase
is the largest absolute difference of the attention output (the layer's
output less its input) over the largest reference value.  The same weights
in bfloat16 are the control: they must miss the limit, as float32 must
meet it.  A third reading, ``ref_form``, runs the reference's own absorbed
decode against its naive layer: the part of the decode error that the
change of form alone brings on this device.  The last line of stdout is
one JSON object with the errors, the limit, ``ok`` and the device.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# both sides in float32 differ in the order of their sums only (chunked
# against whole attention, absorbed against naive).  On a TPU v5e at the
# highest precision that reads 1.2e-5 in prefill and 1.5e-4 in decode,
# where the absorbed form reorders the sums (30 times the CPU's readings);
# bfloat16 weights err by 7e-3 and more.  The limit sits between, with a
# factor of about 7 on either side.
LIMIT = 1e-3


def ref_layer(cfg, p) -> tuple:
    """The reference's weights and config keys of one MLA layer."""
    import jax.numpy as jnp

    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    rcfg = {"num_attention_heads": cfg.n_heads,
            "q_lora_rank": cfg.q_lora_rank, "kv_lora_rank": cfg.kv_lora_rank,
            "qk_nope_head_dim": cfg.qk_nope_head_dim,
            "qk_rope_head_dim": cfg.qk_rope_head_dim,
            "v_head_dim": cfg.v_head_dim, "rms_norm_eps": cfg.norm_eps,
            "rope_theta": cfg.rope_theta}
    rp = {"input_layernorm": 1.0 + f32(p["norm"]["scale"]),
          "q_a_proj": f32(p["wq_a"]["w"]),
          "q_a_layernorm": 1.0 + f32(p["q_norm"]["scale"]),
          "q_b_proj": f32(p["wq_b"]["w"]),
          "kv_a_proj_with_mqa": f32(p["wkv_a"]["w"]),
          "kv_a_layernorm": 1.0 + f32(p["kv_norm"]["scale"]),
          "kv_b_proj": f32(p["wkv_b"]["w"]), "o_proj": f32(p["wo"]["w"])}
    return rcfg, rp


def ref_absorbed_decode(ref, rcfg, rp, x, prompt: int):
    """The reference's absorbed decode of x[:, prompt:] (less its input)
    over the latent cache of x[:, :prompt]."""
    import jax
    import jax.numpy as jnp

    T = x.shape[1]
    pos = jnp.arange(prompt)
    h = ref.rms_norm(x[:, :prompt], rp["input_layernorm"],
                     rcfg["rms_norm_eps"])
    pad = ((0, 0), (0, T - prompt), (0, 0))
    with jax.default_matmul_precision("highest"):
        cache = tuple(jnp.pad(a, pad) for a in ref.latent(rcfg, rp, h, pos))
    step = jax.jit(lambda rp, xt, c, t: ref.mla_step(rcfg, rp, xt, t, c))
    out = []
    for t in range(prompt, T):
        yt, cache = step(rp, x[:, t:t + 1], cache, t)
        out.append(yt - x[:, t:t + 1])
    return jnp.concatenate(out, axis=1)


def compare(cfg, prompt: int, steps: int, seed: int) -> dict:
    """Errors of the model's prefill and decode against the reference, in
    float32 and (the control) bfloat16, and of the reference's absorbed
    decode against its naive layer."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench.ref import deepseek_v3 as ref
    from repro.models import blocks as B

    cfg = dataclasses.replace(cfg, dtype="float32")
    spec = next(s for s in cfg.layer_pattern if s.kind == "mla")
    key = jax.random.PRNGKey(seed)
    p = B.mla_init(cfg, key)
    p = jax.tree.map(lambda a: a + 0.3 * jax.random.normal(
        jax.random.fold_in(key, a.size), a.shape) if a.ndim == 1 else a, p)
    T = prompt + steps
    x = jax.random.normal(jax.random.fold_in(key, 7), (1, T, cfg.d_model))
    rcfg, rp = ref_layer(cfg, p)
    want = np.asarray(jax.jit(lambda rp, x: ref.mla_layer(rcfg, rp, x) - x)(
        rp, x))

    def err(got, lo, hi):
        w = want[:, lo:hi]
        return float(np.max(np.abs(np.asarray(got, np.float32) - w))
                     / np.max(np.abs(w)))

    out = {"ref_form": {"decode": err(ref_absorbed_decode(
        ref, rcfg, rp, x, prompt), prompt, T)}}
    for name, dt in (("f32", jnp.float32), ("bf16", jnp.bfloat16)):
        c = dataclasses.replace(cfg, dtype=jnp.dtype(dt).name)
        q = jax.tree.map(lambda a: a.astype(dt), p)
        xs = x.astype(dt)
        pos = jnp.arange(prompt)[None]
        with jax.default_matmul_precision("highest"):
            y = jax.jit(lambda q, xs: B.mla_fwd(c, spec, q, xs, pos))(
                q, xs[:, :prompt])
            state = B.mla_prefill_state(c, q, xs[:, :prompt], pos, T)
            step = jax.jit(lambda q, xt, st, t: B.mla_step(c, spec, q, xt,
                                                           st, t))
            dec = []
            for t in range(prompt, T):
                yt, state = step(q, xs[:, t:t + 1], state, t)
                dec.append(yt - xs[:, t:t + 1])
        out[name] = {
            "prefill": err(y.astype(jnp.float32) - x[:, :prompt], 0, prompt),
            "decode": err(jnp.concatenate(dec, axis=1), prompt, T)}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--prompt", type=int, default=2048)
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    for p in (_ROOT, os.path.join(_ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax

    from repro.configs import get_config

    errs = compare(get_config("deepseek_v3_671b"), args.prompt, args.steps,
                   args.seed)
    d = jax.devices()[0]
    ok = (max(errs["f32"].values()) < LIMIT
          and min(errs["bf16"].values()) > LIMIT)
    print(json.dumps({"ok": ok, "limit": LIMIT, "errors": errs,
                      "prompt": args.prompt, "steps": args.steps,
                      "device": {"platform": d.platform,
                                 "kind": d.device_kind}}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
