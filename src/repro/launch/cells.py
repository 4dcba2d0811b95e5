"""(architecture × input-shape) dry-run cells.

Each cell = a jit'd step function + ShapeDtypeStruct inputs + NamedShardings,
ready to ``.lower().compile()`` — no real allocation anywhere (params come
from ``jax.eval_shape`` over the initializers).

Assigned shapes (LM family, applied to every arch):
  train_4k     seq 4096   global_batch 256   → train_step
  prefill_32k  seq 32768  global_batch 32    → prefill (forward, no grad)
  decode_32k   seq 32768  global_batch 128   → serve_step (1 token, full KV)
  long_500k    seq 524288 global_batch 1     → serve_step; SSM/hybrid only
                                               (skips recorded in DESIGN.md §4)

Modality stubs: phi-3-vision gets 576 precomputed patch embeddings inside
the 4096-token budget; whisper gets 1500 precomputed encoder frame
embeddings and decodes against the assigned sequence lengths mechanically.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import ARCH_IDS, get_config
from ..models import encdec as ED
from ..models import transformer as TF
from ..models.common import ModelConfig
from ..parallel.sharding import logical_to_spec, shard_params_spec
from ..serve.engine import (ServeConfig, build_serve_step,
                            decode_state_shapes, state_sharding_spec)
from ..train.step import build_train_step, make_train_state

__all__ = ["SHAPES", "cell_is_applicable", "build_cell", "all_cells"]

SHAPES = {
    "train_4k": dict(seq_len=4096, global_batch=256, kind="train"),
    "prefill_32k": dict(seq_len=32768, global_batch=32, kind="prefill"),
    "decode_32k": dict(seq_len=32768, global_batch=128, kind="decode"),
    "long_500k": dict(seq_len=524288, global_batch=1, kind="decode"),
}

# long_500k needs sub-quadratic attention: run only for SSM/hybrid families
LONG_OK = {"jamba_1_5_large_398b", "rwkv6_7b"}


def cell_is_applicable(arch: str, shape: str) -> tuple[bool, str]:
    if shape == "long_500k" and arch not in LONG_OK:
        return False, ("pure full-attention (or modality-inapplicable) arch; "
                       "524k decode assigned to SSM/hybrid families only")
    return True, ""


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _batch_shapes(cfg: ModelConfig, B: int, T: int):
    batch = {"tokens": _sds((B, T - cfg.prefix_len), jnp.int32),
             "labels": _sds((B, T - cfg.prefix_len), jnp.int32)}
    if cfg.prefix_len:
        batch["prefix_embeds"] = _sds((B, cfg.prefix_len, cfg.d_model),
                                      jnp.float32)
    if cfg.is_encoder_decoder:
        batch["enc_embeds"] = _sds((B, cfg.enc_seq_len, cfg.d_model),
                                   jnp.float32)
    return batch


def _batch_shardings(batch, mesh):
    return jax.tree.map(
        lambda x: NamedSharding(mesh, logical_to_spec(
            ("batch",) + ("none",) * (len(x.shape) - 1), x.shape, mesh)),
        batch)


@dataclass
class Cell:
    arch: str
    shape: str
    jitted: object
    args: tuple
    cfg: ModelConfig

    def lower(self):
        return self.jitted.lower(*self.args)


def build_cell(arch: str, shape: str, mesh, *,
               opt_dtype=None, compress_grads=False,
               accum_steps: int = 1) -> Cell:
    ok, why = cell_is_applicable(arch, shape)
    assert ok, f"{arch}×{shape} skipped: {why}"
    cfg = get_config(arch)
    info = SHAPES[shape]
    B, T = info["global_batch"], info["seq_len"]
    kind = info["kind"]

    if kind == "train":
        # bf16 optimizer moments for the 398B config: fp32 moments alone are
        # 3.2 TB — 12.4 GB/chip at 256-way sharding, over the 16 GB budget
        # once activations are added.
        odt = opt_dtype or (jnp.bfloat16 if cfg.n_params() > 1e11
                            else jnp.float32)
        state_shapes = jax.eval_shape(
            lambda: make_train_state(cfg, jax.random.PRNGKey(0),
                                     compress_grads, odt))
        batch = _batch_shapes(cfg, B, T)
        step = build_train_step(cfg, mesh, accum_steps=accum_steps,
                                compress_grads=compress_grads)
        jitted = step.jit_with(state_shapes, batch)
        return Cell(arch, shape, jitted, (state_shapes, batch), cfg)

    params_shapes = jax.eval_shape(
        lambda: (ED.init_params_encdec(cfg, jax.random.PRNGKey(0))
                 if cfg.is_encoder_decoder
                 else TF.init_params(cfg, jax.random.PRNGKey(0))))
    pspec = jax.tree.map(lambda s: NamedSharding(mesh, s),
                         shard_params_spec(params_shapes, mesh),
                         is_leaf=lambda x: isinstance(x, P))

    if kind == "prefill":
        batch = _batch_shapes(cfg, B, T)
        if cfg.is_encoder_decoder:
            def prefill(params, tokens, enc_embeds):
                return ED.forward_encdec(params, tokens, enc_embeds, cfg, mesh)
            bsh = _batch_shardings(batch, mesh)
            args = (params_shapes, batch["tokens"], batch["enc_embeds"])
            shardings = (pspec, bsh["tokens"], bsh["enc_embeds"])
        elif cfg.prefix_len:
            def prefill(params, tokens, prefix):
                out, _ = TF.forward(params, tokens, cfg, mesh,
                                    prefix_embeds=prefix)
                return out
            bsh = _batch_shardings(batch, mesh)
            args = (params_shapes, batch["tokens"], batch["prefix_embeds"])
            shardings = (pspec, bsh["tokens"], bsh["prefix_embeds"])
        else:
            def prefill(params, tokens):
                out, _ = TF.forward(params, tokens, cfg, mesh)
                return out
            args = (params_shapes, batch["tokens"])
            shardings = (pspec, _batch_shardings(batch, mesh)["tokens"])
        jitted = jax.jit(prefill, in_shardings=shardings)
        return Cell(arch, shape, jitted, args, cfg)

    # decode
    sc = ServeConfig(batch=B, max_len=T)
    state_shapes = decode_state_shapes(cfg, sc)
    token = _sds((B,), jnp.int32)
    pos = _sds((), jnp.int32)
    if cfg.is_encoder_decoder:
        enc_out = _sds((B, cfg.enc_seq_len, cfg.d_model), cfg.jdtype)
        step, jit_with = build_serve_step(cfg, mesh,
                                          enc_out_shape=enc_out.shape)
        jitted = jit_with(params_shapes, state_shapes)
        args = (params_shapes, state_shapes, token, pos, enc_out)
    else:
        step, jit_with = build_serve_step(cfg, mesh)
        jitted = jit_with(params_shapes, state_shapes)
        args = (params_shapes, state_shapes, token, pos)
    return Cell(arch, shape, jitted, args, cfg)


def all_cells() -> list[tuple[str, str]]:
    out = []
    for arch in ARCH_IDS:
        for shape in SHAPES:
            out.append((arch, shape))
    return out
