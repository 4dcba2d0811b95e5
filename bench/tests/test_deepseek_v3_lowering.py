"""The lowering of the benchmark's DeepSeek-V3 configuration against the
plain reference, at published widths and 32768 tokens: per operator
class, the MACs of the program's rows equal those of the ``dot_general``
equations of the reference's traced pass (over ``ShapeDtypeStruct``
weights, so nothing is allocated), in prefill (naive MLA over the whole
prompt) and decode (one absorbed step over a 32768-position cache)."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from bench.lib.cell import BENCH_DIR, model_config
from bench.ref import deepseek_v3 as ref

SEQ = 32768

# the program's lowered operators -> the reference's operator classes
OP_CLASS = {"q_a_proj": "q_path", "q_b_proj": "q_path",
            "kv_a_proj": "kv_path", "kv_b_proj": "kv_path",
            "attn_scores": "attn_scores", "attn_context": "attn_context",
            "absorb_uk": "absorb", "absorb_uv": "absorb",
            "out_proj": "o_proj", "router": "router",
            "expert_up": "experts", "expert_down": "experts",
            "ffn_up": "dense_ffn", "ffn_down": "dense_ffn",
            "lm_head": "head"}


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH_DIR, "configs", "deepseek_v3_671b.json")) as f:
        return json.load(f)


def program_macs(config, phase: str) -> dict:
    from repro.frontend import build_model_graph

    (m,) = config["models"].values()
    out: dict = {}
    for n in build_model_graph(model_config(m["model_config"]), seq=SEQ,
                               phase=phase).nodes:
        c = OP_CLASS[n.op]
        out[c] = out.get(c, 0) + n.macs
    return out


def reference_macs(cfg: dict, phase: str) -> dict:
    shapes = ref.param_shapes(cfg)
    if phase == "prefill":
        jaxpr = jax.make_jaxpr(lambda p, t: ref.forward(cfg, p, t))(
            shapes, jax.ShapeDtypeStruct((1, SEQ), jnp.int32))
    else:
        cache = jax.eval_shape(lambda: ref.init_cache(cfg, 1, SEQ))
        jaxpr = jax.make_jaxpr(lambda p, c, t: ref.decode_step(
            cfg, p, c, t, SEQ - 1))(
            shapes, cache, jax.ShapeDtypeStruct((1,), jnp.int32))
    return ref.dot_macs(jaxpr)


@pytest.mark.parametrize("phase", ["prefill", "decode"])
def test_published_width_macs_match_reference(config, phase):
    want = reference_macs(config, phase)
    assert set(want) == set(ref.CLASSES) - (
        {"absorb"} if phase == "prefill" else set())
    assert program_macs(config, phase) == want
