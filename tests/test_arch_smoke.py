"""Per-architecture smoke tests: reduced config of the same family, one
forward + one train-grad step + one decode step on CPU; asserts output
shapes and absence of NaNs.  The FULL configs are exercised only via the
dry-run (ShapeDtypeStructs, no allocation)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.models import transformer as TF
from repro.models import encdec as ED

KEY = jax.random.PRNGKey(0)
Bsz, T = 2, 32

DECODER_ARCHS = [a for a in ARCH_IDS if a != "whisper_base"]


def _batch(cfg, key):
    ks = jax.random.split(key, 3)
    tokens = jax.random.randint(ks[0], (Bsz, T), 0, cfg.vocab_size)
    labels = jax.random.randint(ks[1], (Bsz, T), 0, cfg.vocab_size)
    batch = {"tokens": tokens, "labels": labels}
    if cfg.prefix_len:
        batch["prefix_embeds"] = jax.random.normal(
            ks[2], (Bsz, cfg.prefix_len, cfg.d_model), jnp.float32) * 0.02
    return batch


@pytest.mark.parametrize("arch", DECODER_ARCHS)
def test_forward_and_grad(arch):
    cfg = get_config(arch, reduced=True)
    params = TF.init_params(cfg, KEY)
    batch = _batch(cfg, jax.random.fold_in(KEY, 1))

    logits, aux = jax.jit(
        lambda p, t: TF.forward(p, t, cfg, prefix_embeds=batch.get(
            "prefix_embeds")))(params, batch["tokens"])
    assert logits.shape == (Bsz, T + cfg.prefix_len, cfg.vocab_size)
    assert not np.any(np.isnan(np.asarray(logits, np.float32)))

    (loss, metrics), grads = jax.jit(
        lambda p, b: jax.value_and_grad(TF.loss_fn, has_aux=True)(p, b, cfg)
    )(params, batch)
    assert np.isfinite(float(loss))
    gnorm = jax.tree_util.tree_reduce(
        lambda a, g: a + float(jnp.sum(jnp.square(g.astype(jnp.float32)))),
        grads, 0.0)
    assert np.isfinite(gnorm) and gnorm > 0


@pytest.mark.parametrize("arch", DECODER_ARCHS)
def test_decode_step(arch):
    cfg = get_config(arch, reduced=True)
    params = TF.init_params(cfg, KEY)
    state = TF.init_decode_state(cfg, Bsz, max_len=16)
    token = jnp.zeros((Bsz,), jnp.int32)
    step = jax.jit(lambda p, s, t, pos: TF.decode_step(p, s, t, pos, cfg))
    logits, state = step(params, state, token, 0)
    assert logits.shape == (Bsz, cfg.vocab_size)
    logits, state = step(params, state, jnp.argmax(logits, -1).astype(
        jnp.int32), 1)
    assert not np.any(np.isnan(np.asarray(logits)))


def test_decode_matches_forward_prefix():
    """Teacher-forced decode over a short prompt must match the parallel
    forward logits (validates cache/state handoff for the hybrid arch).
    fp32 + high MoE capacity so the comparison is numerically exact (bf16
    scan-order noise and train-time capacity drops are semantic, not bugs)."""
    import dataclasses
    cfg = dataclasses.replace(get_config("jamba_1_5_large_398b", reduced=True),
                              dtype="float32", capacity_factor=8.0)
    params = TF.init_params(cfg, KEY)
    toks = jax.random.randint(jax.random.fold_in(KEY, 9), (1, 8), 0,
                              cfg.vocab_size)
    full_logits, _ = TF.forward(params, toks, cfg)
    state = TF.init_decode_state(cfg, 1, max_len=8)
    outs = []
    for t in range(8):
        lg, state = TF.decode_step(params, state, toks[:, t], t, cfg)
        outs.append(lg)
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec, np.float32),
                               np.asarray(full_logits, np.float32),
                               rtol=1e-4, atol=1e-4)


def test_whisper_encdec_smoke():
    cfg = get_config("whisper_base", reduced=True)
    params = ED.init_params_encdec(cfg, KEY)
    enc_embeds = jax.random.normal(KEY, (Bsz, cfg.enc_seq_len, cfg.d_model))
    tokens = jax.random.randint(KEY, (Bsz, T), 0, cfg.vocab_size)
    logits = jax.jit(lambda p, t, e: ED.forward_encdec(p, t, e, cfg))(
        params, tokens, enc_embeds)
    assert logits.shape == (Bsz, T, cfg.vocab_size)
    assert not np.any(np.isnan(np.asarray(logits, np.float32)))

    enc_out = ED.encode(params, enc_embeds, cfg)
    state = ED.init_decode_state_encdec(cfg, Bsz, max_len=8)
    lg, state = ED.decode_step_encdec(params, state,
                                      jnp.zeros((Bsz,), jnp.int32), 0,
                                      enc_out, cfg)
    assert lg.shape == (Bsz, cfg.vocab_size)
    assert not np.any(np.isnan(np.asarray(lg)))


def test_param_counts_full_configs():
    """Sanity: full-config parameter counts are in the published ballpark."""
    expect = {
        "jamba_1_5_large_398b": (300e9, 500e9),
        "rwkv6_7b": (6e9, 9e9),
        "mistral_nemo_12b": (10e9, 14e9),
        "gemma_7b": (7e9, 10e9),
        "glm4_9b": (8e9, 11e9),
        "gemma2_9b": (8e9, 11.5e9),
        "llama4_scout_17b_a16e": (90e9, 120e9),
        "deepseek_moe_16b": (14e9, 20e9),
        "deepseek_v3_671b": (640e9, 700e9),
        "phi_3_vision_4_2b": (3.5e9, 5e9),
    }
    for arch, (lo, hi) in expect.items():
        n = get_config(arch).n_params()
        assert lo < n < hi, f"{arch}: {n/1e9:.2f}B outside [{lo/1e9},{hi/1e9}]"


def test_active_params_moe():
    cfg = get_config("llama4_scout_17b_a16e")
    act = cfg.n_active_params()
    assert 12e9 < act < 25e9  # ~17B active
    dsk = get_config("deepseek_moe_16b")
    assert 2e9 < dsk.n_active_params() < 5e9  # ~2.8B active
    v3 = get_config("deepseek_v3_671b")
    assert 35e9 < v3.n_active_params() < 40e9  # ~37B active
