"""Model-graph frontend tests: shape correctness of the lowering for all
assigned configs (both phases), golden dedup counts, and parity with the
hand-maintained layer tables the frontend replaced in
``benchmarks/nn_workloads.py``."""

import math

import pytest

from repro.configs import ARCH_IDS, get_config, resolve_ids
from repro.core import workload as W
from repro.frontend import (PHASES, build_model_graph, lower_model,
                            lower_zoo, merge_rows)
from repro.models.common import BlockSpec, ModelConfig

_WL = {"gemm": W.gemm(), "conv": W.conv2d(), "dwconv": W.depthwise_conv2d(),
       "attn_qk": W.attention_qk(), "attn_pv": W.attention_pv()}


def _row_macs(rows):
    return sum(rep * math.prod(dims.values()) for _, dims, rep, _ in rows)


def _shapes(rows):
    """Comparable set of (kind, sorted dims) over a row list."""
    return {(kind, tuple(sorted(dims.items()))) for kind, dims, _, _ in rows}


class TestShapeCorrectness:
    """Every lowered row must be a well-formed query for its workload."""

    @pytest.mark.parametrize("phase", PHASES)
    @pytest.mark.parametrize("name", ARCH_IDS)
    def test_rows_well_formed(self, name, phase):
        rows = lower_model(get_config(name), seq=128, phase=phase)
        assert rows, (name, phase)
        for kind, dims, rep, nt in rows:
            wl = _WL[kind]
            # dims must name the workload's iteration dims exactly
            assert set(dims) == set(wl.iter_dims), (name, kind, dims)
            assert all(isinstance(v, int) and v >= 1
                       for v in dims.values()), (name, dims)
            assert isinstance(rep, int) and rep >= 1
            assert nt >= 0.0

    @pytest.mark.parametrize("name", ARCH_IDS)
    def test_dedup_preserves_macs(self, name):
        g = build_model_graph(get_config(name), seq=96)
        assert _row_macs(g.lowered()) == g.macs()

    def test_merge_rows_sums_repeats(self):
        rows = [("gemm", dict(i=4, j=8, k=2), 3, 1.0),
                ("gemm", dict(i=4, j=8, k=2), 5, 1.0),
                ("gemm", dict(i=4, j=8, k=2), 1, 2.0)]  # nt differs: kept
        merged = merge_rows(rows)
        assert len(merged) == 2
        assert merged[0][2] == 8

    def test_bad_inputs_rejected(self):
        cfg = get_config("gemma_7b", reduced=True)
        with pytest.raises(ValueError):
            build_model_graph(cfg, phase="train")
        with pytest.raises(ValueError):
            build_model_graph(cfg, seq=0)
        with pytest.raises(ValueError):
            build_model_graph(cfg, batch=0)


class TestGoldenDedup:
    """Node/row counts are part of the lowering contract: a refactor that
    silently splits or drops operators shows up here first (full() configs,
    seq 512 — regenerate by printing n_nodes/len(lowered()))."""

    GOLDEN = {
        #                        prefill      decode
        "jamba_1_5_large_398b": ((60, 13), (60, 13)),
        "rwkv6_7b":             ((7, 7),   (7, 7)),
        "mistral_nemo_12b":     ((7, 7),   (7, 7)),
        "gemma_7b":             ((7, 7),   (7, 7)),
        "glm4_9b":              ((7, 7),   (7, 7)),
        "gemma2_9b":            ((13, 7),  (13, 7)),  # window 4096 > seq 512
        "llama4_scout_17b_a16e": ((8, 8),  (8, 8)),
        "deepseek_moe_16b":     ((8, 8),   (8, 8)),
        # 61 explicit layers; MLA decode adds the two absorptions
        "deepseek_v3_671b":     ((608, 13), (669, 14)),
        "phi_3_vision_4_2b":    ((8, 8),   (7, 7)),   # patch stem: prefill only
        "whisper_base":         ((20, 19), (11, 10)),  # encoder: prefill only
    }

    # fused attention rows per phase: (attn_qk, attn_pv) dedup counts.
    # Every attention-bearing config keeps the score-stationary op pair;
    # rwkv6 is attention-free; whisper adds self- + cross-attention variants
    # (encoder self-attention merges away in decode).
    GOLDEN_ATTN = {
        "jamba_1_5_large_398b": ((1, 1), (1, 1)),
        "rwkv6_7b":             ((0, 0), (0, 0)),
        "mistral_nemo_12b":     ((1, 1), (1, 1)),
        "gemma_7b":             ((1, 1), (1, 1)),
        "glm4_9b":              ((1, 1), (1, 1)),
        "gemma2_9b":            ((1, 1), (1, 1)),
        "llama4_scout_17b_a16e": ((1, 1), (1, 1)),
        "deepseek_moe_16b":     ((1, 1), (1, 1)),
        "deepseek_v3_671b":     ((1, 1), (1, 1)),
        "phi_3_vision_4_2b":    ((1, 1), (1, 1)),
        "whisper_base":         ((3, 3), (2, 2)),
    }

    def test_golden_covers_zoo(self):
        assert set(self.GOLDEN) == set(ARCH_IDS)
        assert set(self.GOLDEN_ATTN) == set(ARCH_IDS)

    @pytest.mark.parametrize("name", ARCH_IDS)
    def test_counts_stable(self, name):
        cfg = get_config(name)
        for phase, want, want_attn in zip(PHASES, self.GOLDEN[name],
                                          self.GOLDEN_ATTN[name]):
            g = build_model_graph(cfg, seq=512, phase=phase)
            rows = g.lowered()
            assert (g.n_nodes, len(rows)) == want, (name, phase)
            got_attn = (sum(1 for k, *_ in rows if k == "attn_qk"),
                        sum(1 for k, *_ in rows if k == "attn_pv"))
            assert got_attn == want_attn, (name, phase)
            # each qk row pairs with a pv row over the same score tensor
            # (b, m, n) and repeat; of identical dims, the contract
            # apply_attention_fusion relies on, except under latent
            # attention, whose stages differ in d
            def pairs(kind, keep_d):
                return {(tuple(sorted((a, v) for a, v in d.items()
                                      if keep_d or a != "d")), r)
                        for k, d, r, _ in rows if k == kind}
            mla = any(s.kind == "mla" for s in cfg.layer_pattern)
            assert pairs("attn_qk", False) == pairs("attn_pv", False), \
                (name, phase)
            assert (pairs("attn_qk", True) == pairs("attn_pv", True)) \
                != mla, (name, phase)


class TestFamilyFeatures:
    def test_gqa_shrinks_kv_projection(self):
        cfg = get_config("glm4_9b")  # 32 heads, kv=2
        g = build_model_graph(cfg, seq=64)
        qkv = next(n for n in g.nodes if n.op == "qkv_proj")
        assert qkv.dims["j"] == (32 + 2 * 2) * 128

    def test_moe_emits_router_and_active_experts(self):
        cfg = get_config("deepseek_moe_16b")  # 64 experts top-6 + 2 shared
        g = build_model_graph(cfg, seq=64)
        ops = g.ops()
        assert ops["router"] == 1
        up = next(n for n in g.nodes if n.op == "expert_up")
        assert up.repeat == cfg.n_periods * 2 * (6 + 2)  # glu up/gate
        assert up.dims["j"] == cfg.d_ff_expert

    def test_mla_naive_prefill_absorbed_decode(self):
        """DeepSeek-V3 at 32k: the naive form's rows in prefill, the
        absorbed form's in decode (arXiv:2405.04434 §2.1), per layer."""
        cfg = get_config("deepseek_v3_671b")
        S, d, H = 32768, 7168, 128

        def mla_rows(phase):
            g = build_model_graph(cfg, seq=S, phase=phase)
            return [(n.op, n.kind, n.dims, n.repeat) for n in g.nodes
                    if n.name.startswith("dec0.") and n.op not in
                    ("ffn_up", "ffn_down")]

        assert mla_rows("prefill") == [
            ("q_a_proj", "gemm", dict(i=S, j=1536, k=d), 1),
            ("q_b_proj", "gemm", dict(i=S, j=H * 192, k=1536), 1),
            ("kv_a_proj", "gemm", dict(i=S, j=512 + 64, k=d), 1),
            ("kv_b_proj", "gemm", dict(i=S, j=H * 256, k=512), 1),
            ("attn_scores", "attn_qk", dict(b=H, m=S, n=S, d=192), 1),
            ("attn_context", "attn_pv", dict(b=H, m=S, n=S, d=128), 1),
            ("out_proj", "gemm", dict(i=S, j=d, k=H * 128), 1)]
        assert mla_rows("decode") == [
            ("q_a_proj", "gemm", dict(i=1, j=1536, k=d), 1),
            ("q_b_proj", "gemm", dict(i=1, j=H * 192, k=1536), 1),
            ("kv_a_proj", "gemm", dict(i=1, j=512 + 64, k=d), 1),
            ("absorb_uk", "gemm", dict(i=1, j=512, k=128), H),
            ("attn_scores", "attn_qk", dict(b=1, m=H, n=S, d=576), 1),
            ("attn_context", "attn_pv", dict(b=1, m=H, n=S, d=512), 1),
            ("absorb_uv", "gemm", dict(i=1, j=128, k=512), H),
            ("out_proj", "gemm", dict(i=1, j=d, k=H * 128), 1)]

    @pytest.mark.parametrize("phase", PHASES)
    def test_mla_unfused_lowering_is_the_fallback(self, phase):
        from repro.frontend import unfuse_attention_rows
        cfg = get_config("deepseek_v3_671b")
        fused = lower_model(cfg, seq=4096, phase=phase)
        plain = lower_model(cfg, seq=4096, phase=phase,
                            fused_attention=False)
        assert unfuse_attention_rows(fused) == plain

    def test_jamba_ssm_lowers_dwconv(self):
        g = build_model_graph(get_config("jamba_1_5_large_398b"), seq=64)
        conv = [n for n in g.nodes if n.op == "ssm_conv"]
        assert conv and all(n.kind == "dwconv" for n in conv)
        assert conv[0].dims["kh"] == 4 and conv[0].dims["oh"] == 64

    def test_vision_prefix_stem_and_context(self):
        cfg = get_config("phi_3_vision_4_2b")  # 576-token prefix
        g = build_model_graph(cfg, seq=64)
        stem = next(n for n in g.nodes if n.op == "patch_embed")
        assert stem.kind == "conv"
        assert stem.dims["oh"] == stem.dims["ow"] == 24  # 576 = 24x24
        scores = next(n for n in g.nodes if n.op == "attn_scores")
        assert scores.kind == "attn_qk"
        assert scores.dims["n"] == 64 + 576  # prefix extends the context
        # decode: no stem, but the prefix stays in the KV context
        gd = build_model_graph(cfg, seq=64, phase="decode")
        assert not [n for n in gd.nodes if n.op == "patch_embed"]
        assert next(n for n in gd.nodes
                    if n.op == "attn_scores").dims["n"] == 64 + 576

    def test_window_clamps_context(self):
        cfg = get_config("gemma2_9b")  # local 4096 / global alternation
        g = build_model_graph(cfg, seq=8192)
        eff = sorted({n.dims["n"] for n in g.nodes if n.op == "attn_scores"})
        assert eff == [4096, 8192]

    def test_encdec_cross_attention(self):
        cfg = get_config("whisper_base")  # 6+6L, enc seq 1500
        g = build_model_graph(cfg, seq=64)
        ops = g.ops()
        assert ops["audio_embed"] == 1 and ops["cross_scores"] == 1
        xs = next(n for n in g.nodes if n.op == "cross_scores")
        assert xs.kind == "attn_qk"
        assert xs.dims["n"] == 1500 and xs.repeat == 6
        assert xs.dims["b"] == cfg.n_heads  # heads ride the batched b dim
        enc = [n for n in g.nodes if n.stage == "encoder"]
        assert enc and all(n.repeat % cfg.n_enc_layers == 0 for n in enc)
        gd = build_model_graph(cfg, seq=64, phase="decode")
        assert not [n for n in gd.nodes if n.stage == "encoder"]
        assert not [n for n in gd.nodes if n.op == "cross_kv_proj"]

    def test_decode_is_gemv_shaped(self):
        g = build_model_graph(get_config("gemma_7b"), seq=512,
                              phase="decode", lm_head=False)
        assert all(n.dims["i"] == 1 for n in g.nodes if n.kind == "gemm")
        scores = next(n for n in g.nodes if n.op == "attn_scores")
        assert scores.dims["m"] == 1   # one query row per sequence
        assert scores.dims["n"] == 512  # full context as the score axis


class TestDecodeEdgeCases:
    """Boundary shapes of the phase contract: the golden counts only pin
    default shapes, so the seq=1 extremes need their own tests."""

    def test_seq1_prefill_well_formed(self):
        """A one-token prefill: every row must still be a valid workload
        query (dims >= 1), attention collapses to a 1x1 score tile."""
        g = build_model_graph(get_config("gemma_7b"), seq=1)
        for n in g.nodes:
            wl = _WL[n.kind]
            assert set(n.dims) == set(wl.iter_dims), n
            assert all(v >= 1 for v in n.dims.values()), n
        scores = next(n for n in g.nodes if n.op == "attn_scores")
        assert scores.dims["m"] == scores.dims["n"] == 1
        qkv = next(n for n in g.nodes if n.op == "qkv_proj")
        assert qkv.dims["i"] == 1  # one token through the projections
        assert _row_macs(g.lowered()) == g.macs()

    def test_first_decode_step_minimal_context(self):
        """The first decode step after a single prompt token (seq=1, no
        prefix) is the smallest legal KV context: a pure GEMV stack with a
        1-element score axis."""
        g = build_model_graph(get_config("gemma_7b"), seq=1, phase="decode",
                              lm_head=False)
        assert all(n.dims["i"] == 1 for n in g.nodes if n.kind == "gemm")
        scores = next(n for n in g.nodes if n.op == "attn_scores")
        assert scores.dims["m"] == 1 and scores.dims["n"] == 1
        ctx = next(n for n in g.nodes if n.op == "attn_context")
        assert ctx.dims["n"] == 1  # context of exactly one cached token

    def test_zero_context_decode_rejected(self):
        """KV-context=0 has no attention semantics: the seq >= 1 contract
        rejects it for both phases instead of lowering a 0-dim workload."""
        cfg = get_config("gemma_7b", reduced=True)
        for phase in PHASES:
            with pytest.raises(ValueError):
                build_model_graph(cfg, seq=0, phase=phase)

    def test_gqa_nondivisible_head_count_rejected(self):
        """GQA shares each KV head across an integer group of query heads —
        12 % 5 != 0 has no defined grouping and must be rejected up front,
        not lowered into a silently wrong KV projection."""
        with pytest.raises(ValueError, match="n_kv_heads"):
            build_model_graph(ModelConfig(n_heads=12, n_kv_heads=5), seq=8)
        with pytest.raises(ValueError, match="n_kv_heads"):
            build_model_graph(ModelConfig(n_heads=8, n_kv_heads=0), seq=8)
        # divisible grouping (MQA included) stays accepted
        for kv in (1, 2, 4, 12):
            g = build_model_graph(ModelConfig(n_heads=12, n_kv_heads=kv),
                                  seq=8)
            assert g.n_nodes
        # attention-free patterns don't consult the head counts at all
        g = build_model_graph(
            ModelConfig(layer_pattern=(BlockSpec(kind="rwkv"),),
                        n_heads=12, n_kv_heads=5), seq=8)
        assert g.n_nodes


class TestHandListParity:
    """The hand-maintained transformer tables that lived in
    benchmarks/nn_workloads.py before the frontend existed, pinned: their
    shapes must appear in the frontend-lowered graphs."""

    def test_gpt2_decode(self):
        from benchmarks.nn_workloads import NETWORKS
        d, f, H, prompt = 768, 3072, 12, 1000
        old = [dict(i=1, j=3 * d, k=d), dict(i=1, j=prompt, k=64),
               dict(i=1, j=64, k=prompt), dict(i=1, j=d, k=d),
               dict(i=1, j=f, k=d), dict(i=1, j=d, k=f)]
        got = _shapes(NETWORKS["GPT2"]())
        for dims in old:
            assert ("gemm", tuple(sorted(dims.items()))) in got, dims

    def test_llama7b_decode(self):
        from benchmarks.nn_workloads import NETWORKS
        d, f, prompt = 4096, 11008, 1000
        for bs, key in ((1, "LLaMA-7B-bs1"), (32, "LLaMA-7B-bs32")):
            old = [dict(i=bs, j=3 * d, k=d), dict(i=bs, j=prompt, k=128),
                   dict(i=bs, j=128, k=prompt), dict(i=bs, j=d, k=d),
                   dict(i=bs, j=f, k=d), dict(i=bs, j=d, k=f)]
            got = _shapes(NETWORKS[key]())
            for dims in old:
                assert ("gemm", tuple(sorted(dims.items()))) in got, (key,
                                                                      dims)

    def test_bert_prefill(self):
        from benchmarks.nn_workloads import NETWORKS
        d, f, seq = 768, 3072, 16
        old = [dict(i=seq, j=3 * d, k=d), dict(i=seq, j=seq, k=64),
               dict(i=seq, j=64, k=seq), dict(i=seq, j=d, k=d),
               dict(i=seq, j=f, k=d), dict(i=seq, j=d, k=f)]
        got = _shapes(NETWORKS["BERT"]())
        for dims in old:
            assert ("gemm", tuple(sorted(dims.items()))) in got, dims

    def test_gemma_prefill_attention_shapes(self):
        """The old dse.evaluate hand formulas for a dense GQA-free block,
        checked against the lowered Gemma graph (fallback per-GEMM
        attention lowering — the fused pair is pinned in TestGoldenDedup
        and TestFusedAttentionLowering)."""
        cfg = get_config("gemma_7b")
        seq, d, hd = 64, cfg.d_model, cfg.hd
        got = _shapes(lower_model(cfg, seq=seq, fused_attention=False))
        for dims in [
            dict(i=seq, j=(cfg.n_heads + 2 * cfg.n_kv_heads) * hd, k=d),
            dict(i=seq, j=seq, k=hd),           # scores
            dict(i=seq, j=hd, k=seq),           # context
            dict(i=seq, j=d, k=cfg.n_heads * hd),
            dict(i=seq, j=cfg.d_ff, k=d),
            dict(i=seq, j=d, k=cfg.d_ff),
            dict(i=seq, j=cfg.vocab_size, k=d),  # LM head
        ]:
            assert ("gemm", tuple(sorted(dims.items()))) in got, dims


class TestFusedAttentionLowering:
    """Fused attn_qk/attn_pv pair ↔ plain-GEMM fallback contract."""

    def test_unfuse_preserves_macs_and_ppu(self):
        from repro.frontend import unfuse_attention_rows
        for name in ARCH_IDS:
            rows = lower_model(get_config(name), seq=128)
            uf = unfuse_attention_rows(rows)
            assert _row_macs(rows) == _row_macs(uf), name
            nt = sum(r * n for _, _, r, n in rows)
            nt_uf = sum(r * n for _, _, r, n in uf)
            assert nt == pytest.approx(nt_uf), name
            assert not any(k in ("attn_qk", "attn_pv") for k, *_ in uf)

    def test_fused_matches_explicit_gemm_lowering(self):
        """unfuse(fused lowering) must equal the fused_attention=False
        lowering row-for-row — one contract, two entry points."""
        from repro.frontend import unfuse_attention_rows
        for name in ("gemma_7b", "whisper_base", "glm4_9b"):
            cfg = get_config(name)
            for phase in PHASES:
                fused = lower_model(cfg, seq=96, phase=phase)
                plain = lower_model(cfg, seq=96, phase=phase,
                                    fused_attention=False)
                assert _shapes(unfuse_attention_rows(fused)) == \
                    _shapes(plain), (name, phase)

    def test_fused_rows_are_workload_shaped(self):
        rows = lower_model(get_config("glm4_9b"), seq=64)
        qk = next(r for r in rows if r[0] == "attn_qk")
        _, dims, rep, nt = qk
        cfg = get_config("glm4_9b")
        assert dims["b"] == cfg.n_heads      # heads on the batched b dim
        assert dims["m"] == dims["n"] == 64  # score tile
        assert dims["d"] == cfg.hd
        assert nt == dims["b"] * dims["m"] * dims["n"]  # softmax elements


class TestZooAndResolve:
    def test_lower_zoo_phase_keys(self):
        zoo = lower_zoo(["gemma_7b"], seq=32, reduced=True)
        assert set(zoo) == {"gemma_7b"}
        zoo2 = lower_zoo(["gemma_7b"], seq=32, reduced=True,
                         phases=("prefill", "decode"))
        assert set(zoo2) == {"gemma_7b@prefill", "gemma_7b@decode"}
        with pytest.raises(ValueError):
            lower_zoo(["gemma_7b"], phases=("train",))

    def test_resolve_ids(self):
        assert resolve_ids("all") == list(ARCH_IDS)
        assert resolve_ids("gemma-7b,gemma_7b") == ["gemma_7b"]
        with pytest.raises(KeyError):
            resolve_ids("gpt5")

    def test_unknown_block_kind_rejected(self):
        cfg = ModelConfig(layer_pattern=(BlockSpec(kind="ssm2"),))
        with pytest.raises(ValueError):
            build_model_graph(cfg, seq=8)
