"""Dry-run tooling tests: HLO parser (trip-exact costs), collective
accounting, roofline terms, mesh/cell plumbing — all on tiny meshes that fit
the single-CPU test environment (the 512-device configuration is exercised
by the launch scripts)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.hloparse import analyze_hlo
from repro.launch.roofline import Roofline


def _xla_cost(comp) -> dict:
    """Normalize Compiled.cost_analysis across JAX API drift: newer
    releases return a one-element list of the properties dict."""
    c = comp.cost_analysis()
    return c[0] if isinstance(c, (list, tuple)) else c


class TestHloParse:
    def test_matmul_matches_xla(self):
        M = N = K = 256
        comp = jax.jit(lambda a, b: a @ b).lower(
            jax.ShapeDtypeStruct((M, K), jnp.float32),
            jax.ShapeDtypeStruct((K, N), jnp.float32)).compile()
        h = analyze_hlo(comp.as_text())
        c = _xla_cost(comp)
        assert h.flops == pytest.approx(c["flops"])
        assert h.flops == 2 * M * N * K

    @pytest.mark.parametrize("trips", [3, 9, 28])
    def test_scan_trip_multiplication(self, trips):
        M = 128

        def body(c, w):
            return c @ w, None

        def f(x, ws):
            out, _ = jax.lax.scan(body, x, ws)
            return out

        comp = jax.jit(f).lower(
            jax.ShapeDtypeStruct((M, M), jnp.float32),
            jax.ShapeDtypeStruct((trips, M, M), jnp.float32)).compile()
        h = analyze_hlo(comp.as_text())
        assert h.flops == pytest.approx(2 * M ** 3 * trips)
        assert trips in h.trip_counts
        # XLA's own accounting misses the trips — the reason the parser
        # exists (rel tolerance: newer XLA adds a few scalar loop-counter
        # flops on top of the single-iteration matmul cost)
        assert _xla_cost(comp)["flops"] == pytest.approx(2 * M ** 3,
                                                         rel=1e-3)

    def test_nested_scan(self):
        M = 64

        def inner(c, w):
            return c @ w, None

        def outer(c, ws):
            c, _ = jax.lax.scan(inner, c, ws)
            return c, None

        def f(x, ws):
            out, _ = jax.lax.scan(outer, x, ws)
            return out

        comp = jax.jit(f).lower(
            jax.ShapeDtypeStruct((M, M), jnp.float32),
            jax.ShapeDtypeStruct((3, 4, M, M), jnp.float32)).compile()
        h = analyze_hlo(comp.as_text())
        assert h.flops == pytest.approx(2 * M ** 3 * 12)

    def test_dus_charged_as_update(self):
        # updating one row of a big buffer must not charge the whole buffer
        def f(buf, row, i):
            return jax.lax.dynamic_update_slice_in_dim(buf, row, i, axis=0)

        comp = jax.jit(f, donate_argnums=0).lower(
            jax.ShapeDtypeStruct((4096, 256), jnp.float32),
            jax.ShapeDtypeStruct((1, 256), jnp.float32),
            jax.ShapeDtypeStruct((), jnp.int32)).compile()
        h = analyze_hlo(comp.as_text())
        assert h.bytes < 4096 * 256 * 4  # far below a full-buffer pass

    def test_collective_parse_sharded_matmul(self):
        if jax.device_count() < 2:
            pytest.skip("needs >1 device")


class TestRoofline:
    def _mk(self, tc, tm, tx):
        return Roofline("a", "train_4k", 256,
                        flops_global=tc * 256 * 197e12,
                        bytes_global=tm * 256 * 819e9,
                        collective_bytes_global=tx * 256 * 50e9,
                        model_flops=tc * 256 * 197e12 * 0.8)

    def test_terms_roundtrip(self):
        r = self._mk(0.1, 0.2, 0.05)
        assert r.t_compute == pytest.approx(0.1)
        assert r.t_memory == pytest.approx(0.2)
        assert r.t_collective == pytest.approx(0.05)
        assert r.bottleneck == "memory"
        assert r.useful_flops_ratio == pytest.approx(0.8)

    def test_roofline_fraction(self):
        # compute-bound at 80% useful flops → 80% of roofline
        r = self._mk(0.2, 0.1, 0.1)
        assert r.roofline_fraction == pytest.approx(0.8)

    def test_model_flops_decode_counts_tokens_not_cache(self):
        from repro.configs import get_config
        from repro.launch.roofline import model_flops_for
        cfg = get_config("glm4_9b")
        f_dec = model_flops_for(cfg, dict(kind="decode", global_batch=128,
                                          seq_len=32768))
        f_tr = model_flops_for(cfg, dict(kind="train", global_batch=256,
                                         seq_len=4096))
        assert f_dec == pytest.approx(2.0 * cfg.n_active_params() * 128)
        assert f_tr > 1000 * f_dec


class TestCellsPlumbing:
    def test_skip_rules(self):
        from repro.launch.cells import cell_is_applicable
        ok, _ = cell_is_applicable("jamba_1_5_large_398b", "long_500k")
        assert ok
        ok, why = cell_is_applicable("gemma_7b", "long_500k")
        assert not ok and "full-attention" in why
        ok, _ = cell_is_applicable("rwkv6_7b", "long_500k")
        assert ok

    def test_all_cells_count(self):
        from repro.launch.cells import all_cells
        assert len(all_cells()) == 44

    def test_report_names_every_arch(self):
        from repro.configs import ARCH_IDS
        from repro.launch.report import SHORT
        assert set(SHORT) == set(ARCH_IDS)

    def test_mesh_function_shapes(self):
        # make_production_mesh is a function returning the assigned shapes;
        # constructing it needs 512 devices, so only inspect the source here
        import inspect
        from repro.launch import mesh
        src = inspect.getsource(mesh.make_production_mesh)
        assert "(2, 16, 16)" in src and "(16, 16)" in src
        assert '"pod", "data", "model"' in src


class TestEmit:
    def test_netlist_contains_structure(self):
        from repro.core import workload as W
        from repro.core.adg import generate_adg
        from repro.core.dag import codegen
        from repro.core.dataflow import build_dataflow
        from repro.core.emit import emit_netlist
        from repro.core.passes import run_backend

        wl = W.gemm()
        df = build_dataflow(wl, spatial=[("k", 4), ("j", 4)],
                            temporal=[("i", 2), ("j", 2), ("k", 2), ("i", 4)],
                            c=(1, 1), name="gemm-jk")
        adg = generate_adg([(wl, df)], name="tpu")
        dag = codegen(adg)
        run_backend(dag)
        text = emit_netlist(dag)
        assert "module tpu (" in text          # top with the df_sel fabric
        assert "module tpu_dp (" in text       # shared datapath
        assert "module tpu_ctrl_gemm_jk (" in text  # one ctrl per dataflow
        # 16 multiplier instances of the primitive library, named ports
        assert text.count("lego_mul #(.W") == 16
        assert "lego_addrgen" in text
        assert "endmodule" in text
        assert "pipe(" not in text             # old pseudo-netlist constructs
