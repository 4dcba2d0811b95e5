"""The scoring engine's copy back to the host: a dispatch copies only the
two scores the host selection reads, and every other output is copied on
its first read, identical to a copy of all of them."""

import numpy as np
import pytest

from repro.core import perf_model_jax as pmj
from repro.core import workload as W
from repro.core.mapper import SpatialChoice
from repro.core.mapper_batch import (best_mappings, best_mappings_design,
                                     build_batch)
from repro.core.perf_model import HWConfig
from repro.obs import METRICS

needs_jax = pytest.mark.skipif(not pmj.jax_available(),
                               reason="jax runtime not importable")

MENU = [SpatialChoice(("i", "j"), (1, 1), "ij"),
        SpatialChoice(("k", "j"), (1, 1), "jk")]
QUERIES = [({"i": 16, "j": 16, "k": 16}, 0.0),
           ({"i": 56, "j": 7, "k": 130}, 4096.0)]
HWS = [HWConfig(n_fus=64, buffer_bytes=kb * 1024) for kb in (64, 512, 96)]
N_OUTPUTS = 8


@pytest.fixture(autouse=True)
def _fresh_metrics():
    METRICS.reset()
    yield
    METRICS.reset()


def _counters() -> dict:
    return METRICS.snapshot()["counters"]


def _kernel_args(design_axis: bool):
    wl = W.gemm()
    batch = build_batch(wl, [q[0] for q in QUERIES], MENU, HWS[0])
    true = np.array([[q[0][d] for d in wl.iter_dims]
                     for q in QUERIES])[batch.layer_id]
    ppu = np.array([q[1] for q in QUERIES])[batch.layer_id]
    T = len(wl.tensors)
    if design_axis:
        hw = HWS
        dn = np.full((len(HWS), T), 64, dtype=np.int64)
    else:
        hw = HWS[0]
        dn = np.full((batch.n_candidates, T), 64, dtype=np.int64)
    return (wl, hw, batch.loop_dim, batch.loop_size, batch.S, batch.n_fus,
            batch.fill, true, dn, ppu)


def _solve(design_axis: bool):
    wl = W.gemm()
    if design_axis:
        return best_mappings_design(wl, QUERIES, MENU, HWS)
    return best_mappings(wl, QUERIES, MENU, HWS[0], engine="jax")


@needs_jax
@pytest.mark.parametrize("design_axis", [False, True],
                         ids=["per_design", "design_axis"])
def test_a_solve_copies_only_the_two_scores(design_axis):
    _solve(design_axis)  # compiles
    METRICS.reset()
    _solve(design_axis)
    c = _counters()
    assert c["mapper_batch.jax_dispatches"] == 1
    padded = c["mapper_batch.jax_rows_padded"]
    assert padded >= c["mapper_batch.jax_candidates"] > 0
    assert c["mapper_batch.d2h_bytes"] == 16 * padded  # two float64 rows
    assert c["mapper_batch.outputs_deferred"] == N_OUTPUTS - 2
    assert "mapper_batch.outputs_fetched_late" not in c


@needs_jax
@pytest.mark.parametrize("design_axis", [False, True],
                         ids=["perf_kernel_jax", "perf_kernel_jax_design"])
def test_late_reads_equal_an_eager_copy(design_axis, monkeypatch):
    kernel = (pmj.perf_kernel_jax_design if design_axis
              else pmj.perf_kernel_jax)
    args = _kernel_args(design_axis)
    out = kernel(*args)
    with monkeypatch.context() as m:  # the same dispatch, all copied
        m.setattr(pmj, "EAGER_OUTPUTS", tuple(out))
        eager = kernel(*args)
    assert isinstance(eager, pmj.KernelOutputs)
    METRICS.reset()
    out = kernel(*args)
    copied = _counters()["mapper_batch.d2h_bytes"]
    assert len(out) == N_OUTPUTS and list(out) == list(eager)
    late = [k for k in out if k not in pmj.EAGER_OUTPUTS]
    assert len(late) == N_OUTPUTS - 2
    for n, key in enumerate(out, 1):
        for _ in range(2):  # a second read copies nothing
            v, want = out[key], eager[key]
            assert isinstance(v, np.ndarray)
            assert (v.dtype, v.shape) == (want.dtype, want.shape), key
            assert v.tobytes() == want.tobytes(), key
        c = _counters()
        fetched = sum(1 for k in list(out)[:n] if k in late)
        assert c.get("mapper_batch.outputs_fetched_late", 0) == fetched
    padded = _counters()["mapper_batch.jax_rows_padded"]
    row_bytes = sum(v.dtype.itemsize for v in eager.values())
    assert _counters()["mapper_batch.d2h_bytes"] == copied + \
        (row_bytes - 16) * padded
    shape = (len(HWS), args[3].shape[0]) if design_axis else \
        (args[3].shape[0],)
    assert all(v.shape == shape for v in out.values())
    assert dict(out.items()).keys() == eager.keys()


@needs_jax
def test_outputs_are_read_only():
    out = pmj.perf_kernel_jax(*_kernel_args(False))
    with pytest.raises(TypeError):
        out["macs"] = out["cycles"]
