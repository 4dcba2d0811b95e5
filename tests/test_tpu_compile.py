"""The JAX scoring engine's kernels compile for a described TPU v5e.

The TPU compiler is installed without a chip, and it refuses what XLA:CPU
accepts: a 64-bit ``dot_general`` cannot be rewritten into the 32-bit ops
the chip has.  These tests AOT-compile the design-axis kernel of
:mod:`repro.core.perf_model_jax` (the program a ``--design-batch`` sweep
dispatches) for one chip of a described ``v5e:2x2``, at the shapes the
``--space large`` sweep over the default zoo issues, one test per workload
kind that sweep reaches.  A compile that passes is not a chip run: nothing
here executes.

This is the only test file that describes the chip.  The topology is
described inside a fixture, never at import: only one process at a time
may load the TPU library, and every test worker imports every test file.
"""

import os
import re

import pytest

from repro.core import workload as W
from repro.core.perf_model_jax import design_kernel_program, jax_available

# (designs, padded candidates, padded loop slots) of the large sweep's
# design-batched dispatch per workload kind: 28 tiles of 32 designs, the
# candidate axis padded to the widest group's batch
LARGE_SWEEP_SHAPES = {
    "gemm": (32, 65536, 4),
    "attention_qk": (32, 8192, 8),
    "attention_pv": (32, 8192, 8),
}


@pytest.fixture(scope="module")
def one_chip():
    if not jax_available():
        pytest.skip("jax runtime not importable")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one: keep the cache off around it."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.mark.parametrize("kind", sorted(LARGE_SWEEP_SHAPES))
def test_design_kernel_compiles_for_v5e(kind, one_chip, no_persistent_cache):
    import jax

    wl = {"gemm": W.gemm, "attention_qk": W.attention_qk,
          "attention_pv": W.attention_pv}[kind]()
    D, C, L = LARGE_SWEEP_SHAPES[kind]
    jitted, shapes = design_kernel_program(jax, wl, D, C, L,
                                           sharding=one_chip)
    with jax.enable_x64(True):
        lowered = jitted.lower(*shapes)
        compiled = lowered.compile()
    hlo = lowered.as_text(dialect="hlo")
    # the einsum the TPU compiler refused must not come back in any form
    wide_dots = re.findall(r"= [suf]64\[[^\]]*\]\S* dot\(", hlo)
    assert not wide_dots, wide_dots
    # the program fits one chip's 16 GB of HBM with room to spare
    mem = compiled.memory_analysis()
    total = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
             + mem.output_size_in_bytes)
    assert total < 4 << 30, total
