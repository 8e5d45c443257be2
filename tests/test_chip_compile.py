"""Compile the main path's device programs for a described TPU v5e.

No chip is attached: ``topologies.get_topology_desc`` describes one and the
TPU compiler, which is installed, compiles for it.  That catches what
interpret mode cannot (lowering refusals, tiling, VMEM and HBM limits) at the
real widths: 62,710 genes, ELL K = 3,456 (the ~3.4k longest row of a
Tahoe-like atlas, rounded up to 128 lanes), and the Fig. 5 heads.

The topology is described only inside the module fixture, never at import:
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from benchmarks.bench_fig5_classification import TASKS, _train_step
from repro.kernels.csr_to_dense import ell_to_dense

N_GENES = 62_710
ELL_K = 3_456
V5E_HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler or library lock held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # entries compiled for a described chip cannot be read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("rows", [64, 1024])
def test_ell_to_dense_compiles_for_v5e(one_chip, rows):
    vals = _sds((rows, ELL_K), jnp.float32, one_chip)
    cols = _sds((rows, ELL_K), jnp.int32, one_chip)
    compiled = ell_to_dense.lower(vals, cols, n_cols=N_GENES).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().output_size_in_bytes >= rows * N_GENES * 4


def test_fig5_step_compiles_for_v5e_and_fits_hbm(one_chip):
    assert sum(TASKS.values()) == 461
    heads = {t: {"w": _sds((N_GENES, c), jnp.float32, one_chip),
                 "b": _sds((c,), jnp.float32, one_chip)} for t, c in TASKS.items()}
    opt = {"m": heads, "v": heads, "count": _sds((), jnp.int32, one_chip)}
    x = _sds((64, N_GENES), jnp.float32, one_chip)
    ys = {t: _sds((64,), jnp.int32, one_chip) for t in TASKS}
    compiled = _train_step.lower(heads, opt, x, ys).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < total < V5E_HBM_BYTES
