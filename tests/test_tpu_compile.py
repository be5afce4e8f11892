"""Compile the main-path Pallas kernels for a described TPU v5e, no chip needed.

Interpret mode (every other kernel test) runs the kernel body in Python and
accepts block shapes that Mosaic refuses.  These tests lower and compile
``fedavg_reduce`` and ``vaoi_distance`` for a ``v5e:2x2`` topology at the
shapes the simulator hands them: the compacted ``cap`` slab (K=10), the
paper's dense and old-carrier reductions (K=N=100), and fleet-sized client
axes (1024, 4096) at the paper CNN's width (P≈845k parameters, F=10).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and the test workers all import
this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.fedavg_reduce import fedavg_reduce
from repro.kernels.vaoi_distance import vaoi_distance

P_PAPER = 845_000  # ~ parameter count of cifar_cnn.CONFIG
F_PAPER = 10  # feature_dim of the CNN backend (its logits)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _shape(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _row_major(shape, sharding):
    """A (K, P) message matrix as the simulator builds it inside its own
    program (``flatten_clients``), where the kernel's operand constraint
    makes it row-major.  Left to choose a standalone parameter's layout, the
    TPU compiler may pick column-major for K >= 512 and copy the whole
    matrix into the kernel's layout, which at K=4096 no longer fits."""
    from jax.experimental.layout import Format, Layout

    return _shape(shape, Format(Layout((0, 1)), sharding))


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the Mosaic kernel is there
    return compiled


@pytest.mark.parametrize("k", [10, 100, 4096])
def test_fedavg_reduce_compiles_for_v5e(k, one_chip, no_compile_cache):
    _compile(
        fedavg_reduce,
        _row_major((k, P_PAPER), one_chip),
        _shape((k,), one_chip),
    )


@pytest.mark.parametrize("n", [100, 1024, 4096])
def test_vaoi_distance_compiles_for_v5e(n, one_chip, no_compile_cache):
    _compile(
        lambda v, h, age, q: vaoi_distance(v, h, age, q, 0.5),
        _shape((n, F_PAPER), one_chip),
        _shape((n, F_PAPER), one_chip),
        _shape((n,), one_chip),
        _shape((n,), one_chip),
    )
