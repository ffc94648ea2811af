"""Compile-only guards for the TPU kernels of the device training path.

Each test compiles a kernel for a described (not attached) TPU v5e chip
at the widths ``chip_smoke.py`` runs: d=100 features, a 192k-row shard,
hot sets of 4096 to 32768 ids, and query vectors of ``m_max`` rows. The
TPU compiler refuses layouts that interpret mode accepts, so these catch
a kernel that would fail on the chip without spending chip time. Nothing
runs; results are covered by the interpret-mode parity suites.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.assemble.assemble import assemble
from repro.kernels.cache_lookup.cache_lookup import search

D, N_PER = 100, 192_000

#: (m query rows, n_hot cache ids): the one-chip smoke's m_max, a
#: larger m with the biggest hot set, and an awkward small case
SHAPES = [(40_259, 4096), (150_016, 32_768), (1003, 37)]


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the program"


@pytest.mark.parametrize("m,n_hot", SHAPES)
def test_search_compiles_for_v5e(one_chip, no_persistent_cache, m, n_hot):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    _compile(lambda c, q: search(c, q),
             sds((n_hot,), jnp.int32), sds((m,), jnp.int32))


@pytest.mark.parametrize("m,n_hot", SHAPES)
def test_assemble_select_compiles_for_v5e(one_chip, no_persistent_cache,
                                          m, n_hot):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    _compile(lambda t, b, c, cf, q, p: assemble(t, b, c, cf, q, p),
             sds((N_PER, D), jnp.float32), sds((), jnp.int32),
             sds((n_hot,), jnp.int32), sds((n_hot, D), jnp.float32),
             sds((m,), jnp.int32), sds((m, D), jnp.float32))
