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


def _metric_pattern(name: str) -> str:
    """The op-name regex of the benchmark's reader ``name``."""
    import importlib.util

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chipbench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("metric", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.PATTERN


def test_assemble_kernels_keep_their_names_for_v5e(one_chip,
                                                   no_persistent_cache):
    """The epoch programs' assembly step at the products cell's widths
    (m_max 40,960, a 4096-row hot set): its two Pallas calls are named
    ``assemble_search`` and ``assemble_select``, so the trace reader
    ``assemble.ms_per_step`` matches exactly them, and both carry the
    ``assemble`` scope."""
    import re

    from repro.dist.gnn_step import _assemble

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    m, n_hot = 40_960, 4096
    text = jax.jit(
        lambda t, b, c, cf, q, p: _assemble(t, b, c, cf, q, p, "fused",
                                            False)
    ).lower(sds((N_PER, D), jnp.float32), sds((), jnp.int32),
            sds((n_hot,), jnp.int32), sds((n_hot, D), jnp.float32),
            sds((m,), jnp.int32), sds((m, D), jnp.float32)
            ).compile().as_text()
    ops = [line.strip().removeprefix("ROOT ") for line in text.splitlines()
           if " = " in line]
    kernels = [op for op in ops if 'custom_call_target="tpu_custom_call"'
               in op]
    matched = [op for op in ops
               if re.search(_metric_pattern("assemble.ms_per_step"), op)]
    assert matched == kernels
    assert sorted(op.split(" = ")[0].lstrip("%").split(".")[0]
                  for op in matched) == ["assemble_search",
                                         "assemble_select"]
    for op in matched:
        op_name = re.search(r'op_name="([^"]*)"', op).group(1)
        assert "assemble" in op_name.split("/"), op_name
