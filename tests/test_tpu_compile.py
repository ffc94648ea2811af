"""Compile-only guards for the TPU kernels of the device training path.

Each test compiles a kernel for a described (not attached) TPU v5e chip
at the widths ``chip_smoke.py`` runs: d=100 features, a 192k-row shard,
hot sets of 4096 to 32768 ids, and query vectors of ``m_max`` rows. The
TPU compiler refuses layouts that interpret mode accepts, so these catch
a kernel that would fail on the chip without spending chip time. Nothing
runs; results are covered by the interpret-mode parity suites.
"""
import os
import re

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


#: the reddit cell's widths: rows six times as wide, 640 lanes padded
REDDIT = dict(d=602, n_per=60_000, m=22_528, n_hot=4096)


@pytest.mark.parametrize("views", [False, True])
def test_assemble_select_compiles_for_v5e_at_reddit_width(
        one_chip, no_persistent_cache, views):
    """The select pass at d=602: from the raw (n_per, 602) arrays, and
    from the lane-padded row views the epoch programs pass."""
    from repro.kernels.assemble.assemble import lane_width

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    d, n_per, m, n_hot = (REDDIT[k] for k in ("d", "n_per", "m", "n_hot"))
    w = lane_width(d) if views else d
    table, cache = ((sds((n_per, 1, w)), sds((n_hot, 1, w))) if views
                    else (sds((n_per, w)), sds((n_hot, w))))
    _compile(lambda t, b, c, cf, q, p: assemble(t, b, c, cf, q, p),
             table, sds((), jnp.int32), sds((n_hot,), jnp.int32), cache,
             sds((m,), jnp.int32), sds((m, w)))


def _while_bodies(text: str) -> dict:
    """-> {computation name: its instruction lines} for every while-loop
    body of an HLO module's text."""
    names = set(re.findall(r"body=%?([\w.\-]+)", text))
    bodies, current = {}, None
    for line in text.splitlines():
        head = re.match(r"%?([\w.\-]+) \(", line)
        if head and not line.startswith(" "):
            current = head.group(1) if head.group(1) in names else None
            if current:
                bodies[current] = []
        elif current and " = " in line:
            bodies[current].append(line.strip())
    return bodies


#: ops that only pass a loop-invariant buffer through the loop
_PLUMBING = {"parameter", "get-tuple-element", "tuple", "bitcast"}


@pytest.mark.parametrize("cell", ["products", "reddit"])
def test_assembly_keeps_table_work_out_of_the_step_loop(
        one_chip, no_persistent_cache, cell):
    """The epoch programs' assembly inside a ``lax.scan`` over steps, with
    a loop-invariant table and hot set turned into lane-padded row views
    before the loop (``_sources``), as the epoch programs do, and pulled
    rows as wide as the pull makes them: no op of the loop body yields an
    array with the table's row count, so no step pads, relayouts or
    copies the table."""
    from repro.dist.gnn_step import _assemble, _sources
    from repro.kernels.assemble.assemble import lane_width

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    d, n_per, m, n_hot = ((D, N_PER, 40_960, 4096) if cell == "products"
                          else (REDDIT[k] for k in
                                ("d", "n_per", "m", "n_hot")))
    steps = 2

    def program(table, base, cids, cfeats, queries, pulled):
        tsrc, csrc, _ = _sources(table, cfeats, "fused")

        def step(acc, x):
            q, p = x
            feats = _assemble(tsrc, base, cids, csrc, q, p, "fused", False,
                              d)
            return acc + feats.sum(), None
        return jax.lax.scan(step, 0.0, (queries, pulled))[0]

    text = jax.jit(program).lower(
        sds((n_per, d)), sds((), jnp.int32), sds((n_hot,), jnp.int32),
        sds((n_hot, d)), sds((steps, m), jnp.int32),
        sds((steps, m, lane_width(d)))).compile().as_text()
    bodies = _while_bodies(text)
    body_ops = [op for ops in bodies.values() for op in ops]
    assert any("assemble_select" in op.split(" = ")[0] for op in body_ops)
    table_sized = []
    for op in body_ops:
        found = re.match(r"(?:ROOT )?%?\S+ = (.*?) ([a-z][\w\-]*)\(", op)
        if (found and found.group(2) not in _PLUMBING
                and f"[{n_per}," in found.group(1)):
            table_sized.append(op[:160])
    assert not table_sized, table_sized


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
    from repro.dist.gnn_step import _assemble

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    m, n_hot = 40_960, 4096
    text = jax.jit(
        lambda t, b, c, cf, q, p: _assemble(t, b, c, cf, q, p, "fused",
                                            False, D)
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
