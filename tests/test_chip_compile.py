"""The query path's Pallas kernels compile for a TPU v5e chip at real size.

Nothing runs here: the TPU compiler that ships with jax compiles for a chip
that is described (``v5e:2x2``), not attached, and refuses what the chip's
compiler would refuse — a block that breaks the (8, 128) tiling, more VMEM
than a kernel may use, more HBM than the chip has. Interpret mode, which
every other kernel test uses off-TPU, checks none of that.

Sizes are the deployment's: a 32M-triple scan (a full shard of a ~128M
store), a 4M-row shard for the batched and fused scans, a 1M-key sorted
predicate view. Every argument is a ``ShapeDtypeStruct`` on one described
device; the topology is described inside a fixture, never at import time,
so every pytest-xdist worker collects the same tests.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.join_probe import probe_sorted_many, scan_probe
from repro.kernels.triple_scan import triple_scan, triple_scan_many

#: HBM scratch a scan may take beyond its arguments and outputs; the former
#: ``[T, 3]`` layout took 512 B per triple (8 GiB at T=2^24)
MAX_TEMP_BYTES = 64 << 20

SHARD_ROWS = 1 << 22
VIEW_KEYS = 1 << 20


@pytest.fixture(scope="module")
def one_chip():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")    # else libtpu logs to /tmp
        from jax.experimental import topologies
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:                  # no TPU compiler installed
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compile_for(one_chip):
    """``compile_for(jitted, *shapes, **static)`` -> compiled executable.

    ``shapes`` are int32 shapes (or tuples of them, for the column
    triple); the persistent cache is off around the compile — an entry
    written for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    def sds(shape):
        if shape and isinstance(shape[0], tuple):
            return tuple(sds(s) for s in shape)
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    def run(fn, *shapes, **static):
        compiled = fn.lower(*(sds(s) for s in shapes), **static).compile()
        assert "tpu_custom_call" in compiled.as_text()
        return compiled

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield run
    finally:
        jax.config.update("jax_enable_compilation_cache", before)
        cc.reset_cache()


def _columns(T: int) -> tuple:
    return ((T,), (T,), (T,))


def _temp(compiled) -> int:
    return compiled.memory_analysis().temp_size_in_bytes


def test_triple_scan_compiles_at_32m_triples(compile_for):
    T = 1 << 25
    c = compile_for(triple_scan, _columns(T), (3,), bt=2048)
    assert _temp(c) <= MAX_TEMP_BYTES
    # arguments are the three columns and the pattern: 12 B per triple
    assert c.memory_analysis().argument_size_in_bytes < 13 * T


@pytest.mark.parametrize("Q", [1, 5, 8, 13])
def test_triple_scan_many_compiles_for_any_pattern_count(compile_for, Q):
    c = compile_for(triple_scan_many, _columns(SHARD_ROWS), (Q, 3), bt=2048)
    assert _temp(c) <= MAX_TEMP_BYTES


def test_probe_sorted_many_compiles_at_1m_keys(compile_for):
    c = compile_for(probe_sorted_many, (VIEW_KEYS,), (1, VIEW_KEYS),
                    bk=2048, bp=128)
    assert _temp(c) <= MAX_TEMP_BYTES


def test_scan_probe_compiles_at_shard_size(compile_for):
    c = compile_for(scan_probe, _columns(SHARD_ROWS), (3,), (VIEW_KEYS,),
                    col=0, bt=2048, bk=2048)
    assert _temp(c) <= MAX_TEMP_BYTES
