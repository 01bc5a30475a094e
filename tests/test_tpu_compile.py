"""Ahead-of-time compiles of the ``dot_seen`` kernel for a TPU v5e.

Interpret mode has no VMEM limit, so the CPU tests cannot show that the
kernel fits the chip.  These tests hand the TPU compiler a *described*
v5e (no chip attached) and compile the kernel at the shapes the served
path gives it: every actor and run bucket a fragmented tombstone can
reach, up to the 65,536-run bucket that a 500k-element set with 10% of
its elements removed lands in.  Nothing runs; a shape that passes here
compiles on the chip.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and only the test worker
given this file does.
"""
import os

import pytest
import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels.dot_seen.kernel import dot_seen_pallas

RUNS = [2048, 4096, 8192, 65536]
ACTORS = [1, 3, 8]
DOTS = [512, 1024]


@pytest.fixture(scope="module")
def no_persistent_cache():
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep these compiles out of it.
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(no_persistent_cache):
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("n_dots", DOTS)
@pytest.mark.parametrize("n_actors", ACTORS)
@pytest.mark.parametrize("n_runs", RUNS)
def test_dot_seen_compiles_for_v5e(one_chip, n_runs, n_actors, n_dots):
    def spec(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    compiled = dot_seen_pallas.lower(
        spec(n_actors, n_runs), spec(n_actors, n_runs),
        spec(n_dots), spec(n_dots), interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
