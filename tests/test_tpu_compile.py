"""The device decision plane compiles for a TPU v5e without one attached.

The TPU compiler ships with jaxlib's TPU plugin and compiles for a
*described* topology, so these tests catch what only the chip's compiler
refuses (unsupported 64-bit rewrites, scoped-memory overflows, compiler
aborts) at no chip time.  Both fused programs are compiled at the fleet-tick
shape: 100 offerings x 1,000 pods x 32 decisions, padded to N=128, B=512,
RC=1025, D=32 with the 9-point prescan grid.

The topology is described inside a module fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import make_backend

N, B, RC, D, G, MAXR = 128, 512, 1025, 32, 9, 12


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _args(one_chip, *tail):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    market = (s((N,), jnp.int32), s((N,), jnp.int32), s((B,), jnp.int32),
              s((B,), jnp.int32), s((B,), jnp.int32), s((B,), bool),
              s((N,), jnp.float32), s((N,), jnp.float32))
    decisions = (s((D, N), jnp.int64), s((D, N), jnp.int64),
                 s((D, N), bool), s((D,), jnp.int64))
    return (market, *decisions, *(s(shape, dt) for shape, dt in tail),
            s((3,), jnp.int64))


def _compile(program, args):
    compiled = program.lower(*args).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 30
    return compiled


def test_prescan_compiles_for_v5e(one_chip, no_compile_cache):
    be = make_backend("jax:fused")
    _compile(be._prescan_program(N, B, RC, D, G),
             _args(one_chip, ((G,), jnp.int64)))


def test_golden_compiles_for_v5e(one_chip, no_compile_cache):
    be = make_backend("jax:fused")
    _compile(be._golden_program(N, B, RC, D, MAXR),
             _args(one_chip, ((D,), jnp.int64), ((D,), jnp.int64),
                   ((), jnp.int64)))
