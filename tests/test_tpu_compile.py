"""Ahead-of-time compiles of the simulator's chip path for a described TPU v5e.

No chip is needed: the TPU compiler compiles for a topology that is
described, not attached, and refuses what the chip would refuse (unaligned
tiles, scalar VMEM stores, int64 kernel operands).  Covers the Pallas
serve-round kernel at a deployment window (65,536 rows x 3 hops), its ops
wrapper, and the engine fixpoint that dispatches to it.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and the fixture skips where it
cannot.  The persistent compilation cache is off around these compiles (an
entry written for a described chip cannot be read back without one).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

import repro.core  # noqa: F401  (x64: the engine's int64 picoseconds)
from repro.core import engine
from repro.kernels.serve_round.kernel import serve_scan
from repro.kernels.serve_round.ops import serve_round

K = 196_608  # one 65,536-row x 3-hop stream window


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _sds(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def test_serve_scan_compiles_for_v5e(one_chip):
    x = _sds(one_chip, (K,), jnp.int32)
    compiled = serve_scan.lower(x, x, x, x, x, x).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_serve_round_pallas_compiles_for_v5e(one_chip):
    dt = (jnp.int32, bool, bool, jnp.int64, jnp.int8, jnp.int32, jnp.int64,
          jnp.int64, jnp.int64, jnp.int64, jnp.int64, jnp.int64, jnp.int8,
          jnp.int32, jnp.int64)
    args = [_sds(one_chip, (K,), d) for d in dt]
    compiled = serve_round.lower(*args, impl="pallas").compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_engine_fixpoint_pallas_compiles_for_v5e(one_chip):
    n, h, c = 1024, 3, 5
    i32 = lambda s: _sds(one_chip, s, jnp.int32)  # noqa: E731
    i64 = lambda s: _sds(one_chip, s, jnp.int64)  # noqa: E731
    hops = engine.Hops(i32((n, h)), i64((n, h)), _sds(one_chip, (n, h),
                                                       jnp.int8),
                       i32((n, h)), i64((n, h)), _sds(one_chip, (n, h), bool),
                       _sds(one_chip, (n, h), bool))
    ch = engine.Channels(i64((c,)), i64((c,)), i64((c,)), i64((c,)))
    carry = engine.StreamCarry(i64((c,)), _sds(one_chip, (c,), jnp.int8),
                               i32((c,)), i64((c,)))
    compiled = engine._simulate_fixpoint.lower(
        hops, ch, i64((n,)), i64((n, h)), i64(()), carry, "pallas").compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert np.isfinite(compiled.memory_analysis().temp_size_in_bytes)
