"""The replay programs compile for a TPU v5e with the Pallas kernel inside.

Each test compiles one jitted program for a described (not attached)
``v5e:2x2`` topology and checks that the fused ``rank_step`` kernel is in
the compiled HLO (``tpu_custom_call``).  This catches what interpret mode
cannot: SMEM/VMEM overuse, unaligned blocks, programs that do not fit.
Nothing runs, so nothing here says anything about results or times.

Only this file loads the TPU compiler.  The topology is described in a
module-scoped fixture, never at import, so every test worker collects the
same tests and only the worker that runs this file takes libtpu's lock.
"""
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import make_policy
from repro.core.policy import Request
from repro.core.simulator import _replay_batched, _replay_chunk, _replay_single
from repro.fleet import FleetTier
from repro.fleet.fleet import _replay_fleet_sharded, _replay_fleet_single
from repro.tier import CacheTier
from repro.tier.tier import _replay_tier_single

T = 64          # the scan length does not change what is compiled per step


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler or topology here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A described-device compile is written to the persistent cache but
    cannot be read back without the chip: keep the cache off here."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _requests(shape, sharding):
    def s(dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    return Request(key=s(jnp.int32), size=s(jnp.int32), cost=s(jnp.float32))


def _assert_kernel(lowered):
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("policy", ["climb", "adaptiveclimb",
                                    "dynamicadaptiveclimb"])
def test_scanned_rank_policy_at_node_capacity(one_chip, policy):
    _assert_kernel(_replay_single.lower(
        make_policy(policy), _requests((T,), one_chip), 65536, False, False,
        "compiled"))


@pytest.mark.parametrize("K,lanes", [(65536, 256), (128, 1024),
                                     (128, 4096)])
def test_batched_dac(one_chip, K, lanes):
    """1,024 and 4,096 lanes overflowed SMEM while the kernel kept its
    scalar I/O in whole ``[B, n]`` SMEM arrays."""
    c = _assert_kernel(_replay_batched.lower(
        make_policy("dac"), _requests((lanes, T), one_chip), K, False,
        False, "compiled", None, "data"))
    # lane state + scan temporaries fit the 16 GB chip
    assert c.memory_analysis().temp_size_in_bytes < 8 << 30


def test_batched_dac_over_four_chips(topo):
    mesh = Mesh(np.array(topo.devices), ("data",))
    c = _assert_kernel(_replay_batched.lower(
        make_policy("dac"),
        _requests((256, T), NamedSharding(mesh, P("data", None))), 65536,
        False, False, "compiled", mesh, "data"))
    assert "all-gather" not in c.as_text()     # lanes stay on their chip


def test_admitted_dac(one_chip):
    _assert_kernel(_replay_batched.lower(
        make_policy("admit(dac)"), _requests((64, T), one_chip), 4096,
        False, False, "compiled", None, "data"))


def test_tier_step(one_chip):
    tier = CacheTier("dac", n_tenants=16, budget=16 * 1024)
    _assert_kernel(_replay_tier_single.lower(
        tier, _requests((T, 16), one_chip), False, "compiled"))


def test_fleet_step(one_chip):
    tier = FleetTier("dac", n_lanes=64, budget=64 * 1024, arbiter="auction")
    _assert_kernel(_replay_fleet_single.lower(
        tier, _requests((T, 64), one_chip), True, "compiled"))


def test_fleet_step_over_four_chips(topo):
    mesh = Mesh(np.array(topo.devices), ("data",))
    tier = FleetTier("dac", n_lanes=64, budget=64 * 1024, arbiter="auction")
    c = _assert_kernel(_replay_fleet_sharded.lower(
        tier, _requests((T, 64), NamedSharding(mesh, P(None, "data"))),
        mesh, "data", 256, True, "compiled"))
    assert "all-reduce" in c.as_text()         # the psum budget re-deal


@pytest.mark.parametrize("lanes", [None, 5, 1024])
def test_stream_chunk_dense_kernel(one_chip, lanes):
    """The stream chunk program of the ``dac_paper_s`` cells (DAC at
    K = 1,048: rows of 4,224 ranks) on the dense kernel: one lane, fewer
    lanes than a block, and 1,024 lanes, whose carried ``[1024, 4224]``
    state the kernel updates in place, with no copy of it in the scan."""
    pol = make_policy("dac(eps=0.5,growth=4,k_min=2)")
    lead = () if lanes is None else (lanes,)
    state = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(lead + x.shape, x.dtype,
                                       sharding=one_chip), pol.init(1048))
    text = _assert_kernel(_replay_chunk.lower(
        pol, state, _requests(lead + (T,), one_chip), "compiled",
        False)).as_text()
    if lanes == 1024:
        assert "output_to_operand_aliasing={{0}: (1, {})}" in text
        assert not re.search(
            r"= \(?s32\[1024,4224\]\S* copy(-start|-done)?\(", text)
