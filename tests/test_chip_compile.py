"""Compile the main path's device programs for a described v5e chip.

Nothing runs: the TPU compiler builds each program for a chip that is
described, not attached, and raises what the chip's compiler would raise
(unaligned blocks, too much VMEM, unsupported ops).  Interpret mode cannot
show these.  Widths are the paper's: P from the three machines
(``sim/systems.py``) and K up to the last schedule bucket.

The topology is described inside a module fixture, never while a module is
imported: only one process at a time may load the TPU library, and every
test worker imports this file.
"""

from __future__ import annotations

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as Pspec

from repro.kernels.event_loop import event_finish
from repro.sim.backends.jax_batched import (_K_BUCKETS, _batched_events,
                                            _sharded_events, _sharded_route)
from repro.sim.workloads import GRID

#: the paper's machines: broadwell, cascadelake, epyc
PAPER_P = (20, 56, 128)
#: the backend's lane budget per (B, K) call (``_MAX_ELEMS``) sets B
WIDTHS = ((1024, 4096), (64, 65536), (16, _K_BUCKETS[-1]))


@pytest.fixture(scope="module")
def topo():
    """The described 2x2 v5e, with the persistent compile cache off: a
    compile for a described chip is written to it but cannot be read
    back."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernel(monkeypatch):
    """This process's backend is the CPU: steer the kernel's platform
    policy to the chip's branch (compiled, not interpreted)."""
    from repro.kernels import ops

    monkeypatch.setattr(ops, "_interpret", lambda: False)


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_args(sharding, B, K, P):
    f32 = lambda *shape: _spec(sharding, shape)
    i32 = lambda *shape: _spec(sharding, shape, jnp.int32)
    return (f32(B, K), f32(B, P), f32(B, P), f32(B), f32(B), i32(B, K),
            i32(B))


def _events_args(lanes, B, K, P, S=32, rep=None):
    """``_batched_events`` arguments: per-lane arrays on ``lanes``, the
    grid stack and the scalars on ``rep`` (default: ``lanes``)."""
    rep = lanes if rep is None else rep
    f32 = lambda *shape: _spec(lanes, shape)
    i32 = lambda *shape: _spec(lanes, shape, jnp.int32)
    scalar = lambda: _spec(rep, ())
    return (_spec(rep, (S, GRID + 1)), i32(B), f32(B), i32(B, K), i32(B, K),
            f32(B, K), i32(B), i32(B, K), _spec(lanes, (B,), jnp.uint32),
            f32(B), f32(B), f32(B, P), f32(B), scalar(), scalar(), scalar())


@pytest.mark.parametrize("B,K", WIDTHS, ids=lambda v: str(v))
@pytest.mark.parametrize("P", PAPER_P)
def test_event_kernel_compiles_for_v5e(one_chip, B, K, P):
    compiled = event_finish.lower(*_kernel_args(one_chip, B, K, P)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("core", ["while_loop", "pallas"])
def test_batched_events_compiles_for_v5e(one_chip, compiled_kernel, core):
    """The whole jitted campaign program (draws, prefix gather, core) at
    epyc's width and the 4096 bucket; only the Pallas one holds a kernel."""
    B, K, P = 256, 4096, 128
    compiled = _batched_events.lower(
        P, core, *_events_args(one_chip, B, K, P)).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == (core == "pallas")


def test_sharded_programs_compile_for_v5e_2x2(topo, compiled_kernel):
    """The lane-sharded campaign and fleet-route programs on a data=4 mesh
    of the four described chips: the kernel is there, no collective is."""
    mesh = Mesh(np.asarray(topo.devices, dtype=object).reshape(4, 1),
                ("data", "model"))
    lanes = NamedSharding(mesh, Pspec("data"))
    rep = NamedSharding(mesh, Pspec())
    B, K, P = 512, 4096, 56
    events = _sharded_events(mesh, P, "pallas").lower(
        *_events_args(lanes, B, K, P, rep=rep)).compile().as_text()
    A, K, R = 96, 1024, 8
    f32 = lambda *shape: _spec(lanes, shape)
    route = _sharded_route(mesh, R, "pallas").lower(
        f32(A, K), _spec(lanes, (A,), jnp.int32),
        _spec(lanes, (A, K), jnp.int32), f32(A, R),
        _spec(rep, ())).compile().as_text()
    for text in (events, route):
        assert "tpu_custom_call" in text
        assert not any(op in text for op in ("all-gather", "all-reduce",
                                             "all-to-all",
                                             "collective-permute"))
