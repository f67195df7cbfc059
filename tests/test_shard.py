"""Mesh-sharded campaign lanes: helper semantics and sharded-vs-single-
device bit-equality.

The multi-device equality checks need jax to boot with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``, which only takes
effect at process start — so they run ``tests/_shard_subproc.py`` in a
subprocess (one process covers lockstep Q-tables/traces, the portfolio
``run_batch`` fan-out and ``what_if_routes``/``what_if_wave`` pricing,
including lane counts that do not divide the mesh extent).  Everything
single-device (mesh construction, lane padding, env resolution, async
double-buffered dispatch) is tested in-process.
"""

from __future__ import annotations

import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.launch.mesh import (campaign_mesh, lane_count, lane_spec,
                               make_host_mesh, pad_lanes)
from repro.sim import CellSpec, ReplayBatch, sweep_portfolio
from repro.sim.backends.jax_batched import (JaxBatchedBackend,
                                            resolve_async_dispatch,
                                            resolve_data_parallel,
                                            resolve_event_core)

REPO = os.path.join(os.path.dirname(__file__), "..")


# ---------------------------------------------------------------------------
# mesh helpers
# ---------------------------------------------------------------------------

def test_make_host_mesh_rejects_non_divisible_model_parallel():
    with pytest.raises(ValueError, match="not divisible"):
        make_host_mesh(model_parallel=3)
    with pytest.raises(ValueError, match="model_parallel"):
        make_host_mesh(model_parallel=0)


def test_make_host_mesh_data_parallel_clamp():
    # requesting more lanes than devices clamps to what exists; requesting
    # fewer uses exactly that many
    m = make_host_mesh(data_parallel=64)
    assert m.shape["data"] <= 64
    m1 = make_host_mesh(data_parallel=1)
    assert m1.shape["data"] == 1 and m1.shape["model"] == 1
    with pytest.raises(ValueError, match="data_parallel"):
        make_host_mesh(data_parallel=0)


def test_campaign_mesh_is_data_only():
    m = campaign_mesh()
    assert m.axis_names == ("data", "model")
    assert m.shape["model"] == 1


def test_lane_padding_helpers():
    m = campaign_mesh(data_parallel=1)
    assert lane_count(m) == 1
    assert pad_lanes(7, m) == 7
    assert tuple(lane_spec(m)) == ("data",)


def test_resolve_data_parallel(monkeypatch):
    monkeypatch.delenv("REPRO_DATA_PARALLEL", raising=False)
    import jax
    assert resolve_data_parallel() == len(jax.devices())
    assert resolve_data_parallel(1) == 1
    assert resolve_data_parallel(10**6) == len(jax.devices())  # clamp
    monkeypatch.setenv("REPRO_DATA_PARALLEL", "1")
    assert resolve_data_parallel() == 1
    with pytest.raises(ValueError):
        resolve_data_parallel(0)


def test_resolve_async_dispatch(monkeypatch):
    monkeypatch.delenv("REPRO_ASYNC_DISPATCH", raising=False)
    assert resolve_async_dispatch() is True
    assert resolve_async_dispatch(False) is False
    monkeypatch.setenv("REPRO_ASYNC_DISPATCH", "0")
    assert resolve_async_dispatch() is False


def test_resolve_event_core_auto():
    # the platform default: the while-loop reference on CPU, the compiled
    # kernel on TPU; "auto" is accepted explicitly and via the env default.
    # No fallback: an explicit core always resolves to itself
    import jax
    expect = "pallas" if jax.default_backend() == "tpu" else "while_loop"
    assert resolve_event_core("auto") == expect
    assert resolve_event_core("pallas") == "pallas"
    with pytest.raises(ValueError, match="auto"):
        resolve_event_core("triton")


# ---------------------------------------------------------------------------
# async double-buffered dispatch (single device)
# ---------------------------------------------------------------------------

def test_async_dispatch_bit_equal_single_device():
    sync = JaxBatchedBackend(data_parallel=1, async_dispatch=False)
    asyn = JaxBatchedBackend(data_parallel=1, async_dispatch=True)
    s_ref = sweep_portfolio("sphynx", "epyc", T=2, reps=2, backend=sync)
    s_asy = sweep_portfolio("sphynx", "epyc", T=2, reps=2, backend=asyn)
    for key in s_ref.runs:
        assert (s_ref.runs[key].times == s_asy.runs[key].times).all()
        assert (s_ref.runs[key].libs == s_asy.runs[key].libs).all()


def test_async_dispatch_bit_equal_lockstep():
    lanes = [CellSpec("tc", "epyc", "QLearn", "default", "LT"),
             CellSpec("tc", "epyc", "ExpertSel", "expChunk", None)]
    runs = {}
    for flag in (False, True):
        bk = JaxBatchedBackend(data_parallel=1, async_dispatch=flag)
        runs[flag] = ReplayBatch(lanes, T=3, seed=0, backend=bk).run()
    for a, b in zip(runs[False], runs[True]):
        assert a.total == b.total
        assert a.history == b.history


# ---------------------------------------------------------------------------
# multi-device bit-equality (subprocess: forced 8 virtual CPU devices)
# ---------------------------------------------------------------------------

def test_sharded_bit_equality_8_virtual_devices():
    """Lockstep Q-tables/traces, portfolio sweeps and what-if prices must be
    identical on a (data: 8) mesh, a non-divisible (data: 3) mesh and the
    single-device path — see ``tests/_shard_subproc.py``."""
    # the child forces virtual host devices: pin it to the CPU so it never
    # reaches for an accelerator this process may hold
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    env.pop("REPRO_DATA_PARALLEL", None)
    env.pop("REPRO_ASYNC_DISPATCH", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(os.path.dirname(__file__),
                                      "_shard_subproc.py")],
        env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "SHARD-OK" in proc.stdout, proc.stdout + proc.stderr
