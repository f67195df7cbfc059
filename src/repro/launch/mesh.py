"""Host meshes and the campaign's lane-sharding rule.

Meshes are built by FUNCTIONS (not module-level constants) so importing
this module never touches jax device state.  The data axis, the lane spec
and the lane padding live here together: every batched lane dimension
shards over ``data`` and nothing else.
"""

from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P


def make_host_mesh(model_parallel: int = 1,
                   data_parallel: Optional[int] = None):
    """Whatever fits the local devices — used by tests, examples and the
    campaign lane sharding.

    ``data_parallel`` clamps the data axis so callers can request fewer
    lanes than the host exposes (a campaign slice smaller than the device
    count, or a controlled scaling sweep over 1/2/4/8 devices); the mesh
    then covers the first ``data_parallel * model_parallel`` devices.
    """
    devices = jax.devices()
    n = len(devices)
    if model_parallel < 1:
        raise ValueError(f"model_parallel must be >= 1, got {model_parallel}")
    if n % model_parallel != 0:
        raise ValueError(
            f"device count {n} is not divisible by "
            f"model_parallel={model_parallel}; pick a divisor of {n}")
    dp = n // model_parallel
    if data_parallel is not None:
        if data_parallel < 1:
            raise ValueError(
                f"data_parallel must be >= 1, got {data_parallel}")
        dp = min(dp, data_parallel)
    use = devices[: dp * model_parallel]
    return jax.sharding.Mesh(
        np.asarray(use, dtype=object).reshape(dp, model_parallel),
        ("data", "model"))


def campaign_mesh(data_parallel: Optional[int] = None):
    """1-D-data host mesh for campaign lane sharding: every batched lane
    dimension (``run_batch`` / ``run_lockstep`` instances, what-if candidate
    rows) shards over ``data``; ``model`` stays 1 — the event cores are
    per-lane sequential and never split a lane across devices."""
    return make_host_mesh(model_parallel=1, data_parallel=data_parallel)


def lane_spec(mesh: Mesh) -> P:
    """Leading-axis lane sharding for campaign batches: instances / what-if
    candidate rows shard over ``data``, everything trailing (schedule
    slots, PEs) stays local to the lane's device."""
    return P("data")


def lane_count(mesh: Mesh) -> int:
    """Extent of the ``data`` axis — the number of lane shards."""
    return mesh.shape["data"]


def pad_lanes(n: int, mesh: Mesh) -> int:
    """Round a lane count up to a multiple of the mesh's data extent so the
    leading axis divides evenly under ``shard_map``.  Padding lanes carry
    ``count == 0`` schedules (the event cores never execute them) and are
    sliced off host-side — bit-equality to the unsharded path is preserved
    by construction."""
    d = lane_count(mesh)
    return -(-n // d) * d
