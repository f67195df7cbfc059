"""The tiled precompute under ``shard_map``, run in a subprocess with 2
virtual CPU devices (``tests/test_precompute_tiles.py``): each shard takes
its live tiles from its own lanes' chunk counts, and the sharded program's
effective costs and (makespan, LIB, finish) match the dense single-device
oracle bit for bit.  Prints ``TILES-OK`` and exits 0 on success."""

from __future__ import annotations

import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=2")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(HERE, "..", "src")]

import numpy as np  # noqa: E402

from repro.launch.mesh import campaign_mesh  # noqa: E402
from repro.sim.backends.jax_batched import _sharded_events  # noqa: E402
from test_precompute_tiles import _mixed, check, lanes  # noqa: E402


def main() -> None:
    mesh = campaign_mesh(2)
    assert mesh.shape["data"] == 2, mesh.shape
    # 32 lanes: 16 a shard, two row blocks each; 8 lanes: 4 a shard, so a
    # tile of 4 lanes below K = 16384 and of one lane from there
    for P, core, B, K, counts in (
            (56, "while_loop", 32, 1024, _mixed(1024, 28)),
            (20, "while_loop", 8, 4096, np.array([0, 0, 17, 0, 4095, 3])),
            # lane-major tiles, each shard's lanes on their own
            (56, "while_loop", 8, 16384, np.array([0, 9000, 0, 0, 4097, 1])),
            (128, "while_loop", 8, 262144, np.array([0, 0, 0, 0, 0, 65538])),
            (20, "pallas", 16, 256, _mixed(256, 10))):
        fn = _sharded_events(mesh, P, core)
        check(P, core, lanes(B, K, P, counts=counts, steal=True), events=fn)
        print("sharded", P, core, B, K, "bit-equal", flush=True)
    print("TILES-OK")


if __name__ == "__main__":
    main()
