"""The event program's precompute over live tiles only
(``jax_batched._effective_costs``) against the dense formula, kept here as
the oracle: the effective costs and ``_batched_events``' (makespan, LIB,
finish) must match the dense program's bit for bit, on one device and on a
2-device lane mesh (``tests/_tiles_subproc.py``)."""

from __future__ import annotations

import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.sim.backends import jax_batched as jb

#: prefix-grid columns (the backend's resolution is 16384; the formula
#: does not depend on it)
G = 1024


def dense_eff(grids, gs, grid_id, starts, sizes, loc, noise):
    """The dense precompute: every (lane, slot) of the padded batch."""
    G = grids.shape[1] - 1

    def eff_one(gid, gs, starts, sizes, loc, noise):
        def pref(x):
            pos = x.astype(jnp.float32) * gs
            i = jnp.clip(pos.astype(jnp.int32), 0, G - 1)
            lo = grids[gid, i]
            return lo + (pos - i) * (grids[gid, i + 1] - lo)

        return (pref(starts + sizes) - pref(starts)) * loc * noise

    return jax.vmap(eff_one)(grid_id, gs, starts, sizes, loc, noise)


def dense_events(P, core, grids, grid_id, inv_n, starts, sizes, loc, count,
                 forced, seeds, h_eff, bcost, pe_mult, sig_scale, sigma,
                 jitter_max, speed_spread):
    """``_batched_events`` with the dense precompute."""
    G = grids.shape[1] - 1
    K = starts.shape[1]

    def draws(seed, ss):
        key = jax.random.PRNGKey(seed)
        kj, ks, kn = jax.random.split(key, 3)
        jitter = jax.random.uniform(kj, (P,)) * jitter_max
        speed = jnp.clip(1.0 + speed_spread * jax.random.normal(ks, (P,)),
                         0.8, 1.25)
        noise = jnp.exp((sigma * ss) * jax.random.normal(kn, (K,)))
        return jitter, speed, noise

    jitter, speed, noise = jax.vmap(draws)(seeds, sig_scale)
    speed = speed * pe_mult
    eff = dense_eff(grids, G * inv_n, grid_id, starts, sizes, loc, noise)
    fin = jb._core_finish(core, eff, speed, jitter, h_eff, bcost, forced,
                          count)
    mk = fin.max(axis=1)
    lib = jnp.where(mk > 0.0, (1.0 - fin.mean(axis=1) / mk) * 100.0, 0.0)
    return mk, lib, fin


_dense_eff = jax.jit(dense_eff)
_dense_events = jax.jit(dense_events, static_argnums=(0, 1))
_tiled_eff = jax.jit(jb._effective_costs)


def lanes(B, K, P, counts, seed=0, steal=False, perturbed=False):
    """The 16 operands of ``_batched_events``: lane i holds ``counts[i]``
    chunks of a random partition of its loop, the rest of its row and the
    padding lanes are zero, as the backend packs them."""
    rng = np.random.default_rng(seed)
    S = 4
    grids = np.cumsum(rng.random((S, G + 1)), axis=1).astype(np.float32)
    counts = np.asarray(counts, np.int32)
    starts = np.zeros((B, K), np.int32)
    sizes = np.zeros((B, K), np.int32)
    loc = np.zeros((B, K), np.float32)
    forced = np.full((B, K), -1, np.int32)
    N = np.ones(B, np.int64)
    for b, c in enumerate(counts):
        if c == 0:
            continue
        N[b] = int(rng.integers(max(c, 2 * c), 30518 * c + 1))
        cuts = np.sort(rng.choice(np.arange(1, N[b]), c - 1, replace=False))
        bounds = np.concatenate([[0], cuts, [N[b]]])
        starts[b, :c] = bounds[:-1]
        sizes[b, :c] = np.diff(bounds)
        loc[b, :c] = 1.0 + rng.random(c)
        if steal and b % 2 == 0:
            forced[b, :c] = rng.integers(-1, P, c)   # -1: argmin
    pe_mult = np.ones((B, P), np.float32)
    sig_scale = np.ones(B, np.float32)
    if perturbed:
        pe_mult[: len(counts)] = rng.uniform(0.5, 2.0, (len(counts), P))
        sig_scale[: len(counts)] = rng.uniform(0.5, 3.0, len(counts))
    cnt = np.zeros(B, np.int32)
    cnt[: len(counts)] = counts
    return (grids, rng.integers(0, S, B).astype(np.int32),
            (1.0 / N).astype(np.float32), starts, sizes, loc, cnt, forced,
            rng.integers(0, 2**31, B).astype(np.uint32),
            np.full(B, 2e-6, np.float32), np.full(B, 1e-7, np.float32),
            pe_mult, sig_scale, np.float32(0.05), np.float32(1e-6),
            np.float32(0.02))


def _mixed(K, n):
    rng = np.random.default_rng(K + n)
    counts = rng.integers(0, K + 1, n)
    counts[:2] = (K - 1, 0)       # one slot short of a whole segment
    counts[8:16] = 0              # a row block of padding lanes
    return counts


#: case -> (P, core, B, K, lanes' keywords)
CASES = {
    # stream on epyc with expChunk: 65538 chunks spill into K = 262144
    "spill": (128, "while_loop", 8, 262144, dict(counts=[65538])),
    "full": (20, "while_loop", 16, 1024, dict(counts=[1024] * 16)),
    "mixed": (56, "while_loop", 32, 1024, dict(counts=_mixed(1024, 30))),
    "steal": (20, "while_loop", 16, 4096,
              dict(counts=_mixed(4096, 16), steal=True)),
    "perturbed": (56, "while_loop", 16, 1024,
                  dict(counts=_mixed(1024, 12), perturbed=True)),
    "pallas": (20, "pallas", 16, 256,
               dict(counts=_mixed(256, 14), steal=True)),
}


def check(P, core, ops, events=None):
    """Effective costs and (makespan, LIB, finish) of the tiled program
    ``events`` (default ``_batched_events``) against the dense oracle, bit
    for bit; the dense costs are 0.0 wherever the live tiles do not
    reach."""
    grids, gid, inv_n, starts, sizes, loc, cnt = ops[:7]
    B, K = starts.shape
    noise = np.exp(np.random.default_rng(1).normal(0, 0.1, (B, K))).astype(
        np.float32)
    gs = np.float32(grids.shape[1] - 1) * inv_n
    want = np.asarray(_dense_eff(grids, gs, gid, starts, sizes, loc, noise))
    got = np.asarray(_tiled_eff(grids, gs, gid, starts, sizes, loc, noise,
                                cnt))
    assert got.tobytes() == want.tobytes()
    rows, seg = jb._tile_shape(B, K)
    reach = -(-cnt.reshape(-1, rows).max(axis=1) // seg) * seg
    live = np.arange(K)[None, :] < np.repeat(reach, rows)[:, None]
    assert not want[~live].any()
    assert live.sum() == jb._live_slots(cnt, B, K)
    events = events or (lambda *a: jb._batched_events(P, core, *a))
    ref = _dense_events(P, core, *ops)
    for a, b in zip(events(*ops), ref):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


@pytest.mark.parametrize("case", [*CASES, "sharded"])
def test_tiled_precompute_is_bit_identical(case):
    if case == "sharded":
        # the lane mesh needs 2 virtual devices, fixed when JAX starts
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   XLA_FLAGS="--xla_force_host_platform_device_count=2")
        proc = subprocess.run(
            [sys.executable, os.path.join(os.path.dirname(__file__),
                                          "_tiles_subproc.py")],
            env=env, capture_output=True, text=True, timeout=600)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "TILES-OK" in proc.stdout, proc.stdout + proc.stderr
        return
    P, core, B, K, kw = CASES[case]
    check(P, core, lanes(B, K, P, **kw))


def test_live_slots_count_the_tiles_a_dispatch_fills():
    # 8 x 512 tiles: a 65538-chunk lane fills 129 of an (8, 262144) batch
    assert jb._tile_shape(8, 262144) == (8, 512)
    assert jb._live_slots(np.array([65538]), 8, 262144) == 129 * 8 * 512
    # full rows cover the batch; padding rows and blocks cover nothing
    assert jb._live_slots(np.full(16, 1024), 16, 1024) == 16 * 1024
    assert jb._live_slots(np.array([1, 0, 0]), 16, 1024) == 8 * 512
    assert jb._live_slots(np.array([], np.int64), 8, 256) == 0
    # each of 2 shards of 4 lanes is tiled on its own
    assert jb._tile_shape(4, 256) == (4, 256)
    assert jb._live_slots(np.array([0, 0, 0, 0, 3]), 8, 256, 2) == 4 * 256
