"""Pallas event-loop kernel: bit-equivalence against the ``lax.while_loop``
reference core (interpret mode), across every portfolio algorithm, forced-PE
StaticSteal rows, over-bucket schedule lengths, and random ragged batches.

The contract under test (``repro.kernels.event_loop``): with identical
inputs — the random draws and the prefix-grid gather live in the shared
data-parallel precompute — the lanes-last kernel must reproduce the
reference core *bit for bit*, so switching ``REPRO_EVENT_CORE`` can never
change a campaign statistic.
"""

import dataclasses
import os
import sys
import warnings

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from _hypothesis_fallback import given, settings, st

import jax.numpy as jnp

from repro.sim import LoopProfile, get_application, get_backend, get_system
from repro.sim.backends import InstanceSpec
from repro.sim.backends.jax_batched import (EVENT_CORES, JaxBatchedBackend,
                                            _core_while, resolve_event_core)

#: explicit kernel= constructions so the equivalence suite never degrades
#: to pallas-vs-pallas when REPRO_EVENT_CORE is set in the environment
#: (the jitted cores are module-level, so compile caches are still shared)
WHILE = JaxBatchedBackend(kernel="while_loop")
PALLAS = JaxBatchedBackend(kernel="pallas")

NOISY = dataclasses.replace(get_system("broadwell"), P=8)
QUIET = dataclasses.replace(NOISY, noise_sigma=0.0, jitter=0.0,
                            speed_spread=0.0)
UNIFORM = LoopProfile(name="u", N=4096, memory_bound=0.2, locality_sens=0.4,
                      c_loc=64, unit=2**-20)


# ---------------------------------------------------------------------------
# core selection plumbing
# ---------------------------------------------------------------------------

def test_resolve_event_core(monkeypatch):
    monkeypatch.delenv("REPRO_EVENT_CORE", raising=False)
    assert resolve_event_core() == "while_loop"
    with warnings.catch_warnings():
        warnings.simplefilter("error")     # no silent fallback to while_loop
        assert resolve_event_core("pallas") == "pallas"
    monkeypatch.setenv("REPRO_EVENT_CORE", "pallas")
    assert resolve_event_core() == "pallas"
    assert resolve_event_core("while_loop") == "while_loop"   # arg wins
    with pytest.raises(ValueError, match="unknown event core"):
        resolve_event_core("triton")
    assert set(EVENT_CORES) == {"while_loop", "pallas"}


def test_registry_exposes_pallas_backend():
    # explicit kernel= always wins over the environment
    assert WHILE.event_core == "while_loop"
    assert PALLAS.event_core == "pallas"
    assert PALLAS.name == "jax-pallas"
    # the registry name constructs with kernel="pallas" (env-proof)
    pk = get_backend("jax-pallas")
    assert isinstance(pk, JaxBatchedBackend)
    assert pk.event_core == "pallas"
    assert pk is not get_backend("jax")


# ---------------------------------------------------------------------------
# bit-equivalence across every portfolio algorithm (noise-free AND noisy)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("alg", range(12))
@pytest.mark.parametrize("system", [QUIET, NOISY], ids=["quiet", "noisy"])
def test_all_algorithms_bit_identical(alg, system):
    """Same fold seed => same noise realization => identical finish times,
    makespans and LIBs on both cores (STATIC delegates to the shared
    closed form on both, trivially equal)."""
    a = WHILE.run_instance(UNIFORM, system, alg, 0, np.random.default_rng(7))
    b = PALLAS.run_instance(UNIFORM, system, alg, 0, np.random.default_rng(7))
    assert a.loop_time == b.loop_time, alg
    assert a.lib == b.lib, alg
    assert a.n_chunks == b.n_chunks, alg
    np.testing.assert_array_equal(np.asarray(a.finish), np.asarray(b.finish))


def test_staticsteal_forced_rows_bit_identical():
    """StaticSteal rows carry forced-PE assignments (own ranges + steals);
    the kernel's forced branch must track the reference exactly, including
    on an imbalanced (gridded) profile."""
    profile = get_application("mandelbrot").loops(0)[0]
    for cp in (0, 16):
        a = WHILE.run_instance(profile, NOISY, 5, cp,
                               np.random.default_rng(11))
        b = PALLAS.run_instance(profile, NOISY, 5, cp,
                                np.random.default_rng(11))
        assert (a.loop_time, a.lib) == (b.loop_time, b.lib), cp


def test_over_bucket_schedule_bit_identical():
    """SS with a unit chunk floor on N=4096 fills the 4096 bucket — the
    kernel streams 8 segments through the sequential grid axis with the
    finish state resident in scratch; a 586-chunk schedule exercises the
    partial tail segment of the 1024 bucket."""
    for cp, chunks in ((1, 4096), (7, 586)):
        a = WHILE.run_instance(UNIFORM, NOISY, 1, cp,
                               np.random.default_rng(5))
        b = PALLAS.run_instance(UNIFORM, NOISY, 1, cp,
                                np.random.default_rng(5))
        assert a.n_chunks == b.n_chunks == chunks
        assert (a.loop_time, a.lib) == (b.loop_time, b.lib), cp


def test_mixed_batch_bit_identical():
    """One run_batch mixing bucket sizes, algorithms, and closed-form
    delegates — spec order and results must be identical across cores."""
    profiles = [UNIFORM, get_application("mandelbrot").loops(0)[0]]
    specs = [InstanceSpec(i % 2, alg, cp, (alg, cp, i))
             for i, (alg, cp) in enumerate(
                 [(1, 1), (2, 0), (5, 0), (6, 37), (0, 0), (9, 0), (1, 7)])]
    ra = WHILE.run_batch(profiles, NOISY, specs)
    rb = PALLAS.run_batch(profiles, NOISY, specs)
    np.testing.assert_array_equal(ra.loop_time, rb.loop_time)
    np.testing.assert_array_equal(ra.lib, rb.lib)
    np.testing.assert_array_equal(ra.n_chunks, rb.n_chunks)


def test_what_if_wave_cores_bit_identical():
    """The serving what-if routes through the same sequential core."""
    rng = np.random.default_rng(0)
    prefix = np.concatenate([[0.0], np.cumsum(rng.random(512) * 1e-3)])
    avail = rng.random(8) * 1e-3
    wa = WHILE.what_if_wave(prefix, 8, avail, 2e-4, 1e-3, list(range(12)))
    wb = PALLAS.what_if_wave(prefix, 8, avail, 2e-4, 1e-3, list(range(12)))
    np.testing.assert_array_equal(wa, wb)


# ---------------------------------------------------------------------------
# property test: random ragged schedules straight into the cores
# ---------------------------------------------------------------------------

@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 10_000), P=st.integers(1, 12),
       seg=st.sampled_from([64, 256]))
def test_random_ragged_schedules_property(seed, P, seg):
    """Random effective costs, speeds, jitters, forced rows and ragged
    counts (including empty lanes): kernel == while-loop reference, bit for
    bit, for any segment length that divides the bucket."""
    from repro.kernels.event_loop import event_finish

    rng = np.random.default_rng(seed)
    B, K = int(rng.integers(1, 6)), 256
    eff = jnp.asarray(rng.random((B, K)), jnp.float32)
    speed = jnp.asarray(1.0 + 0.2 * rng.standard_normal((B, P)), jnp.float32)
    jitter = jnp.asarray(rng.random((B, P)) * 1e-2, jnp.float32)
    h_eff = jnp.asarray(rng.random(B) * 1e-3, jnp.float32)
    bcost = jnp.asarray(rng.random(B) * 1e-3, jnp.float32)
    forced = np.full((B, K), -1, np.int32)
    nf = int(rng.integers(0, K))
    lane = int(rng.integers(0, B))
    forced[lane, :nf] = rng.integers(0, P, nf)
    cnt = rng.integers(0, K + 1, B).astype(np.int32)
    kernel = event_finish(eff, speed, jitter, h_eff, bcost,
                          jnp.asarray(forced), jnp.asarray(cnt),
                          seg=seg, interpret=True)
    ref = _core_while(eff, speed, jitter, h_eff, bcost,
                      jnp.asarray(forced), jnp.asarray(cnt))
    np.testing.assert_array_equal(np.asarray(kernel), np.asarray(ref))


@pytest.mark.parametrize("B,P,K,seg", [
    (130, 8, 64, 16),      # B not a multiple of 128: two lane blocks
    (3, 128, 256, 64),     # the widest paper machine (epyc)
    (129, 20, 128, 128),   # one lane past a block, one segment
    (1, 1, 8, 8),          # smallest what-if bucket, a single PE
])
def test_lanes_last_layout_edges(B, P, K, seg):
    """Edges of the lanes-last layout: lane padding to 128, P=128, a lane
    with count 0, a full lane, forced-PE rows on several lanes and ragged
    counts — kernel == while-loop reference, bit for bit."""
    from repro.kernels.event_loop import event_finish

    rng = np.random.default_rng(B * 1000 + P)
    eff = jnp.asarray(rng.random((B, K)), jnp.float32)
    speed = jnp.asarray(1.0 + 0.2 * rng.standard_normal((B, P)), jnp.float32)
    jitter = jnp.asarray(rng.random((B, P)) * 1e-2, jnp.float32)
    h_eff = jnp.asarray(rng.random(B) * 1e-3, jnp.float32)
    bcost = jnp.asarray(rng.random(B) * 1e-3, jnp.float32)
    forced = np.full((B, K), -1, np.int32)
    for lane in range(0, B, 3):
        nf = int(rng.integers(0, K + 1))
        forced[lane, :nf] = rng.integers(0, P, nf)
    cnt = rng.integers(0, K + 1, B).astype(np.int32)
    cnt[0] = K
    cnt[-1] = 0
    args = (eff, speed, jitter, h_eff, bcost, jnp.asarray(forced),
            jnp.asarray(cnt))
    kernel = np.asarray(event_finish(*args, seg=seg, interpret=True))
    ref = np.asarray(_core_while(*args))
    assert kernel.shape == (B, P)
    np.testing.assert_array_equal(kernel, ref)
    np.testing.assert_array_equal(kernel[-1], np.asarray(jitter)[-1])


def test_segment_must_tile_the_bucket():
    from repro.kernels.event_loop import _seg_for

    assert _seg_for(4096, 512) == 512
    assert _seg_for(8, 512) == 8
    with pytest.raises(ValueError, match="multiple of 8"):
        _seg_for(256, 12)
    with pytest.raises(ValueError, match="divide"):
        _seg_for(256, 96)


# ---------------------------------------------------------------------------
# campaign scale (slow tier)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_campaign_sweep_pallas_bit_identical():
    from repro.sim import sweep_portfolio

    sw = sweep_portfolio("tc", "epyc", T=4, reps=1, backend=WHILE)
    sp = sweep_portfolio("tc", "epyc", T=4, reps=1, backend=PALLAS)
    assert sw.oracle_total() == sp.oracle_total()
    assert (sw.oracle_argmin() == sp.oracle_argmin()).all()
    for key, run in sw.runs.items():
        np.testing.assert_array_equal(run.times, sp.runs[key].times)
        np.testing.assert_array_equal(run.libs, sp.runs[key].libs)


@pytest.mark.slow
def test_lockstep_replay_pallas_bit_identical():
    """Selector replays consume lane rngs host-side; with bit-equal cores
    the full decide/execute/learn trajectory is identical."""
    from repro.sim import CellSpec, ReplayBatch

    lanes = [CellSpec("mandelbrot", "broadwell", "QLearn", reward="LT"),
             CellSpec("tc", "epyc", "ExhaustiveSel")]
    rw = ReplayBatch(lanes, T=4, seed=0, backend=WHILE).run()
    rp = ReplayBatch(lanes, T=4, seed=0, backend=PALLAS).run()
    for a, b in zip(rw, rp):
        assert a.history == b.history
        assert a.total == b.total


@pytest.mark.slow
def test_stream_scale_lane_bit_identical():
    """K = 65536 (the STREAM-scale SS lane): 128 sequential segments
    through the grid axis, still bit-exact."""
    sysm = dataclasses.replace(get_system("cascadelake"), P=20)
    prof = LoopProfile(name="u", N=4_194_304, memory_bound=0.3,
                       locality_sens=0.2, c_loc=64, unit=1e-8)
    specs = [InstanceSpec(0, 1, 64, (i,)) for i in range(4)]
    ma, la, fa, ca = WHILE._run_events([prof], sysm, specs)
    mb, lb, fb, cb = PALLAS._run_events([prof], sysm, specs)
    assert (ca == cb).all() and ca[0] == 65536
    np.testing.assert_array_equal(ma, mb)
    np.testing.assert_array_equal(la, lb)
    np.testing.assert_array_equal(fa, fb)
