"""Backend equivalence: the batched vmapped JAX engine must agree with the
reference Python event loop — exactly where exactness is possible (noise-free
chunk sequences, shared closed forms), within tolerance elsewhere."""

import dataclasses

import numpy as np
import pytest

from repro.core.jaxsched import chunk_schedule, staticsteal_schedule
from repro.sim import (InstanceSpec, LoopProfile, backend_names, get_backend,
                       get_system, sweep_portfolio)
from repro.sim.whatif import noise_free

# P a power of two and unit an exact binary fraction keep the adaptive
# algorithms' telemetry bit-exact (variance exactly 0, weights exactly 1),
# so even the surrogate recurrences must match the host classes chunk-for-
# chunk.  locality_sens = 0: chunk-size-dependent locality inflation is real
# telemetry the surrogates cannot see.
QUIET = dataclasses.replace(get_system("broadwell"), P=8, noise_sigma=0.0,
                            jitter=0.0, speed_spread=0.0)
UNIFORM = LoopProfile(name="u", N=4096, memory_bound=0.0, locality_sens=0.0,
                      c_loc=64, unit=2**-20)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_registry_names():
    assert {"python", "jax"} <= set(backend_names())
    assert get_backend("python").name == "python"
    assert get_backend("jax").name == "jax"
    assert get_backend(get_backend("python")).name == "python"
    with pytest.raises(ValueError, match="unknown simulation backend"):
        get_backend("fortran")


def test_registry_env_default(monkeypatch):
    monkeypatch.delenv("REPRO_SIM_BACKEND", raising=False)
    assert get_backend(None).name == "python"
    monkeypatch.setenv("REPRO_SIM_BACKEND", "jax")
    assert get_backend(None).name == "jax"


def test_event_cap_is_shared():
    from repro.sim import engine, engine_jax
    from repro.sim.backends import base

    assert engine.EVENT_CAP == base.EVENT_CAP == engine_jax.MAX_EVENTS
    assert get_backend("python").event_cap == get_backend("jax").event_cap


# ---------------------------------------------------------------------------
# noise-free exact equivalence, every portfolio algorithm
# ---------------------------------------------------------------------------

#: QUIET, then the noise-free twins of the paper's machines (P = 20, 56,
#: 128): chunk sequences depend on P
NOISE_FREE_SYSTEMS = {"quiet": QUIET} | {
    name: noise_free(get_system(name))
    for name in ("broadwell", "cascadelake", "epyc")}


@pytest.mark.parametrize("system", list(NOISE_FREE_SYSTEMS))
@pytest.mark.parametrize("alg", range(12))
@pytest.mark.parametrize("cp", [0, 37])
def test_noise_free_chunk_sequences_and_makespan(alg, cp, system):
    py = get_backend("python")
    jx = get_backend("jax")
    sysm = NOISE_FREE_SYSTEMS[system]
    rp = py.run_instance(UNIFORM, sysm, alg, cp, np.random.default_rng(0),
                         record_chunks=True)
    rj = jx.run_instance(UNIFORM, sysm, alg, cp, np.random.default_rng(0),
                         record_chunks=True)
    assert rp.n_chunks == rj.n_chunks
    assert rp.chunk_sizes == rj.chunk_sizes, f"alg {alg} cp {cp} {system}"
    np.testing.assert_allclose(rj.loop_time, rp.loop_time, rtol=1e-4)
    np.testing.assert_allclose(rj.lib, rp.lib, atol=0.05)


def test_noise_free_nonadaptive_on_nonuniform_profile():
    """Non-adaptive schedules don't depend on telemetry, so they stay exact
    on imbalanced (gridded) loops too."""
    from repro.sim import get_application

    profile = get_application("mandelbrot").loops(0)[0]
    py = get_backend("python")
    jx = get_backend("jax")
    for alg in (1, 2, 3, 4, 6):
        rp = py.run_instance(profile, QUIET, alg, 64,
                             np.random.default_rng(0), record_chunks=True)
        rj = jx.run_instance(profile, QUIET, alg, 64,
                             np.random.default_rng(0), record_chunks=True)
        assert rp.chunk_sizes == rj.chunk_sizes, alg
        np.testing.assert_allclose(rj.loop_time, rp.loop_time, rtol=2e-3)


def test_closed_forms_are_bit_identical():
    """STATIC and over-cap SS/StaticSteal share the reference closed forms
    AND the numpy rng streams — identical even with noise on."""
    system = get_system("cascadelake")
    from repro.sim import get_application

    profile = get_application("stream").loops(0)[0]
    py = get_backend("python")
    jx = get_backend("jax")
    for alg in (0, 1, 5):       # N/c_floor = 2e9 >> EVENT_CAP
        seed = (0, 7, system.P, alg)
        rp = py.run_batch([profile], system,
                          [InstanceSpec(0, alg, 0, seed)])
        rj = jx.run_batch([profile], system,
                          [InstanceSpec(0, alg, 0, seed)])
        assert rp.loop_time[0] == rj.loop_time[0]
        assert rp.lib[0] == rj.lib[0]


# ---------------------------------------------------------------------------
# the acceptance cell: same Oracle selections on a T=4 campaign cell
# ---------------------------------------------------------------------------

def test_campaign_cell_oracle_agreement():
    """T=4 cell with a 40 % winner margin (TC on EPYC: StaticSteal-default
    dominates) — both engines must elect the same Oracle even under their
    different noise realizations."""
    sp = sweep_portfolio("tc", "epyc", T=4, reps=1, backend="python")
    sj = sweep_portfolio("tc", "epyc", T=4, reps=1, backend="jax")
    assert (sp.oracle_argmin() == sj.oracle_argmin()).all()
    # the winner goes through the shared closed form -> bit-identical
    np.testing.assert_allclose(sj.oracle_total(), sp.oracle_total(),
                               rtol=1e-12)
    # the c.o.v. regime (Fig. 4) must match across engines (adaptive
    # surrogates shift TC's portfolio spread by ~10 %)
    np.testing.assert_allclose(sj.cov(), sp.cov(), rtol=0.25)


def test_batch_matches_per_instance_python():
    """The batched campaign path reproduces run_fixed's historical rng
    tuples bit-for-bit on the Python backend."""
    from repro.sim import get_application
    from repro.sim.campaign import _digest
    from repro.sim.engine import run_instance

    app = get_application("sphynx")
    system = get_system("broadwell")
    profile = app.loops(0)[0]
    seed = (0, _digest("sphynx"), system.P, 6, _digest("default"), 0, 0)
    direct = run_instance(profile, system, 6, 0,
                          np.random.default_rng(seed))
    res = get_backend("python").run_batch(
        [profile], system, [InstanceSpec(0, 6, 0, seed)])
    assert res.loop_time[0] == direct.loop_time


# ---------------------------------------------------------------------------
# jaxsched: surrogates, StaticSteal replay, int32 overflow regression
# ---------------------------------------------------------------------------

def _drain_constant_telemetry(alg_idx, N, P, chunk_param):
    from repro.core import make_algorithm

    alg = make_algorithm(alg_idx)
    alg.reset(N, P, chunk_param)
    sizes = []
    pe = 0
    while True:
        c = alg.next_chunk(pe % P)
        if c == 0:
            break
        alg.report(pe % P, c, c * 1.0, c * 1.0)   # exactly 1.0 s/iteration
        sizes.append(c)
        pe += 1
        assert len(sizes) <= N + P
    return sizes


@pytest.mark.parametrize("alg", [4, 7, 8, 9, 10, 11])
def test_surrogate_schedules_match_host_classes(alg):
    for (N, P, cp) in [(1000, 4, 0), (4096, 8, 0), (4096, 8, 64),
                       (5000, 7, 8), (20000, 32, 0), (16, 1, 0)]:
        sizes, count = chunk_schedule(alg, N, P, cp, max_chunks=4096)
        got = list(np.asarray(sizes[: int(count)]))
        assert got == _drain_constant_telemetry(alg, N, P, cp), (N, P, cp)


def test_chunk_schedule_int32_overflow_regression():
    """TSS on STREAM (N = 2e9, x64 off): the old fixed-point state
    ``f0 * 1024`` wrapped int32 and degenerated into unit chunks."""
    N = 2_000_000_000
    sizes, count = chunk_schedule(4, N, 20, 0, max_chunks=4096)
    s = np.asarray(sizes[: int(count)], dtype=np.int64)
    assert s[0] == 50_000_000          # ceil(N / 2P)
    assert s.min() >= 1
    assert s.sum() == N
    assert int(count) < 4096


def test_chunk_schedule_rejects_beyond_int32():
    import jax

    if jax.config.read("jax_enable_x64"):
        pytest.skip("x64 enabled: large N is legal")
    with pytest.raises(ValueError, match="int32"):
        chunk_schedule(2, 2**31, 8, 0)
    with pytest.raises(ValueError, match="int32"):
        chunk_schedule(2, np.int64(2**31 + 5), 8, 0)   # np scalars too


def test_staticsteal_schedule_covers_and_marks_ownership():
    starts, sizes, pes, own, count = staticsteal_schedule(
        4096, 8, 0, max_chunks=8192, unit=2**-20)
    count = int(count)
    sizes = np.asarray(sizes[:count], dtype=np.int64)
    own = np.asarray(own[:count])
    assert sizes.sum() == 4096
    assert own[: 8].all()              # first rounds serve own ranges
    # every iteration delivered exactly once
    starts = np.asarray(starts[:count], dtype=np.int64)
    covered = np.zeros(4096, bool)
    for a, c in zip(starts, sizes):
        assert not covered[a: a + c].any()
        covered[a: a + c] = True
    assert covered.all()


# ---------------------------------------------------------------------------
# serving what-if
# ---------------------------------------------------------------------------

def test_what_if_wave_backends_agree():
    from repro.data.pipeline import Request
    from repro.serving.engine import DispatchSimulator

    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt_len=int(rng.integers(10, 400)),
                    gen_len=int(rng.integers(10, 200)), arrival=0.0)
            for i in range(192)]
    sim_py = DispatchSimulator(n_replicas=8, selector="Fixed",
                               selector_kw={"algorithm": 2})
    sim_jx = DispatchSimulator(n_replicas=8, selector="Fixed",
                               selector_kw={"algorithm": 2}, backend="jax")
    wp = sim_py.what_if(reqs)
    wj = sim_jx.what_if(reqs)
    assert wp.shape == wj.shape == (12,)
    # non-adaptive candidates are exact; adaptive/StaticSteal surrogates
    # within 5 %
    np.testing.assert_allclose(wj[[0, 1, 2, 3, 4, 6]], wp[[0, 1, 2, 3, 4, 6]],
                               rtol=1e-5)
    np.testing.assert_allclose(wj, wp, rtol=0.05)
    assert wp.argmin() == wj.argmin()
    # candidate-subset form (before run_wave mutates the busy-state)
    sub = sim_py.what_if(reqs, algs=[1, 2])
    np.testing.assert_allclose(sub, wp[[1, 2]])
    # the prediction for the committed wave matches the actual dispatch
    st = sim_py.run_wave(reqs)
    np.testing.assert_allclose(st.makespan, wp[2], rtol=1e-9)


def test_what_if_wave_float64_prefix_precision():
    """Regression for the float32 downcast of the request-cost prefix: the
    JAX backend now gathers per-chunk costs from the float64 prefix host-side
    (exact integer indexing), so large request totals stay within float32
    rounding of the float64 reference loop.  The old device-side f32-prefix
    subtraction lost ~3e-6 relative on this 16k-request wave — two orders of
    magnitude outside this tolerance."""
    rng = np.random.default_rng(0)
    prefix = np.concatenate([[0.0], np.cumsum(rng.random(16384) * 1e-2)])
    avail = rng.random(16) * 1e-3
    algs = [1, 2, 3, 6]                  # exact (non-adaptive) candidates
    wp = get_backend("python").what_if_wave(prefix, 16, avail, 2e-4, 1e-3,
                                            algs, chunk_param=4)
    wj = get_backend("jax").what_if_wave(prefix, 16, avail, 2e-4, 1e-3,
                                         algs, chunk_param=4)
    np.testing.assert_allclose(wj, wp, rtol=5e-7)


def test_schedule_caches_are_lru_bounded():
    """Long campaign processes must not grow the schedule caches without
    bound — the LRU evicts the least-recently-used entry."""
    from repro.sim.backends.jax_batched import JaxBatchedBackend, _LRU

    lru = _LRU(maxsize=2)
    lru.put("a", 1)
    lru.put("b", 2)
    assert lru.get("a") == 1             # refreshes "a"
    lru.put("c", 3)                      # evicts "b"
    assert lru.get("b") is None
    assert lru.get("a") == 1 and lru.get("c") == 3

    bk = JaxBatchedBackend()
    bk._sched_cache = _LRU(maxsize=4)
    for n in range(1000, 1006):
        bk._central_schedule(2, n, 8, 0)
    assert len(bk._sched_cache) <= 4
    assert get_backend("jax")._sched_cache.maxsize > 0
    assert get_backend("jax")._steal_cache.maxsize > 0


def test_grids_device_upload_cached_per_profile_stack():
    """Equal-content profile stacks (even rebuilt objects, as lockstep
    replays do every time step) hit the same device-resident upload."""
    from repro.sim import get_application

    bk = get_backend("jax")
    mk = lambda: LoopProfile(name="u", N=1024, memory_bound=0.0,
                             locality_sens=0.0, c_loc=64, unit=2**-20)
    assert bk._grids_dev([mk()]) is bk._grids_dev([mk()])
    # gridded profiles are rebuilt per loops(t) call yet digest equal
    app = get_application("mandelbrot")
    d1 = bk._grids_dev(app.loops(0))
    d2 = bk._grids_dev(app.loops(0))
    assert d1 is d2
    # different content -> different upload
    assert bk._grids_dev([mk()]) is not d1


def test_continuous_batcher_queue_is_deque():
    from collections import deque

    from repro.data.pipeline import Request
    from repro.serving.engine import ContinuousBatcher

    b = ContinuousBatcher(serve_step=None, init_cache_fn=None, batch_slots=2)
    assert isinstance(b.queue, deque)
    reqs = [Request(rid=i, prompt_len=4, gen_len=2, arrival=0.0)
            for i in range(4)]
    b.submit(reqs)
    b._refill()
    # FIFO: the first two submitted occupy the slots, rest stay queued
    assert [r.rid for r in b.active] == [0, 1]
    assert [r.rid for r in b.queue] == [2, 3]
