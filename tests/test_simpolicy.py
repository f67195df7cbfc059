"""Simulation-assisted selection (SimAS-style): SimPolicy / SimAssistedHybrid.

The core contract: on a noise-free cell the policy's argmin over the priced
candidate set must equal the Oracle selector's choice — on BOTH simulation
backends — and the sim-pruned hybrid's exploration set must always be a
subset of the full portfolio containing the Oracle pick.  Wiring tests cover
the two execution layers (campaign lanes, dispatch waves) and
the ``REPRO_SIM_POLICY`` environment selection.
"""

import os
import sys

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from _hypothesis_fallback import given, settings, st

from repro.core import (N_ALGORITHMS, Candidate, OraclePolicy,
                        SelectionService, SimAssistedHybrid, SimPolicy,
                        SimUnavailable, SIM_POLICY_ENV, is_sim_policy,
                        make_policy, resolve_sim_policy)
from repro.core.api import Observation
from repro.sim import LoopWhatIf, get_application, get_backend, get_system
from repro.sim.backends import InstanceSpec
from repro.sim.whatif import noise_free

BACKENDS = ("python", "jax")


# ---------------------------------------------------------------------------
# stub candidate simulator (unit-level tests)
# ---------------------------------------------------------------------------

class StubSim:
    """Deterministic candidate simulator over a fixed cost vector."""

    def __init__(self, costs, ready: bool = True):
        self.costs = np.asarray(costs, dtype=np.float64)
        self.ready = ready
        self.calls = 0

    def candidates(self):
        return [Candidate(a) for a in range(len(self.costs))]

    def price(self, cands):
        if not self.ready:
            raise SimUnavailable("stub not ready")
        self.calls += 1
        return [Observation(loop_time=float(self.costs[c.alg]))
                for c in cands]


# ---------------------------------------------------------------------------
# the oracle-agreement contract (acceptance criterion)
# ---------------------------------------------------------------------------

def _oracle_choice(profile, system, backend, candidates):
    """Test-local Oracle: exhaustively evaluate every candidate on the
    noise-free system (independent spec construction / seeds from
    ``LoopWhatIf``) and wrap the argmin as the paper's OraclePolicy."""
    bk = get_backend(backend)
    specs = [InstanceSpec(profile_id=0, alg=c.alg,
                          chunk_param=0 if c.chunk_param is None
                          else c.chunk_param,
                          seed=(7, i))
             for i, c in enumerate(candidates)]
    res = bk.run_batch([profile], noise_free(system), specs)
    best = int(np.argmin(res.loop_time))
    oracle = OraclePolicy(lambda t: candidates[best].alg)
    return oracle.decide().action, candidates[best], res.loop_time


@pytest.mark.parametrize("backend", BACKENDS)
def test_simpolicy_argmin_matches_oracle_noise_free(backend):
    """On a noise-free cell the SimPolicy decision IS the Oracle choice
    (tc/epyc: the winner leads the runner-up by ~48 %, so seed details of
    the closed-form tails cannot flip the argmin)."""
    profile = get_application("tc").loops(0)[0]
    system = get_system("epyc")
    whatif = LoopWhatIf(system, backend=backend)
    whatif.set_context(profile, 0)
    cands = whatif.candidates()

    oracle_action, oracle_cand, times = _oracle_choice(
        profile, system, backend, cands)
    policy = SimPolicy(whatif, reward="LT")
    d = policy.decide()

    assert d.phase == "exploit"
    assert d.action == oracle_action
    assert d.chunk_param == oracle_cand.chunk_param
    # the margin that makes this cell a meaningful probe
    spread = np.partition(times, 1)
    assert (spread[1] - spread[0]) / spread[0] > 0.2


#: the JAX backend's StaticSteal schedule is fixed before the run, so on a
#: non-uniform loop it cannot steal where the load sits: sphynx L0 at t=250
#: on broadwell, chunk 781, prices at 2.349 s with LIB 57% against the
#: python reference's 1.060 s and 0.7%, and SimPolicy elects AutoLLVM
_STATICSTEAL_NONUNIFORM = pytest.mark.xfail(
    strict=True, reason="JAX StaticSteal schedule mis-prices a non-uniform "
    "loop (sphynx L0 t=250 broadwell): 2.349 s vs 1.060 s")

#: the well-separated tc/epyc cell, then the consult cell's 9 (loop,
#: machine) pairs (bench/traffic/consult_varying_loops.json) mid-run
_AGREE_CELLS = [("tc", 0, 0, "epyc")] + [
    pytest.param(app, loop, 250, system,
                 marks=(_STATICSTEAL_NONUNIFORM
                        if (app, system) == ("sphynx", "broadwell") else ()))
    for app, loop in (("mandelbrot", 1), ("mandelbrot", 2), ("sphynx", 0))
    for system in ("broadwell", "cascadelake", "epyc")]


@pytest.mark.parametrize("app,loop,t,system_name", _AGREE_CELLS)
def test_simpolicy_backends_agree(app, loop, t, system_name):
    """Both engines elect the same candidate for the same loop context."""
    profile = get_application(app).loops(t)[loop]
    system = get_system(system_name)
    picks = []
    for backend in BACKENDS:
        whatif = LoopWhatIf(system, backend=backend)
        whatif.set_context(profile, 0)
        d = SimPolicy(whatif).decide()
        picks.append((d.action, d.chunk_param))
    assert picks[0] == picks[1]


def test_whatif_cache_distinguishes_equal_total_profiles():
    """Regression: mean-normalized patterns share N*unit totals across time
    steps, so the price cache must key on prefix-grid *content* — sphynx
    steps with equal totals but different load distributions previously
    aliased to one cached candidate table (stale argmin)."""
    app = get_application("sphynx")
    system = get_system("epyc")
    p6, p13 = app.loops(6)[0], app.loops(13)[0]
    assert p6.total == p13.total            # the collision precondition
    assert not np.array_equal(p6.prefix_grid, p13.prefix_grid)
    whatif = LoopWhatIf(system)
    whatif.set_context(p6, 0)
    a = whatif.price(whatif.candidates())
    whatif.set_context(p13, 0)
    b = whatif.price(whatif.candidates())
    assert a is not b                       # no stale cache hit
    fresh = LoopWhatIf(system)
    fresh.set_context(p13, 0)
    c = fresh.price(fresh.candidates())
    assert [o.loop_time for o in b] == [o.loop_time for o in c]


def test_simpolicy_pricing_is_deterministic_and_cached():
    profile = get_application("mandelbrot").loops(3)[1]
    system = get_system("broadwell")
    whatif = LoopWhatIf(system)
    whatif.set_context(profile, 0)
    cands = whatif.candidates()
    a = whatif.price(cands)
    b = whatif.price(cands)
    assert a is b                        # cache hit
    fresh = LoopWhatIf(system)
    fresh.set_context(profile, 0)
    c = fresh.price(cands)
    assert [o.loop_time for o in a] == [o.loop_time for o in c]


# ---------------------------------------------------------------------------
# fallback behavior
# ---------------------------------------------------------------------------

def test_simpolicy_flat_spread_falls_back_to_expert():
    policy = SimPolicy(StubSim(np.full(12, 3.0)), confidence_threshold=0.02)
    d = policy.decide()
    assert d.phase == "expert"
    # separated costs: committed argmin
    policy = SimPolicy(StubSim([5.0] * 11 + [1.0]))
    d = policy.decide()
    assert d.phase == "exploit" and d.action == 11


def test_simpolicy_unready_simulator_falls_back_to_expert():
    sim = StubSim(np.arange(12), ready=False)
    policy = SimPolicy(sim)
    d = policy.decide()
    assert d.phase == "expert" and d.confidence == 0.0
    # LoopWhatIf with no context behaves identically
    whatif = LoopWhatIf(get_system("broadwell"))
    with pytest.raises(SimUnavailable):
        whatif.price([Candidate(0)])
    assert SimPolicy(whatif).decide().phase == "expert"


def test_simpolicy_feedback_tracks_prediction_fidelity():
    policy = SimPolicy(StubSim([5.0] * 11 + [1.0]))
    d = policy.decide()
    policy.feedback(d, Observation(loop_time=1.1, lib=2.0))
    assert policy.pred_log == [(1.0, 1.1)]


# ---------------------------------------------------------------------------
# SimAssistedHybrid: the pruned-window property (acceptance criterion)
# ---------------------------------------------------------------------------

@settings(max_examples=60, deadline=None)
@given(costs=st.lists(st.floats(min_value=0.01, max_value=100.0,
                                allow_nan=False, allow_infinity=False),
                      min_size=12, max_size=12),
       top_k=st.integers(min_value=1, max_value=12))
def test_simhybrid_pruned_set_is_subset_containing_oracle_pick(costs, top_k):
    sim = StubSim(costs)
    policy = SimAssistedHybrid(sim, top_k=top_k, expert_steps=1)
    d = policy.decide()                          # expert phase
    policy.feedback(d, Observation(loop_time=1.0, lib=5.0))
    d = policy.decide()                          # builds the pruned agent
    assert policy.agent is not None
    actions = set(policy.actions)
    assert actions <= set(range(N_ALGORITHMS))   # subset of the full grid
    assert len(actions) == top_k
    assert int(np.argmin(costs)) in actions      # contains the Oracle pick
    assert d.action in actions                   # RL explores only the top-k


def test_simhybrid_learning_budget_shrinks():
    sim = StubSim(np.arange(12, dtype=float))
    policy = SimAssistedHybrid(sim, top_k=3, expert_steps=2)
    assert policy.learning_steps == 2 + 9        # vs Hybrid's 31, QLearn 144+
    hybrid = make_policy("Hybrid")
    assert policy.learning_steps < hybrid.learning_steps


def test_simhybrid_unready_simulator_uses_expert_window():
    sim = StubSim(np.arange(12, dtype=float), ready=False)
    policy = SimAssistedHybrid(sim, top_k=4, expert_steps=1)
    d = policy.decide()
    policy.feedback(d, Observation(loop_time=1.0, lib=5.0))
    policy.decide()
    # falls back to HybridPolicy's expert-centered window
    assert len(policy.actions) == policy.window
    assert set(policy.actions) <= set(range(N_ALGORITHMS))


# ---------------------------------------------------------------------------
# campaign-lane wiring
# ---------------------------------------------------------------------------

def test_sim_lanes_lockstep_equals_sequential():
    """SimPolicy/SimHybrid lanes ride the lockstep replay bit-exactly:
    candidate pricing draws from a stateless seed, never from the lane rng."""
    from repro.sim import CellSpec, ReplayBatch, run_selector_sequential

    lanes = [CellSpec("mandelbrot", "broadwell", "SimPolicy", "default", "LT"),
             CellSpec("mandelbrot", "broadwell", "SimHybrid", "default", "LT"),
             CellSpec("mandelbrot", "broadwell", "QLearn", "default", "LT")]
    batch = ReplayBatch(lanes, T=3).run()
    for spec, run in zip(lanes, batch):
        ref = run_selector_sequential("mandelbrot", "broadwell",
                                      spec.selector, spec.chunk_mode,
                                      reward=spec.reward, T=3)
        assert run.total == ref.total
        assert run.history == ref.history


def test_sim_lane_decisions_follow_the_sim_winner():
    """A SimPolicy lane executes the simulator's per-loop winners (phase
    'exploit' from instance 0 — no live exploration)."""
    from repro.sim import run_selector

    run = run_selector("tc", "epyc", "SimPolicy", reward="LT", T=3)
    profile = get_application("tc").loops(0)[0]
    whatif = LoopWhatIf(get_system("epyc"))
    whatif.set_context(profile, 0)
    d = SimPolicy(whatif).decide()
    assert [a for a, _, _ in run.history["L0"]] == [d.action] * 3


# ---------------------------------------------------------------------------
# serving wiring
# ---------------------------------------------------------------------------

def _requests(n=192, seed=0):
    from repro.data.pipeline import Request
    rng = np.random.default_rng(seed)
    return [Request(rid=i, prompt_len=int(rng.integers(10, 400)),
                    gen_len=int(rng.integers(10, 200)), arrival=0.0)
            for i in range(n)]


def test_dispatch_simulator_simpolicy_executes_what_if_argmin():
    from repro.serving.engine import DispatchSimulator

    sim = DispatchSimulator(n_replicas=8, selector="SimPolicy")
    reqs = _requests()
    predicted = sim.what_if(reqs)            # default-chunk candidate slice
    st0 = sim.run_wave(reqs)
    # the committed wave is the argmin over the *full* candidate set; its
    # makespan can only improve on the default-chunk argmin
    assert st0.makespan <= predicted.min() * (1 + 1e-9)
    d = sim.service.history("dispatch")
    assert len(d) == 1


def test_dispatch_simulator_binds_caller_supplied_wave_pricer():
    """Regression: a simulator passed through selector_kw must still get
    set_requests before every wave — otherwise SimPolicy silently degrades
    to the expert fallback forever."""
    from repro.serving.engine import DispatchSimulator, WaveWhatIf

    class RecordingWaveWhatIf(WaveWhatIf):
        bound = 0

        def set_requests(self, requests):
            type(self).bound += 1
            super().set_requests(requests)

    sim = DispatchSimulator(n_replicas=8, selector="SimPolicy")
    external = RecordingWaveWhatIf(sim)
    sim2 = DispatchSimulator(n_replicas=8, selector="SimPolicy",
                             selector_kw={"simulator": external})
    external._sim = sim2
    assert sim2._whatif is external
    sim2.run_wave(_requests(64))
    assert RecordingWaveWhatIf.bound == 1
    assert sim2.service.policy("dispatch").pred_log  # sim-driven, not expert


def test_wave_whatif_candidates_cover_chunk_variants():
    from repro.serving.engine import DispatchSimulator, WaveWhatIf

    sim = DispatchSimulator(n_replicas=8, selector="Fixed",
                            selector_kw={"algorithm": 2})
    wi = WaveWhatIf(sim)
    with pytest.raises(SimUnavailable):
        wi.candidates()
    reqs = _requests(64)
    wi.set_requests(reqs)
    cands = wi.candidates()
    assert len(cands) == 24                  # 12 algs x {default, expChunk}
    priced = wi.price(cands)
    assert len(priced) == 24
    assert all(o.loop_time > 0 for o in priced)


# ---------------------------------------------------------------------------
# REPRO_SIM_POLICY environment selection
# ---------------------------------------------------------------------------

def test_resolve_sim_policy_env(monkeypatch):
    monkeypatch.delenv(SIM_POLICY_ENV, raising=False)
    assert resolve_sim_policy("QLearn") == "QLearn"
    monkeypatch.setenv(SIM_POLICY_ENV, "simhybrid")
    assert resolve_sim_policy("QLearn") == "SimHybrid"
    assert is_sim_policy("SimPolicy") and is_sim_policy("sim-hybrid")
    assert not is_sim_policy("QLearn") and not is_sim_policy(None)
    # a typo'd env value must fail at resolve time, naming the variable
    monkeypatch.setenv(SIM_POLICY_ENV, "SimPolcy")
    with pytest.raises(ValueError, match=SIM_POLICY_ENV):
        resolve_sim_policy("QLearn")


def test_selection_service_defaults_from_env(monkeypatch):
    monkeypatch.setenv(SIM_POLICY_ENV, "SimPolicy")
    service = SelectionService(simulator=StubSim([5.0] * 11 + [1.0]))
    policy = service.policy("r0")
    assert isinstance(policy, SimPolicy)
    assert policy.decide().action == 11
    # explicit methods always win over the env
    service = SelectionService("QLearn", reward="LT", seed=0)
    assert service.policy("r0").name == "QLearn"


def test_dispatch_simulator_defaults_from_env(monkeypatch):
    from repro.serving.engine import DispatchSimulator

    monkeypatch.setenv(SIM_POLICY_ENV, "SimPolicy")
    sim = DispatchSimulator(n_replicas=4)
    assert sim._whatif is not None
    assert isinstance(sim.service.policy("dispatch"), SimPolicy)
    monkeypatch.delenv(SIM_POLICY_ENV)
    sim = DispatchSimulator(n_replicas=4)
    assert sim._whatif is None
    assert sim.service.policy("dispatch").name == "QLearn"
