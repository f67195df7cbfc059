"""Structured selection API: reward registry dispatch, Hybrid expert+RL,
SelectionService v2 (instance context manager, overrides, stable seeds),
and the paper-§5 warm-start roundtrip through the service."""

import numpy as np
import pytest

from repro.core import (Decision, HybridPolicy, Observation, QLearnPolicy,
                        SelectionService, get_reward, make_policy,
                        register_reward, reward_names, system_fingerprint)


# ---------------------------------------------------------------------------
# synthetic imbalanced workload: adaptive algorithms (>= 7) fix a severe
# imbalance; cost valley at algorithm 9 (the paper's STREAM-like regime)
# ---------------------------------------------------------------------------

BEST = 9


def synthetic_obs(action: int, t: int, noise: float = 0.0,
                  rng=None) -> Observation:
    cost = 1.0 + 0.3 * abs(action - BEST)
    if noise and rng is not None:
        cost += rng.normal(0.0, noise)
    lib = 5.0 if action >= 7 else 60.0
    return Observation(loop_time=cost, lib=lib, instance=t)


def drive(policy, T=400, noise=0.0, seed=0):
    rng = np.random.default_rng(seed)
    explored = 0
    for t in range(T):
        d = policy.decide()
        if d.phase in ("expert", "explore"):
            explored += 1
        policy.feedback(d, synthetic_obs(d.action, t, noise, rng))
    return policy.decide(), explored


# ---------------------------------------------------------------------------
# reward registry
# ---------------------------------------------------------------------------

def test_builtin_rewards_registered():
    for name in ("LT", "LIB", "p95", "throughput", "LT+LIB"):
        assert name.lower() in reward_names()


def test_reward_dispatch_extracts_the_right_signal():
    obs = Observation(loop_time=2.0, lib=40.0, throughput=100.0,
                      tail_latency=3.5)
    assert get_reward("LT")(obs) == 2.0
    assert get_reward("LIB")(obs) == 40.0
    assert get_reward("p95")(obs) == 3.5
    assert get_reward("throughput")(obs) == -100.0
    assert get_reward("LT+LIB")(obs) == pytest.approx(2.0 * 1.4)


def test_reward_fallbacks_without_rich_signals():
    obs = Observation(loop_time=2.0, lib=10.0)
    assert get_reward("p95")(obs) == 2.0          # falls back to loop time
    assert get_reward("throughput")(obs) == 2.0
    pe = Observation(loop_time=4.0, pe_times=(1.0, 2.0, 4.0))
    assert get_reward("p95")(pe) == pytest.approx(
        np.percentile([1.0, 2.0, 4.0], 95))


def test_register_custom_reward_and_use_by_name():
    @register_reward("test-geo")
    def geo(obs):
        return obs.loop_time * (1.0 + obs.lib / 50.0)

    assert get_reward("TEST-GEO") is geo          # case-insensitive
    policy = make_policy("qlearn", reward="test-geo", n_actions=3)
    d = policy.decide()
    policy.feedback(d, Observation(loop_time=1.0, lib=25.0))
    assert policy.agent.reward.count == 1         # signal reached Eq. 11


def test_unknown_reward_raises():
    with pytest.raises(ValueError, match="unknown reward"):
        make_policy("qlearn", reward="nope")


def test_decision_chunk_param_defaults():
    d = Decision(action=3)
    assert d.with_instance_defaults(64).chunk_param == 64
    steered = Decision(action=3, chunk_param=8)
    assert steered.with_instance_defaults(64).chunk_param == 8


def test_observation_from_pe_times():
    obs = Observation.from_pe_times([1.0, 2.0, 3.0], instance=7)
    assert obs.loop_time == 3.0
    assert obs.lib == pytest.approx((1.0 - 2.0 / 3.0) * 100.0)
    assert obs.instance == 7


# ---------------------------------------------------------------------------
# policies through the structured protocol
# ---------------------------------------------------------------------------

def test_every_policy_name_builds_and_decides():
    for name in ("Fixed", "RandomSel", "ExhaustiveSel", "ExpertSel",
                 "QLearn", "SARSA", "Hybrid"):
        kw = {"algorithm": 2} if name == "Fixed" else {"seed": 3}
        p = make_policy(name, **kw)
        d = p.decide()
        assert isinstance(d, Decision)
        assert 0 <= d.action < 12
        p.feedback(d, synthetic_obs(d.action, 0))


def test_decision_phases_progress_explore_to_exploit():
    p = QLearnPolicy(n_actions=3)
    phases = []
    for t in range(12):
        d = p.decide()
        phases.append(d.phase)
        p.feedback(d, synthetic_obs(d.action, t))
    assert phases[:9] == ["explore"] * 9          # 3*3 explore-first
    assert set(phases[9:]) == {"exploit"}


# ---------------------------------------------------------------------------
# HybridPolicy: the paper-§6 combination
# ---------------------------------------------------------------------------

def test_hybrid_explores_less_than_qlearn_and_matches_selection():
    q_final, q_explored = drive(QLearnPolicy(), T=400)
    h = HybridPolicy()
    h_final, h_explored = drive(h, T=400)
    assert h.learning_steps < 144                 # bounded exploration
    assert h_explored < q_explored                # fewer explore instances
    # equal-or-better final selection on the imbalanced workload
    cost = lambda a: 1.0 + 0.3 * abs(a - BEST)
    assert cost(h_final.action) <= cost(q_final.action)
    assert h_final.action == BEST


def test_hybrid_expert_phase_bounds_rl_to_adaptive_window():
    h = HybridPolicy(expert_steps=4, window=5)
    drive(h, T=60)
    # severe imbalance: the fuzzy ladder must have pushed the RL window
    # into the adaptive end of the portfolio
    assert all(a >= 5 for a in h.actions)
    assert BEST in h.actions
    assert h.learning_steps == 4 + 25


def test_hybrid_robust_to_noise():
    h = HybridPolicy()
    final, _ = drive(h, T=400, noise=0.05, seed=1)
    assert abs(final.action - BEST) <= 1


def test_hybrid_window_clamps_to_portfolio():
    h = HybridPolicy(window=50, n_actions=4, expert_steps=2)
    drive(h, T=30)
    assert h.actions == [0, 1, 2, 3]


# ---------------------------------------------------------------------------
# SelectionService v2
# ---------------------------------------------------------------------------

def test_instance_context_manager_records_feedback():
    svc = SelectionService("QLearn", reward="LT")
    with svc.instance("L0") as inst:
        assert isinstance(inst.decision, Decision)
        inst.report(loop_time=1.5, lib=10.0, throughput=64.0)
    assert len(svc.history("L0")) == 1
    assert svc.history("L0")[0][1] == 1.5
    obs = svc._regions["L0"].observations[0]
    assert obs.throughput == 64.0 and obs.instance == 0


def test_instance_without_report_is_a_peek():
    svc = SelectionService("QLearn")
    with svc.instance("L0"):
        pass                                      # decided, never executed
    assert len(svc.history("L0")) == 0
    assert not svc.policy("L0").agent._t          # agent did not advance


def test_instance_report_accepts_pe_times_only():
    svc = SelectionService("SARSA", reward="p95")
    with svc.instance("L0") as inst:
        inst.report(pe_times=[1.0, 2.0, 4.0])
    (_, lt, lib), = svc.history("L0")
    assert lt == 4.0 and lib > 0


def test_history_is_readonly_introspection():
    """history() must not instantiate a region policy as a side effect
    (an Oracle service would crash on a typo'd region otherwise)."""
    svc = SelectionService("Oracle")          # regions come via overrides
    assert svc.history("typo") == []
    assert svc.regions == []
    svc.set_policy("typo", "ExpertSel")       # still free: nothing was built


def test_randomsel_shim_matches_seed_repo_stream():
    """Seeded RandomSel trajectories must be bit-identical to the
    pre-redesign implementation (select rolls, observe only updates LIB)."""
    def reference(seed, libs):
        rng = np.random.default_rng(seed)
        current, lib, out = 0, 100.0, []
        for l in libs:
            if lib / 10.0 > rng.random():
                current = int(rng.integers(0, 12))
            out.append(current)
            lib = l
        return out

    from repro.core import RandomSel
    libs = [0.0, 50.0, 20.0, 5.0, 80.0, 0.0, 30.0]
    sel = RandomSel(seed=7)
    got = []
    for l in libs:
        a = sel.select()
        got.append(a)
        sel.observe(a, 1.0, l)
    assert got == reference(7, libs)


def test_per_region_policy_overrides():
    svc = SelectionService("QLearn", reward="LT",
                           overrides={"io": {"method": "ExhaustiveSel"}})
    svc.set_policy("ladder", "ExpertSel")
    assert svc.policy("io").name == "ExhaustiveSel"
    assert svc.policy("ladder").name == "ExpertSel"
    assert svc.policy("compute").name == "QLearn"
    with pytest.raises(ValueError, match="live policy"):
        svc.set_policy("io", "SARSA")


def test_region_seeds_are_stable_across_services():
    """The old hash((seed, region)) varied per process under salted string
    hashing; the CRC-32 digest must give identical RandomSel streams for
    identical construction."""
    def stream(svc):
        out = []
        for t in range(30):
            with svc.instance("waves") as inst:
                out.append(inst.action)
                inst.report(loop_time=1.0, lib=30.0)
        return out

    a = stream(SelectionService("RandomSel", seed=42))
    b = stream(SelectionService("RandomSel", seed=42))
    c = stream(SelectionService("RandomSel", seed=43))
    assert a == b
    assert a != c


def test_hybrid_by_name_through_service():
    svc = SelectionService("Hybrid", reward="LT", expert_steps=2, window=3)
    for t in range(20):
        with svc.instance("L0") as inst:
            inst.report(observation=synthetic_obs(inst.action, t))
    assert svc.policy("L0").name == "Hybrid"
    assert not svc.policy("L0").learning          # 2 + 9 = 11 < 20


# ---------------------------------------------------------------------------
# warm start through the service (paper §5 end-to-end)
# ---------------------------------------------------------------------------

def train_service(store, region="gravity", T=300):
    svc = SelectionService("QLearn", reward="LT", store_dir=str(store))
    for t in range(T):
        with svc.instance(region) as inst:
            inst.report(observation=synthetic_obs(inst.action, t))
    return svc


def test_service_save_warmstart_roundtrip(tmp_path):
    svc = train_service(tmp_path)
    trained = svc.policy("gravity")
    assert not trained.learning
    paths = svc.save()
    assert len(paths) == 1

    fresh = SelectionService("QLearn", reward="LT", store_dir=str(tmp_path))
    policy = fresh.policy("gravity")
    assert fresh.warm_started("gravity")
    assert not policy.learning                    # 144-instance phase skipped
    d = policy.decide()
    assert d.phase == "exploit"
    assert d.action == trained.decide().action == BEST
    np.testing.assert_allclose(policy.agent.q, trained.agent.q)


def test_service_context_manager_autosaves(tmp_path):
    with train_service(tmp_path) as svc:
        pass                                      # __exit__ persists
    fresh = SelectionService("QLearn", reward="LT", store_dir=str(tmp_path))
    assert fresh.warm_started("gravity")
    # svc.save() was never called explicitly
    assert svc.policy("gravity").decide().action == BEST


def test_warmstart_keyed_by_region_and_system(tmp_path):
    svc = train_service(tmp_path)
    svc.save()
    other_region = SelectionService("QLearn", store_dir=str(tmp_path))
    assert not other_region.warm_started("pressure")
    other_system = SelectionService("QLearn", store_dir=str(tmp_path),
                                    system="deadbeef")
    assert not other_system.warm_started("gravity")
    assert len(system_fingerprint()) == 8


def test_warmstart_ignores_reward_mismatch(tmp_path):
    """A Q-table trained for LT must not warm-start a LIB-objective run."""
    svc = train_service(tmp_path)
    svc.save()
    lib_run = SelectionService("QLearn", reward="LIB",
                               store_dir=str(tmp_path))
    assert not lib_run.warm_started("gravity")
    assert lib_run.policy("gravity").learning


def test_warmstart_shape_mismatch_starts_cold(tmp_path):
    """Growing the portfolio after a snapshot is a cache miss, not a crash
    (and never a silently mis-shaped table)."""
    svc = SelectionService("QLearn", reward="LT", store_dir=str(tmp_path),
                           n_actions=5)
    for t in range(40):
        with svc.instance("plans") as inst:
            inst.report(observation=synthetic_obs(inst.action, t))
    svc.save()
    grown = SelectionService("QLearn", reward="LT", store_dir=str(tmp_path),
                             n_actions=6)
    assert not grown.warm_started("plans")
    assert grown.policy("plans").agent.q.shape == (6, 6)
    assert grown.policy("plans").learning


def test_midlearning_snapshot_resumes_not_freezes(tmp_path):
    """A snapshot saved 5 instances into the 144-step explore phase must
    resume exploration, not freeze a near-empty Q-table into greedy
    exploitation forever."""
    with SelectionService("QLearn", reward="LT",
                          store_dir=str(tmp_path)) as svc:
        for t in range(5):
            with svc.instance("gravity") as inst:
                inst.report(observation=synthetic_obs(inst.action, t))
    resumed = SelectionService("QLearn", reward="LT",
                               store_dir=str(tmp_path))
    policy = resumed.policy("gravity")
    assert policy.learning                        # still exploring
    assert policy.agent._t == 5                   # ...from where it stopped
    assert not resumed.warm_started("gravity")    # learning was NOT skipped
    for t in range(200):
        with resumed.instance("gravity") as inst:
            inst.report(observation=synthetic_obs(inst.action, t))
    assert resumed.policy("gravity").decide().action == BEST


def test_hybrid_corrupt_agent_snapshot_leaves_policy_untouched(tmp_path):
    """A snapshot with a valid window but inconsistent agent record must not
    half-restore (a stale non-None agent would disable the expert-driven
    window rebuild)."""
    h = HybridPolicy()
    bad = {"actions": [0, 1, 2, 3, 4],
           "agent": {"q": [[0.0] * 3] * 3, "state": 0, "alpha": 0.5}}
    with pytest.raises(ValueError):
        h.load_state_dict(bad)
    assert h.agent is None and h.actions == []    # untouched: expert phase
    drive(h, T=60)                                # ...still builds the window
    assert BEST in h.actions


def test_report_explicit_signals_win_over_pe_derivation():
    svc = SelectionService("QLearn", reward="p95")
    with svc.instance("L0") as inst:
        obs = inst.report(pe_times=[1.0, 2.0, 4.0], lib=12.5,
                          tail_latency=9.0)
    assert obs.loop_time == 4.0                   # derived makespan
    assert obs.lib == 12.5                        # caller's LIB wins
    assert obs.tail_latency == 9.0                # caller's p95 wins


def test_warmstart_corrupt_snapshot_starts_cold(tmp_path):
    svc = train_service(tmp_path)
    path, = svc.save()
    with open(path, "w") as f:
        f.write("{not json")
    fresh = SelectionService("QLearn", reward="LT", store_dir=str(tmp_path))
    assert not fresh.warm_started("gravity")
    assert fresh.policy("gravity").learning


def test_midlearning_restore_resumes_same_explore_circuit():
    """The Eulerian explore-first circuit depends on the start node; a
    mid-learning snapshot must resume on the circuit it was saved on."""
    from repro.core import QLearnAgent
    src = QLearnAgent(n_actions=3, initial_state=1)
    for _ in range(4):
        src.observe(src.select(), 1.0)
    snap = src.state_dict()
    expected = [src.select() for _ in range(1)]   # next explore action
    dst = QLearnAgent(n_actions=3)                # default initial_state=0
    dst.load_state_dict(snap)
    assert dst.initial_state == 1
    assert dst._explore == src._explore
    assert dst.select() == expected[0]


def test_report_derives_lib_from_pe_times_alongside_loop_time():
    """Supplying loop_time must not suppress LIB/p95 derivation from
    pe_times — an LIB-reward policy would otherwise learn from 0.0."""
    svc = SelectionService("QLearn", reward="LIB")
    with svc.instance("L0") as inst:
        obs = inst.report(loop_time=2.0, pe_times=[1.0, 2.0, 0.5])
    assert obs.loop_time == 2.0                   # explicit wins
    assert obs.lib > 0.0                          # derived from pe_times
    assert obs.tail_latency is not None


def test_wrongtyped_snapshot_field_starts_cold(tmp_path):
    import json
    svc = train_service(tmp_path)
    path, = svc.save()
    rec = json.load(open(path))
    rec["state"]["agent"]["q"] = {"bad": 1}
    json.dump(rec, open(path, "w"))
    fresh = SelectionService("QLearn", reward="LT", store_dir=str(tmp_path))
    assert not fresh.warm_started("gravity")      # cache miss, no TypeError
    assert fresh.policy("gravity").learning


def test_truncated_agent_snapshot_leaves_agent_untouched(tmp_path):
    """A record missing a later field (hand-edited/truncated JSON) must not
    half-restore the Q-table before failing."""
    import json
    svc = train_service(tmp_path)
    path, = svc.save()
    rec = json.load(open(path))
    del rec["state"]["agent"]["alpha"]
    json.dump(rec, open(path, "w"))
    fresh = SelectionService("QLearn", reward="LT", store_dir=str(tmp_path))
    assert not fresh.warm_started("gravity")
    agent = fresh.policy("gravity").agent
    assert (agent.q == 0).all() and agent._t == 0  # a true cold start


def test_hybrid_snapshot_rejected_on_grown_portfolio(tmp_path):
    svc = SelectionService("Hybrid", reward="LT", store_dir=str(tmp_path),
                           n_actions=12)
    for t in range(60):
        with svc.instance("plans") as inst:
            inst.report(observation=synthetic_obs(inst.action, t))
    svc.save()
    grown = SelectionService("Hybrid", reward="LT", store_dir=str(tmp_path),
                             n_actions=20)
    assert not grown.warm_started("plans")        # stale window: cache miss
    assert grown.policy("plans").learning


def test_warmstart_reward_match_is_case_insensitive(tmp_path):
    svc = SelectionService("QLearn", reward="lt", store_dir=str(tmp_path))
    for t in range(200):
        with svc.instance("gravity") as inst:
            inst.report(observation=synthetic_obs(inst.action, t))
    svc.save()
    fresh = SelectionService("QLearn", reward="LT", store_dir=str(tmp_path))
    assert fresh.warm_started("gravity")


def test_warmstart_ignores_method_mismatch(tmp_path):
    svc = train_service(tmp_path)
    svc.save()
    sarsa = SelectionService("SARSA", reward="LT", store_dir=str(tmp_path))
    assert not sarsa.warm_started("gravity")
    assert sarsa.policy("gravity").learning       # starts cold, correctly


def test_hybrid_warmstart_roundtrip(tmp_path):
    svc = SelectionService("Hybrid", reward="LT", store_dir=str(tmp_path))
    for t in range(80):
        with svc.instance("L0") as inst:
            inst.report(observation=synthetic_obs(inst.action, t))
    assert not svc.policy("L0").learning
    svc.save()
    fresh = SelectionService("Hybrid", reward="LT", store_dir=str(tmp_path))
    policy = fresh.policy("L0")
    assert fresh.warm_started("L0")
    assert not policy.learning                    # expert + explore skipped
    assert policy.decide().action == BEST


# ---------------------------------------------------------------------------
# deprecated scalar shims stay alive
# ---------------------------------------------------------------------------

def test_decide_is_a_pure_peek_for_every_policy():
    """Repeated decide() without feedback must not change the selection or
    advance any RNG (callers may peek at the selection)."""
    for name in ("RandomSel", "ExhaustiveSel", "ExpertSel", "QLearn",
                 "SARSA", "Hybrid"):
        p = make_policy(name, seed=11)
        first = p.decide().action
        assert all(p.decide().action == first for _ in range(10)), name


def test_make_selector_rl_shims_expose_agent():
    """Pre-redesign scripts rely on sel.agent (e.g. for save_agent)."""
    from repro.core import make_selector
    with pytest.warns(DeprecationWarning):
        q = make_selector("QLearn", reward_type="LIB", seed=0)
        s = make_selector("sarsa")
    assert q.agent.q.shape == (12, 12) and q.reward_type == "LIB"
    assert s.agent.learning_steps == 144


def test_make_selector_shim_warns_and_works():
    from repro.core import make_selector
    with pytest.warns(DeprecationWarning):
        sel = make_selector("qlearn", reward_type="LT")
    assert sel.learning_steps == 144
    for t in range(150):
        a = sel.select()
        sel.observe(a, 1.0 + 0.3 * abs(a - BEST),
                    5.0 if a >= 7 else 60.0)
    assert sel.select() == BEST


def test_begin_end_shims_feed_the_policy():
    svc = SelectionService("ExhaustiveSel")
    for t in range(12):
        a = svc.begin("L0")
        assert a == t
        svc.end("L0", a, 1.0 + 0.1 * abs(a - 4), 3.0)
    assert svc.begin("L0") == 4
