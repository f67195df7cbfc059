"""Config guard for the LB4OMP campaign grid: the program must build the
applications, machines, portfolio and selector grid the configuration file
states.  A mismatch stops the run before anything is measured."""

from __future__ import annotations

import numpy as np

from reference import lb4omp as ref

#: steps whose cost patterns are compared with the reference's
STEPS = (0, 1, 250, 499)


def mismatches(cfg: dict) -> list:
    from repro.core import ALGORITHM_NAMES
    from repro.sim import (APPLICATIONS, CHUNK_MODES, SELECTOR_GRID, SYSTEMS,
                           get_application)
    from repro.sim.backends import EVENT_CAP

    bad = []

    def same(what, prog, want):
        if prog != want:
            bad.append(f"{what}: program {prog!r}, configuration {want!r}")

    same("applications", sorted(APPLICATIONS), sorted(cfg["apps"]))
    same("systems", sorted(SYSTEMS), sorted(cfg["systems"]))
    same("algorithms", list(ALGORITHM_NAMES), cfg["algorithms"])
    same("chunk modes", list(CHUNK_MODES), cfg["chunk_modes"])
    same("selector grid", [list(s) for s in SELECTOR_GRID],
         cfg["selector_grid"])
    same("event cap", EVENT_CAP, cfg["event_cap"])
    for name, m in cfg["systems"].items():
        if name not in SYSTEMS:
            continue
        s = SYSTEMS[name]
        for k, v in m.items():
            same(f"{name}.{k}", getattr(s, k), v)
    for name, a in cfg["apps"].items():
        if name not in APPLICATIONS:
            continue
        app = get_application(name)
        same(f"{name}.T", app.T, a["T"])
        same(f"{name}.loops", list(app.loop_names),
             [lp["name"] for lp in a["loops"]])
        for t in STEPS:
            for li, lp in enumerate(app.loops(t)):
                if li >= len(a["loops"]):
                    break
                want = ref.loop(cfg, name, li, t)
                for k in ("N", "memory_bound", "locality_sens", "c_loc",
                          "unit"):
                    same(f"{name}.{lp.name}.{k}", getattr(lp, k),
                         getattr(want, k))
                if (lp.prefix_grid is None) != (want.grid is None) or (
                        want.grid is not None and not np.allclose(
                            lp.prefix_grid, want.grid, rtol=1e-12,
                            atol=0.0)):
                    bad.append(f"{name}.{lp.name} cost pattern at t={t} "
                               "differs from the configuration's")
    _selectors(cfg["selectors"], same)
    return bad


def _selectors(prm: dict, same) -> None:
    """The selection methods' parameters: the RL agents', the reward levels,
    RandomSel's first jump, ExhaustiveSel's re-trigger, ExpertSel's fuzzy
    systems and SimPolicy's confidence threshold."""
    import inspect

    from repro.core import fuzzy
    from repro.core.agents import QLearnAgent
    from repro.core.portfolio import N_ALGORITHMS
    from repro.core.rewards import RewardTracker
    from repro.core.selectors import ExhaustivePolicy, RandomPolicy
    from repro.core.simpolicy import SimPolicy

    from reference import selectors as sel

    n = prm["n_actions"]
    same("selectors.n_actions", N_ALGORITHMS, n)
    rl = prm["rl"]
    agent = QLearnAgent()
    for k in ("alpha", "gamma", "alpha_decay", "alpha_min", "decay_mode",
              "initial_state"):
        same(f"selectors.rl.{k}", getattr(agent, k), rl[k])
    rt = RewardTracker()
    same("selectors.rl.reward_levels", [rt.r_pos, rt.r_neu, rt.r_neg],
         rl["reward_levels"])
    same("selectors.rl.explore_first", list(agent._explore),
         sel.explore_first(n, rl["initial_state"]))
    same("selectors.random.first_lib", RandomPolicy()._lib,
         prm["random"]["first_lib"])
    ex = ExhaustivePolicy()
    same("selectors.exhaustive", [ex._retrigger, ex._min_samples],
         [prm["exhaustive"]["lib_retrigger"],
          prm["exhaustive"]["min_samples"]])
    for name, system in (("initial", fuzzy.make_initial_system()),
                         ("diff", fuzzy.make_diff_system())):
        want = prm["expert"][name]
        same(f"selectors.expert.{name}.inputs",
             {v.name: {t: list(abc) for t, abc in v.terms.items()}
              for v in system.inputs}, want["inputs"])
        same(f"selectors.expert.{name}.rules",
             [[list(t), c] for t, c in system.rules], want["rules"])
    same("selectors.simpolicy.confidence_threshold",
         inspect.signature(SimPolicy).parameters[
             "confidence_threshold"].default,
         prm["simpolicy"]["confidence_threshold"])
