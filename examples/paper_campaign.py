"""Reproduce the paper's factorial campaign (Table 2 / Fig. 5) at a chosen
scale and print the degradation-vs-Oracle table.

    PYTHONPATH=src python examples/paper_campaign.py                 # subset
    PYTHONPATH=src python examples/paper_campaign.py --apps all --T 500

All requested cells run through ONE ``run_campaign`` call: the portfolio
sweeps batch per cell, and every cell's selector lanes replay in lockstep
(``--selector-backend jax`` batches the replays too; the default keeps them
on the reference engine for exact per-chunk telemetry).

``--selectors sim`` (or setting ``REPRO_SIM_POLICY``) adds the
simulation-assisted lanes — SimPolicy (candidate pricing in a noise-free
simulator, zero live exploration) and SimHybrid (RL over the simulator's
top-k) — priced on ``--sim-backend``.
"""

import argparse

from repro.core import resolve_sim_policy
from repro.launch.compile_cache import enable_compile_cache
from repro.sim import (APPLICATIONS, EXTENDED_SELECTOR_GRID, SELECTOR_GRID,
                       SIM_SELECTOR_GRID, SYSTEMS, run_campaign)

GRIDS = {"paper": SELECTOR_GRID, "extended": EXTENDED_SELECTOR_GRID,
         "sim": SIM_SELECTOR_GRID}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--apps", default="sphynx,stream")
    ap.add_argument("--systems", default="cascadelake")
    ap.add_argument("--T", type=int, default=300)
    ap.add_argument("--reps", type=int, default=2)
    ap.add_argument("--backend", default=None,
                    help="simulation backend for the portfolio sweeps "
                         "(python | jax; default REPRO_SIM_BACKEND)")
    ap.add_argument("--selector-backend", default="python",
                    help="backend for the lockstep selector replays "
                         "(python = exact telemetry; jax = batched lanes)")
    ap.add_argument("--selectors", default=None,
                    choices=sorted(GRIDS),
                    help="selector grid: paper | extended (+Hybrid) | sim "
                         "(+SimPolicy/SimHybrid); default: sim when "
                         "REPRO_SIM_POLICY is set, else paper")
    ap.add_argument("--sim-backend", default=None,
                    help="backend pricing the sim-assisted candidate sets "
                         "(default: --selector-backend)")
    args = ap.parse_args()
    enable_compile_cache()
    if args.selectors is None:
        # resolve_sim_policy validates the env spelling (a typo raises)
        args.selectors = "sim" if resolve_sim_policy() else "paper"

    apps = (list(APPLICATIONS) if args.apps == "all"
            else args.apps.split(","))
    systems = (list(SYSTEMS) if args.systems == "all"
               else args.systems.split(","))
    cells = [(app, system) for app in apps for system in systems]

    results = run_campaign(cells, T=args.T, reps=args.reps,
                           backend=args.backend,
                           selector_backend=args.selector_backend,
                           selectors=GRIDS[args.selectors],
                           sim_backend=args.sim_backend)
    for (app, system), cell in results.items():
        print(f"\n=== {app} on {system} ===   "
              f"Oracle={cell.oracle_total:.2f}s  "
              f"c.o.v.={cell.sweep.cov():.3f}")
        for k, d in sorted(cell.degradation().items(),
                           key=lambda kv: kv[1]):
            sel, mode, reward = k
            r = cell.selector_runs[k]
            shares = r.selection_shares()
            top = max(shares, key=shares.get) if shares else "-"
            tag = f"{sel}+{reward}" if reward else sel
            print(f"  {tag:15s} {mode:9s} {d:+7.1f}%   mostly->{top}")


if __name__ == "__main__":
    main()
