#!/usr/bin/env python
"""The Fig. 5 factorial sweep: every (application x system) cell runs
through the lockstep ``run_campaign`` engine on the chosen backend,
appending one resumable JSON line per cell to results/campaign_all.jsonl.

    PYTHONPATH=src python scripts/run_campaign_sweep.py [--backend jax]
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
from repro.sim.backends import BACKEND_ENV, backend_names  # noqa: E402

CAMPAIGN_OUT = os.path.join(os.path.dirname(__file__), "..", "results",
                            "campaign_all.jsonl")


def run_campaign_sweep(backend, selector_backend, T, reps):
    """Fig. 5 cells through the lockstep replay engine, one JSON line
    appended per (app, system) cell as soon as it completes (a crash loses
    at most the cell in flight).  Cells already present *with the same
    (T, reps, backends)* are skipped, so smoke runs and full sweeps can
    share one results file without masking each other."""
    from repro.sim import APPLICATIONS, SYSTEMS, run_campaign

    bk = backend or os.environ.get(BACKEND_ENV, "python")
    params = {"T": T, "reps": reps, "backend": bk,
              "selector_backend": selector_backend}
    done = set()
    if os.path.exists(CAMPAIGN_OUT):
        with open(CAMPAIGN_OUT) as f:
            for line in f:
                try:
                    r = json.loads(line)
                    done.add((r["app"], r["system"], r["T"], r["reps"],
                              r["backend"], r.get("selector_backend")))
                except Exception:
                    pass
    cells = [(a, s) for a in APPLICATIONS for s in SYSTEMS
             if (a, s, T, reps, bk, selector_backend) not in done]
    if not cells:
        print("campaign: all cells present")
        return
    for app, system in cells:
        cell = run_campaign([(app, system)], T=T, reps=reps, backend=backend,
                            selector_backend=selector_backend)[(app, system)]
        line = json.dumps({
            "app": app, "system": system, **params,
            "oracle_total": cell.oracle_total,
            "cov": cell.sweep.cov(),
            "degradation": {
                f"{sel}|{mode}|{reward or ''}": d
                for (sel, mode, reward), d in cell.degradation().items()},
        })
        with open(CAMPAIGN_OUT, "a") as f:
            f.write(line + "\n")
        print(line[:160], flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", default=None, choices=backend_names(),
                    help="simulation backend for the portfolio sweeps")
    ap.add_argument("--selector-backend", default="python",
                    choices=backend_names(),
                    help="backend for the lockstep selector replays "
                         "(default python = exact per-chunk telemetry for "
                         "the adaptive algorithms)")
    ap.add_argument("--T", type=int, default=50,
                    help="campaign time-steps per cell")
    ap.add_argument("--reps", type=int, default=2,
                    help="campaign portfolio reps")
    args = ap.parse_args()
    os.makedirs(os.path.dirname(CAMPAIGN_OUT), exist_ok=True)
    run_campaign_sweep(args.backend, args.selector_backend, args.T,
                       args.reps)


if __name__ == "__main__":
    main()
