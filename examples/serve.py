"""Chunk-self-scheduled serving dispatch (the paper's kind of system: a
runtime that selects scheduling algorithms online).

16 replica groups serve a heavy-tailed request stream; the dispatcher
self-schedules request chunks with the 12-algorithm portfolio and each
selection method picks the algorithm online (LT/LIB from measured wave
times).  Compare against the fixed-algorithm baselines.

    PYTHONPATH=src python examples/serve.py [--requests 4096]
"""

import argparse

from repro.core import ALGORITHM_NAMES
from repro.data import synthetic_requests
from repro.serving import DispatchSimulator


def dispatch_study(n_requests: int):
    print("== chunk-self-scheduled dispatch over 16 replica groups ==")
    reqs = synthetic_requests(n_requests, seed=7, heavy_tail=1.15)
    rows = []
    for alg in (0, 1, 2, 6):
        sim = DispatchSimulator(16, selector="Fixed",
                                selector_kw={"algorithm": alg})
        sim.run(reqs)
        s = sim.summary()
        rows.append((f"fixed {ALGORITHM_NAMES[alg]}", s))
    for sel, reward in [("ExhaustiveSel", None), ("QLearn", "LT"),
                        ("QLearn", "LIB"), ("SARSA", "LT"),
                        ("Hybrid", "LT"), ("Hybrid", "p95")]:
        sim = DispatchSimulator(16, selector=sel, reward=reward or "LT")
        sim.run(reqs)
        tag = f"{sel}+{reward}" if reward else sel
        rows.append((tag, sim.summary()))
    best = min(s["total_makespan"] for _, s in rows)
    for name, s in rows:
        print(f"  {name:18s} makespan={s['total_makespan']:8.3f}s  "
              f"mean LIB={s['mean_lib']:5.1f}%  "
              f"(x{s['total_makespan'] / best:.2f} of best)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=4096)
    args = ap.parse_args()
    dispatch_study(args.requests)


if __name__ == "__main__":
    main()
