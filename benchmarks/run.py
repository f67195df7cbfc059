"""Benchmark harness — one bench per paper table/figure plus the framework
benches.  Prints ``name,us_per_call,derived`` CSV lines.

    PYTHONPATH=src python -m benchmarks.run [--only NAME] [--full]
    PYTHONPATH=src python -m benchmarks.run --smoke [--tier tier1|slow|all]

Benches:
    chunks       Fig. 1 & 2  chunk-size progressions
    cov          Fig. 4      c.o.v. per app-system pair
    degradation  Fig. 5      selector degradation vs Oracle
    traces       Figs. 7 & 8 per-instance selection traces
    serving      L3          chunk-scheduled dispatch vs selectors
    autotune     L2          step-plan selection on a real model
    backends     §Backends   portfolio sweep: python vs batched JAX engine
    replay       §Backends   lockstep multi-cell replay vs sequential
    event_kernel §Backends   while_loop vs Pallas event core
    simpolicy    §SimAS      simulation-assisted selection regret + latency
    perturb      §Perturb    reactive re-pricing vs frozen under perturbations
    fleet        §Fleet      trace-driven routing over replica groups
    faults       §Faults     failure recovery value + crash-safe kill-resume
    shard        §Mesh       per-device-count scaling of the sharded lanes
    learned      §Learned    offline-trained policy: held-out regret + distill

``--smoke`` is the single CI entry point: it runs every registered smoke
gate for the requested tier and ALWAYS writes ``results/smoke_summary.json``
(per-gate status, duration, error) before exiting non-zero on any failure —
the summary is the triage artifact CI uploads with ``if: always()``.  Each
gate runs in a child process of its own, one at a time, and the parent
never touches JAX: on a TPU host a device belongs to one process, and a
gate may start children that need it (``bench_faults``' kill gate).

A bench whose input is missing fails; it is never reported as a skip.
"""

from __future__ import annotations

import argparse
import inspect
import json
import os
import subprocess
import sys
import time

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
RESULTS = os.path.join(ROOT, "results")

#: every CI smoke gate: name -> (module, tier | tuple-of-tiers).  tier1
#: gates are fast drift checks run next to the unit tests; slow gates ride
#: the campaign-scale job; a tuple runs the gate on every listed tier (the
#: gate's ``smoke(tier)`` sizes itself when its signature takes the tier).
SMOKE_GATES = {
    "backends": ("bench_backends", "tier1"),
    "simpolicy": ("bench_simpolicy", "tier1"),
    "serving": ("bench_serving", "tier1"),
    "perturb": ("bench_perturb", ("tier1", "slow")),
    "fleet": ("bench_fleet", ("tier1", "slow")),
    "faults": ("bench_faults", ("tier1", "slow")),
    "learned": ("bench_learned", "tier1"),
    "replay": ("bench_replay", "slow"),
    "event_kernel": ("bench_event_kernel", "slow"),
    # its CI job boots with XLA_FLAGS=--xla_force_host_platform_device_count=8
    # so the mesh has lanes to shard over; sized to available devices
    # otherwise (bit-equality still gated on one device)
    "shard": ("bench_shard", "shard"),
}


def run_gate(name: str, tier: str) -> None:
    """Run one registered smoke gate in this process (the child side of
    :func:`run_smoke`); a failed gate raises."""
    import importlib

    smoke_fn = importlib.import_module(
        f"benchmarks.{SMOKE_GATES[name][0]}").smoke
    if "tier" in inspect.signature(smoke_fn).parameters:
        smoke_fn(tier=tier)          # tier-sized gates (e.g. fleet)
    else:
        smoke_fn()


def run_smoke(tier: str) -> int:
    """Run every registered smoke gate for ``tier`` ("all" runs everything),
    each in a child process; ``results/smoke_summary.json`` is rewritten
    after EVERY gate so a killed process (OOM, job timeout) still leaves the
    partial record the ``if: always()`` artifact upload exists for.
    Returns the number of failed gates."""
    summary = {"tier": tier, "gates": {}}
    os.makedirs(RESULTS, exist_ok=True)
    path = os.path.join(RESULTS, "smoke_summary.json")

    def flush_summary():
        with open(path, "w") as f:
            json.dump(summary, f, indent=2)

    failures = 0
    for name, (_, gate_tier) in SMOKE_GATES.items():
        tiers = (gate_tier,) if isinstance(gate_tier, str) else gate_tier
        rec = {"tier": "+".join(tiers)}
        run_tier = tier if tier != "all" else tiers[0]
        if tier != "all" and tier not in tiers:
            rec["status"] = "skipped"
            summary["gates"][name] = rec
            flush_summary()
            continue
        rec["status"] = "running"       # visible if this gate kills the job
        summary["gates"][name] = rec
        flush_summary()
        t0 = time.perf_counter()
        proc = subprocess.run(
            [sys.executable, "-m", "benchmarks.run", "--_gate", name,
             "--tier", run_tier],
            cwd=ROOT, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True)
        print(proc.stdout, end="", flush=True)
        if proc.returncode == 0:
            rec["status"] = "ok"
        else:
            failures += 1
            err = proc.stderr.strip().splitlines() or [""]
            rec["status"] = "failed"
            rec["error"] = err[-1]
            rec["traceback"] = "\n".join(err[-40:])
            print(proc.stderr, end="", file=sys.stderr, flush=True)
        rec["seconds"] = round(time.perf_counter() - t0, 3)
        flush_summary()
        print(f"smoke gate {name}: {rec['status']} "
              f"({rec['seconds']}s)", flush=True)
    return failures


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--full", action="store_true",
                    help="full-fidelity Fig. 5 campaign (hours)")
    ap.add_argument("--smoke", action="store_true",
                    help="run the registered CI smoke gates and write "
                         "results/smoke_summary.json")
    ap.add_argument("--tier", default="all",
                    choices=["tier1", "slow", "shard", "all"],
                    help="which smoke gates to run (with --smoke)")
    ap.add_argument("--_gate", default=None, choices=sorted(SMOKE_GATES),
                    help=argparse.SUPPRESS)
    args = ap.parse_args()

    if args.smoke:
        sys.exit(1 if run_smoke(args.tier) else 0)

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args._gate:
        run_gate(args._gate, args.tier)
        return

    from . import (bench_anova, bench_autotune, bench_backends, bench_chunks,
                   bench_cov, bench_degradation, bench_event_kernel,
                   bench_faults, bench_fleet, bench_learned, bench_perturb,
                   bench_replay, bench_serving, bench_shard, bench_simpolicy,
                   bench_traces)
    benches = {
        "chunks": bench_chunks.main,
        "cov": bench_cov.main,
        "degradation": lambda: bench_degradation.main(full=args.full),
        "anova": bench_anova.main,
        "traces": bench_traces.main,
        "serving": bench_serving.main,
        "autotune": bench_autotune.main,
        "backends": bench_backends.main,
        "replay": bench_replay.main,
        "event_kernel": bench_event_kernel.main,
        "simpolicy": bench_simpolicy.main,
        "perturb": bench_perturb.main,
        "fleet": bench_fleet.main,
        "faults": bench_faults.main,
        "shard": bench_shard.main,
        "learned": bench_learned.main,
    }
    if args.only:
        benches = {args.only: benches[args.only]}

    print("name,us_per_call,derived")
    failures = 0
    for name, fn in benches.items():
        t0 = time.time()
        try:
            rows = fn()
            for row in rows:
                print(f"{row[0]},{row[1]:.3f},{row[2]}")
            print(f"bench_{name}_wall,{(time.time() - t0) * 1e6:.0f},ok",
                  flush=True)
        except Exception as e:
            failures += 1
            print(f"bench_{name}_wall,0,FAILED({type(e).__name__}: {e})",
                  flush=True)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
