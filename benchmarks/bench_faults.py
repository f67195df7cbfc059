"""Fault-tolerance benchmark: recovery value and crash-safe resume.

Two CI gates over the fleet fault model
(:mod:`repro.sim.perturb` + :mod:`repro.serving.fleet.recovery`):

**recovery** — replay the bursty headline trace through a fleet that loses
a whole replica group mid-run (``ReplicaFailure`` window covering ~30% of
the trace).  With ``recovery=None`` the fleet runs the blind baseline:
routers keep dispatching into the failed group, interrupted work replays
there when it rejoins.  With a ``RecoveryPolicy`` (failure-aware routing +
migration + capped-backoff retries) the same what-if-priced router routes
around the outage and re-places interrupted work.  The gate: recovery ON
must beat recovery OFF on BOTH total makespan and p95 latency, with zero
dead-lettered requests — and both runs must satisfy the accounting
invariant (completed + dead-lettered == admitted) by construction.

**kill-resume** — launch the same faulty run in a child process journaling
wave-granularity snapshots (``RunJournal``), SIGKILL it mid-run, resume
from the surviving journal, and require the resumed ``FleetReport`` to be
**bit-identical** (every summary field and every latency sample) to an
uninterrupted run.  The uninterrupted run and the resumed run happen in
child processes too, one at a time: the parent only reads their files, so
it never holds the device while a child needs it.  On the ``slow`` tier
this is the issue-level >=1M-request crash-safety gate.

Everything is recorded to ``results/bench_faults.json``.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import tempfile
import time

OUT = os.path.join(os.path.dirname(__file__), "..", "results")
SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _stamp(record: dict) -> dict:
    try:
        from ._meta import stamp
    except ImportError:          # run as a script, not as benchmarks.*
        from _meta import stamp
    return stamp(record)


# fleet shape and headline bursty regime are shared with bench_fleet — the
# fault gates measure recovery value in exactly the routing benchmark's
# regime, not a bespoke one
try:
    from .bench_fleet import BURSTY, N_GROUPS, REPLICAS, WAVE_QUOTA
except ImportError:              # run as a script, not as benchmarks.*
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from bench_fleet import BURSTY, N_GROUPS, REPLICAS, WAVE_QUOTA

#: smoke sizes: the slow tier carries the issue-level >=1M-request gates,
#: tier1 replays the same regime at drift-check scale
SMOKE_N = {"tier1": 60_000, "slow": 1_000_000}

#: the outage: one whole replica group down for ~30% of the trace, placed
#: late (fractions of the trace duration, resolved per n) so the blind
#: baseline's rejoin-and-replay burst lands past the last arrival — the
#: recovery win then shows up in total makespan as well as in p95 (an
#: early outage's backlog re-drains before the trace ends on both sides)
FAIL_GROUP = 1
FAIL_WINDOW = (0.65, 0.95)

#: journal cadence for the kill-resume gate
JOURNAL_EVERY = 10


def _perturb(duration: float):
    from repro.sim.perturb import FleetPerturb, ReplicaFailure

    t0, t1 = (duration * f for f in FAIL_WINDOW)
    return FleetPerturb(failures=(
        ReplicaFailure(group=FAIL_GROUP, t0=t0, t1=t1),))


def _recovery():
    from repro.serving import RecoveryPolicy

    return RecoveryPolicy(max_retries=6)


def _fleet(trace, recovery, router: str = "whatif"):
    from repro.serving import AdmissionControl, FleetSimulator

    return FleetSimulator(n_groups=N_GROUPS, replicas_per_group=REPLICAS,
                          router=router, selector="SimPolicy", backend="jax",
                          admission=AdmissionControl(wave_quota=WAVE_QUOTA),
                          perturb=_perturb(trace.duration),
                          recovery=recovery)


def _trace(n: int, seed: int = 0):
    from repro.serving import make_trace

    return make_trace("bursty", n, seed=seed, **BURSTY)


def _run(trace, recovery, router="whatif", journal=None, resume=False,
         keep_latencies=False) -> dict:
    fleet = _fleet(trace, recovery, router)
    t0 = time.perf_counter()
    rep = fleet.run(trace, keep_latencies=keep_latencies, journal=journal,
                    resume=resume)
    s = rep.summary()
    s["wall_s"] = round(time.perf_counter() - t0, 2)
    return (s, rep) if keep_latencies else s


def _config(n: int) -> dict:
    return {"n_groups": N_GROUPS, "replicas_per_group": REPLICAS,
            "wave_quota": WAVE_QUOTA, "selector": "SimPolicy",
            "backend": "jax", "fail_group": FAIL_GROUP,
            "fail_window": list(FAIL_WINDOW), "n": n}


def _write(results: dict) -> None:
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, "bench_faults.json"), "w") as f:
        json.dump(_stamp(results), f, indent=2)


# ---------------------------------------------------------------------------
# gate 1: recovery ON beats recovery OFF under a mid-run group outage
# ---------------------------------------------------------------------------

def recovery_gate(n: int, seed: int = 0) -> dict:
    trace = _trace(n, seed)
    on = _run(trace, _recovery())
    off = _run(trace, None)
    rec = {"on": on, "off": off}
    print(f"faults recovery n={n}: makespan on={on['makespan']:.3f}s "
          f"off={off['makespan']:.3f}s | p95 on={on['p95'] * 1e3:.1f}ms "
          f"off={off['p95'] * 1e3:.1f}ms | dead on="
          f"{on['recovery']['dead_lettered']}", flush=True)
    assert on["makespan"] < off["makespan"], \
        (f"recovery-enabled makespan {on['makespan']:.4f}s did not beat "
         f"recovery-off {off['makespan']:.4f}s")
    assert on["p95"] < off["p95"], \
        (f"recovery-enabled p95 {on['p95'] * 1e3:.2f}ms did not beat "
         f"recovery-off {off['p95'] * 1e3:.2f}ms")
    assert on["recovery"]["dead_lettered"] == 0, \
        (f"recovery run dead-lettered {on['recovery']['dead_lettered']} "
         f"requests under a transient outage")
    for name, s in rec.items():
        got = s["recovery"]["completed"] + s["recovery"]["dead_lettered"]
        assert got == n, \
            f"{name}: accounting broke — {got} accounted of {n} admitted"
    return rec


# ---------------------------------------------------------------------------
# gate 2: SIGKILL mid-run, resume from the journal, bit-equal report
# ---------------------------------------------------------------------------

def _child_main(mode: str, journal_dir: str, out: str, n: int,
                seed: int) -> None:
    """Child-process entry for the kill gate.  ``ref`` runs uninterrupted,
    ``kill`` runs journaled until it is killed, ``resume`` resumes from the
    journal; ``ref`` and ``resume`` save their report to ``out``."""
    import numpy as np

    from repro.serving import RunJournal

    trace = _trace(n, seed)
    journal = (None if mode == "ref" else
               RunJournal(journal_dir, every=JOURNAL_EVERY, keep=3))
    s, rep = _run(trace, _recovery(), journal=journal,
                  resume=mode == "resume", keep_latencies=True)
    if mode != "kill":
        np.savez(out, summary=json.dumps(s), latencies=rep.latencies)


def _child(mode: str, journal_dir: str, out: str, n: int, seed: int):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(SRC) + os.pathsep + \
        env.get("PYTHONPATH", "")
    return subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--_child", mode,
         "--journal", journal_dir, "--out", out, "--n", str(n),
         "--seed", str(seed)],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.PIPE)


def _child_report(mode: str, journal_dir: str, out: str, n: int,
                  seed: int, timeout: float = 1800.0):
    """Run one child to its end and load the report it saved."""
    import numpy as np

    proc = _child(mode, journal_dir, out, n, seed)
    try:
        _, err = proc.communicate(timeout=timeout)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    if proc.returncode != 0:
        raise RuntimeError(f"{mode} child failed (rc={proc.returncode}): "
                           f"{err.decode(errors='replace')[-2000:]}")
    with np.load(out) as z:
        return json.loads(str(z["summary"])), z["latencies"]


def _kill_child_mid_run(journal_dir: str, n: int, seed: int,
                        min_waves: int = 2, timeout: float = 600.0) -> int:
    """Launch the journaled run in a subprocess and SIGKILL it once the
    journal holds ``min_waves`` snapshots (a genuinely torn run, not a
    cooperative shutdown).  Returns the number of surviving snapshots."""
    from repro.serving import RunJournal

    proc = _child("kill", journal_dir, "", n, seed)
    journal = RunJournal(journal_dir, every=JOURNAL_EVERY, keep=3)
    deadline = time.time() + timeout
    try:
        while time.time() < deadline:
            if len(journal.waves()) >= min_waves:
                proc.send_signal(signal.SIGKILL)
                break
            if proc.poll() is not None:   # finished before we killed it —
                break                     # resume still must reproduce it
            time.sleep(0.02)
        else:
            raise RuntimeError(f"child produced < {min_waves} journal "
                               f"snapshots within {timeout}s")
    finally:
        if proc.poll() is None:
            proc.kill()
        proc.wait()
    waves = journal.waves()
    if not waves:
        raise RuntimeError("no journal snapshot survived the kill")
    return len(waves)


def kill_resume_gate(n: int, seed: int = 0) -> dict:
    """Every fleet run of this gate happens in a child process, one at a
    time; this process only reads the journal and the saved reports, so it
    must not hold the device itself (``smoke`` runs it first)."""
    import numpy as np

    from repro.serving import RunJournal

    with tempfile.TemporaryDirectory() as d:
        jdir = os.path.join(d, "journal")
        ref_s, ref_lat = _child_report("ref", jdir, os.path.join(d, "ref.npz"),
                                       n, seed)
        snapshots = _kill_child_mid_run(jdir, n, seed)
        journal = RunJournal(jdir, every=JOURNAL_EVERY, keep=3)
        resumed_wave = journal.waves()[-1]
        res_s, res_lat = _child_report("resume", jdir,
                                       os.path.join(d, "resume.npz"), n, seed)
    drop = ("wall_s",)
    a = {k: v for k, v in ref_s.items() if k not in drop}
    b = {k: v for k, v in res_s.items() if k not in drop}
    lat_equal = bool(np.array_equal(ref_lat, res_lat))
    print(f"faults kill-resume n={n}: killed with {snapshots} snapshots, "
          f"resumed from wave {resumed_wave}/{ref_s['waves']}, "
          f"bit-equal={'yes' if a == b and lat_equal else 'NO'}", flush=True)
    assert a == b, \
        ("resumed report diverged from the uninterrupted run: "
         + json.dumps({k: [a[k], b[k]] for k in a if a.get(k) != b.get(k)},
                      default=str))
    assert lat_equal, \
        "resumed per-request latencies diverged from the uninterrupted run"
    return {"uninterrupted": ref_s, "resumed": res_s,
            "killed_at_snapshots": snapshots,
            "resumed_from_wave": resumed_wave, "bit_equal": True}


# ---------------------------------------------------------------------------
# harness entries
# ---------------------------------------------------------------------------

def smoke(tier: str = "tier1") -> None:
    """CI fault-tolerance gate: recovery beats the blind baseline on both
    makespan and p95 under a mid-run group outage, and a SIGKILLed
    journaled run resumes bit-identically (>=1M requests on the slow
    tier)."""
    n = SMOKE_N.get(tier, SMOKE_N["tier1"])
    results = {"config": _config(n), "tier": tier}
    # the kill gate's children need the device: run it before this process
    # touches JAX in the recovery gate
    results["kill_resume"] = kill_resume_gate(n)
    _write(results)
    results["recovery"] = recovery_gate(n)
    _write(results)


def main() -> list:
    """Harness entry: the recovery comparison at reduced scale (CSV rows);
    ``smoke`` carries the asserting gates."""
    n = 40_000
    trace = _trace(n)
    rows = []
    for label, recovery in (("recovery_on", _recovery()),
                            ("recovery_off", None)):
        s = _run(trace, recovery)
        r = s["recovery"]
        rows.append((f"faults_{label}", s["wall_s"] * 1e6,
                     f"mk={s['makespan']:.3f}s,p95={s['p95'] * 1e3:.1f}ms,"
                     f"retries={r['retries']},dead={r['dead_lettered']}"))
    return rows


if __name__ == "__main__":
    import argparse

    # allow `python benchmarks/bench_faults.py` from anywhere
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    sys.path.insert(0, os.path.abspath(SRC))

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--tier", default="tier1", choices=["tier1", "slow"])
    ap.add_argument("--_child", default=None,
                    choices=["ref", "kill", "resume"], help=argparse.SUPPRESS)
    ap.add_argument("--journal", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--out", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--n", type=int, default=SMOKE_N["tier1"])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if args._child:
        _child_main(args._child, args.journal, args.out, args.n, args.seed)
    elif args.smoke:
        smoke(args.tier)
    else:
        for row in main():
            print(f"{row[0]},{row[1]:.3f},{row[2]}")
