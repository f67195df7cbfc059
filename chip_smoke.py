"""Chip smoke run: the campaign, consultation and fleet paths once on a TPU.

    python chip_smoke.py              # one chip: every phase below
    python chip_smoke.py --chips 4    # only the mesh-sharded lanes, compared
                                      # with the same calls on one device

Phases (one process, public entry points, ``backend="jax"`` throughout):

* ``device``      — the resolved event core is the Mosaic-compiled Pallas
  kernel (not interpret mode): its compiled program holds a
  ``tpu_custom_call``;
* ``campaign``    — ``run_campaign`` over all 18 (app, system) cells at the
  paper's N and P: 12 algorithms x both chunk modes, and the full
  ``SELECTOR_GRID`` replayed in lockstep;
* ``consultation``— ``SimPolicy.decide()`` on a ``LoopWhatIf`` priced on the
  device, cold and then warm, checked against the python backend's prices;
* ``fleet``       — a what-if-routed 4x8 fleet on a bursty trace of 60k
  requests; every admitted request completes exactly once;
* ``backends``    — noise-free jax vs python (exact chunk sequences,
  makespans within the tolerance of ``tests/test_backends.py``);
* ``cores``       — Pallas vs ``while_loop`` on one noisy batch and on fleet
  what-if rows: bit-equal, or the largest relative difference.

Each phase prints one JSON line.  The last line of standard output is
``{"ok": true, "device": {...}}``.  The script exits non-zero and prints no
result when JAX finds no TPU, or when any phase fails.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

#: campaign time steps per cell (the paper runs T=500; the smoke run only
#: has to drive every cell through the device path)
CAMPAIGN_T = 8
CAMPAIGN_REPS = 3
#: fleet requests on the bursty trace (bench_fleet's regime and shape)
FLEET_N = 60_000

_compile = {"n": 0, "s": 0.0}


def _on_event(event: str, secs: float, **_) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _compile["n"] += 1
        _compile["s"] += secs


def _emit(rec: dict) -> None:
    print(json.dumps(rec), flush=True)


def _phase(name: str, fn, failures: list) -> None:
    """Run one phase; print its line with wall and compile seconds."""
    n0, s0 = _compile["n"], _compile["s"]
    t0 = time.perf_counter()
    try:
        rec = fn()
        ok = bool(rec.pop("ok", True))
    except Exception as e:          # a phase failure fails the run
        rec, ok = {"error": f"{type(e).__name__}: {e}"}, False
    rec = {"phase": name, "ok": ok,
           "wall_s": time.perf_counter() - t0,
           "compiles": _compile["n"] - n0,
           "compile_s": _compile["s"] - s0, **rec}
    _emit(rec)
    if not ok:
        failures.append(name)


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def phase_device() -> dict:
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops
    from repro.sim import get_backend

    bk = get_backend("jax")
    B, K, P = 256, 4096, 128
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    text = jax.jit(ops.event_finish).lower(
        f32(B, K), f32(B, P), f32(B, P), f32(B), f32(B), i32(B, K),
        i32(B)).compile().as_text()
    kernel = "tpu_custom_call" in text
    return {"ok": bk.event_core == "pallas" and kernel,
            "event_core": bk.event_core, "backend": bk.name,
            "compiled_kernel": kernel, "data_parallel": bk.data_parallel}


def phase_campaign(T: int = CAMPAIGN_T, reps: int = CAMPAIGN_REPS) -> dict:
    import numpy as np

    from repro.core import N_ALGORITHMS
    from repro.sim import (APPLICATIONS, CHUNK_MODES, SELECTOR_GRID, SYSTEMS,
                           get_application, run_campaign)

    cells = [(a, s) for a in APPLICATIONS for s in SYSTEMS]
    res = run_campaign(cells, T=T, reps=reps, backend="jax",
                       selector_backend="jax", sim_backend="jax")
    bad = []
    instances = 0
    for (app, sysname), r in res.items():
        n_loops = len(get_application(app).loops(0))
        runs = r.sweep.runs
        if len(runs) != N_ALGORITHMS * len(CHUNK_MODES):
            bad.append(f"{app}/{sysname}: {len(runs)} portfolio runs")
        for key, run in runs.items():
            if run.times.shape != (T, n_loops) or not (
                    np.isfinite(run.times).all() and (run.times > 0).all()):
                bad.append(f"{app}/{sysname}/{key}: times")
        if len(r.selector_runs) != len(SELECTOR_GRID) * len(CHUNK_MODES):
            bad.append(f"{app}/{sysname}: {len(r.selector_runs)} lanes")
        for key, d in r.degradation().items():
            if not np.isfinite(d):
                bad.append(f"{app}/{sysname}/{key}: degradation {d}")
        if not (np.isfinite(r.oracle_total) and r.oracle_total > 0):
            bad.append(f"{app}/{sysname}: oracle {r.oracle_total}")
        instances += (T * n_loops * len(SELECTOR_GRID) * len(CHUNK_MODES))
    return {"ok": not bad, "errors": bad[:10], "cells": len(cells), "T": T,
            "reps": reps, "P": sorted({SYSTEMS[s].P for s in SYSTEMS}),
            "algorithms": N_ALGORITHMS, "chunk_modes": len(CHUNK_MODES),
            "selector_lanes": len(cells) * len(SELECTOR_GRID)
            * len(CHUNK_MODES), "replay_instances": instances}


def phase_consultation() -> dict:
    import numpy as np

    from repro.core import SimPolicy
    from repro.sim import LoopWhatIf, get_application, get_system

    system = get_system("epyc")
    profile = get_application("tc").loops(0)[0]

    def decide():
        whatif = LoopWhatIf(system, backend="jax")   # fresh: prices on device
        whatif.set_context(profile, 0)
        policy = SimPolicy(whatif, reward="LT")
        t0 = time.perf_counter()
        d = policy.decide()
        return d, time.perf_counter() - t0, whatif

    d_cold, cold_s, _ = decide()
    d_warm, warm_s, whatif = decide()
    cands = whatif.candidates()
    ref = LoopWhatIf(system, backend="python")
    ref.set_context(profile, 0)
    py = np.array([o.loop_time for o in ref.price(cands)])
    pick = next(i for i, c in enumerate(cands)
                if (c.alg, c.chunk_param) == (d_warm.action,
                                              d_warm.chunk_param))
    regret = float(py[pick] / py.min() - 1.0)
    return {"ok": (d_cold.action, d_cold.chunk_param)
            == (d_warm.action, d_warm.chunk_param) and regret <= 0.02,
            "P": system.P, "N": profile.N, "candidates": len(cands),
            "decision": [d_warm.action, d_warm.chunk_param],
            "phase_taken": d_warm.phase, "cold_s": cold_s, "warm_s": warm_s,
            "regret_vs_python_prices": regret}


def phase_fleet(n: int = FLEET_N) -> dict:
    import numpy as np

    from benchmarks.bench_fleet import BURSTY, N_GROUPS, REPLICAS, WAVE_QUOTA
    from repro.serving import AdmissionControl, FleetSimulator, make_trace

    trace = make_trace("bursty", n, seed=0, **BURSTY)
    fleet = FleetSimulator(n_groups=N_GROUPS, replicas_per_group=REPLICAS,
                           router="whatif", selector="SimPolicy",
                           backend="jax",
                           admission=AdmissionControl(wave_quota=WAVE_QUOTA))
    t0 = time.perf_counter()
    rep = fleet.run(trace, keep_latencies=True)
    run_s = time.perf_counter() - t0
    lat = rep.latencies
    served = sum(g["requests"] for g in rep.per_group)
    admitted = round(rep.mean_wave_size * rep.waves)
    once = (rep.n_requests == n and admitted == n and served == n
            and lat.size == n and bool(np.isfinite(lat).all())
            and bool((lat >= 0).all()))
    return {"ok": once, "groups": N_GROUPS, "replicas": REPLICAS,
            "requests": n, "admitted": admitted, "served": served,
            "latencies": int(lat.size), "waves": rep.waves, "run_s": run_s,
            "requests_per_s": n / run_s, "sim_makespan_s": rep.makespan,
            "sim_p95_s": rep.p95}


def phase_backends() -> dict:
    """Noise-free jax vs python, as ``tests/test_backends.py`` compares
    them: exact chunk sequences, makespans within its tolerances."""
    import numpy as np

    from repro.sim import LoopProfile, get_application, get_backend, get_system

    quiet = dataclasses.replace(get_system("broadwell"), P=8, noise_sigma=0.0,
                                jitter=0.0, speed_spread=0.0)
    uniform = LoopProfile(name="u", N=4096, memory_bound=0.0,
                          locality_sens=0.0, c_loc=64, unit=2**-20)
    py, jx = get_backend("python"), get_backend("jax")
    bad, worst = [], 0.0

    def compare(profile, system, alg, cp, rtol, lib_atol=None):
        nonlocal worst
        rp = py.run_instance(profile, system, alg, cp,
                             np.random.default_rng(0), record_chunks=True)
        rj = jx.run_instance(profile, system, alg, cp,
                             np.random.default_rng(0), record_chunks=True)
        rel = abs(rj.loop_time - rp.loop_time) / rp.loop_time
        worst = max(worst, rel)
        if rp.chunk_sizes != rj.chunk_sizes or rel > rtol or (
                lib_atol is not None and abs(rj.lib - rp.lib) > lib_atol):
            bad.append([alg, cp, profile.name])

    for alg in range(12):
        for cp in (0, 37):
            compare(uniform, quiet, alg, cp, 1e-4, 0.05)
    mandel = get_application("mandelbrot").loops(0)[0]
    for alg in (1, 2, 3, 4, 6):
        compare(mandel, dataclasses.replace(quiet, P=20), alg, 64, 2e-3)
    return {"ok": not bad, "mismatches": bad, "instances": 24 + 5,
            "max_rel_makespan": worst}


def _rel(a, b) -> float:
    import numpy as np

    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float((np.abs(a - b) / np.maximum(np.abs(b), 1e-30)).max())


def phase_cores() -> dict:
    """Pallas vs ``while_loop`` on one noisy campaign batch and on fleet
    what-if rows: identical inputs, so any difference is the cores'."""
    import numpy as np

    from repro.sim import InstanceSpec, get_application, get_system
    from repro.sim.backends.jax_batched import JaxBatchedBackend

    pallas = JaxBatchedBackend(kernel="pallas")
    loop = JaxBatchedBackend(kernel="while_loop")
    out = {}
    for sysname, app in (("cascadelake", "mandelbrot"), ("epyc", "tc")):
        system = get_system(sysname)
        profiles = get_application(app).loops(0)
        specs = [InstanceSpec(i % len(profiles), alg, cp, (alg, cp, r))
                 for i, (alg, cp, r) in enumerate(
                     (a, cp, r) for a in range(1, 12) for cp in (0, 64)
                     for r in range(4))]
        a = pallas.run_batch(profiles, system, specs)
        b = loop.run_batch(profiles, system, specs)
        equal = bool((a.loop_time == b.loop_time).all()
                     and (a.lib == b.lib).all()
                     and (a.n_chunks == b.n_chunks).all())
        out[f"{app}/{sysname}"] = {
            "P": system.P, "instances": len(specs), "bit_equal": equal,
            "max_rel_makespan": _rel(a.loop_time, b.loop_time),
            "max_chunks": int(a.n_chunks.max())}
    rng = np.random.default_rng(7)
    prefixes = [np.concatenate([[0.0], np.cumsum(rng.random(600 + 97 * i)
                                                 * 1e-3)]) for i in range(4)]
    avails = [rng.random(8) * 1e-3 for _ in range(4)]
    cands = [(s, a, cp) for s in range(4) for a in range(12)
             for cp in (0, 8)]
    ra = pallas.what_if_routes(prefixes, 8, avails, 2e-4, 1e-3, cands)
    rb = loop.what_if_routes(prefixes, 8, avails, 2e-4, 1e-3, cands)
    out["what_if_routes"] = {"rows": len(cands),
                             "bit_equal": bool((ra == rb).all()),
                             "max_rel_makespan": _rel(ra, rb)}
    worst = max(v["max_rel_makespan"] for v in out.values())
    # a real divergence (not an ulp-level fusion difference) fails the run
    return {"ok": worst < 1e-5,
            "bit_equal": all(v["bit_equal"] for v in out.values()),
            "max_rel_makespan": worst, **out}


# ---------------------------------------------------------------------------
# four chips: the mesh-sharded lanes against data_parallel=1
# ---------------------------------------------------------------------------

def phase_mesh() -> dict:
    import jax
    import numpy as np

    from repro.sim import CellSpec, ReplayBatch, sweep_portfolio
    from repro.sim.backends.jax_batched import JaxBatchedBackend

    sharded = JaxBatchedBackend()                 # every visible device
    single = JaxBatchedBackend(data_parallel=1)
    mesh_devices = ([] if sharded.mesh is None else
                    sorted(d.id for d in sharded.mesh.devices.flat))
    spread = mesh_devices == sorted(d.id for d in jax.devices())
    out = {"devices": len(jax.devices()), "mesh_devices": mesh_devices,
           "event_core": sharded.event_core}
    bad = []
    times = {}
    for label, bk in (("data1", single), ("mesh", sharded)):
        t0 = time.perf_counter()
        sweeps = {c: sweep_portfolio(*c, T=4, reps=3, backend=bk)
                  for c in (("mandelbrot", "cascadelake"), ("tc", "epyc"))}
        lanes = [CellSpec(app, sysname, sel, mode, reward)
                 for app, sysname in (("tc", "epyc"),
                                      ("lulesh", "broadwell"))
                 for mode in ("default", "expChunk")
                 for sel, reward in (("RandomSel", None),
                                     ("ExhaustiveSel", None),
                                     ("ExpertSel", None), ("QLearn", "LT"),
                                     ("SARSA", "LIB"))]
        replays = ReplayBatch(lanes, T=4, seed=0, backend=bk).run()
        rng = np.random.default_rng(7)
        prefixes = [np.concatenate([[0.0], np.cumsum(rng.random(600 + 97 * i)
                                                     * 1e-3)])
                    for i in range(4)]
        avails = [rng.random(8) * 1e-3 for _ in range(4)]
        # 4 slots x 12 algs x 2 chunks - 1 = 95 rows: indivisible by 4
        cands = [(s, a, cp) for s in range(4) for a in range(12)
                 for cp in (0, 8)][:-1]
        routes = bk.what_if_routes(prefixes, 8, avails, 2e-4, 1e-3, cands)
        times[label] = time.perf_counter() - t0
        if label == "data1":
            ref = (sweeps, replays, routes)
            continue
        for c, sw in sweeps.items():
            for key, run in sw.runs.items():
                r = ref[0][c].runs[key]
                if not ((run.times == r.times).all()
                        and (run.libs == r.libs).all()):
                    bad.append(f"run_batch {c} {key}")
        for run, r, spec in zip(replays, ref[1], lanes):
            if run.total != r.total or run.history != r.history:
                bad.append(f"run_lockstep {spec.key}")
        if not (routes == ref[2]).all():
            bad.append("what_if_routes")
    out.update({"ok": spread and not bad, "spread_over_all": spread,
                "mismatches": bad[:10], "bit_equal": not bad,
                "lockstep_lanes": len(lanes), "route_rows": len(cands),
                "wall_s_data1": times["data1"],
                "wall_s_mesh": times["mesh"]})
    return out


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); "
              "nothing measured", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_on_event)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    _emit({"phase": "start", "device": device, "compile_cache": cache,
           "jax": jax.__version__})
    failures: list = []
    if args.chips == 4:
        _phase("mesh", phase_mesh, failures)
    else:
        for name, fn in (("device", phase_device),
                         ("campaign", phase_campaign),
                         ("consultation", phase_consultation),
                         ("fleet", phase_fleet),
                         ("backends", phase_backends),
                         ("cores", phase_cores)):
            _phase(name, fn, failures)
    if failures:
        print(f"chip_smoke: failed phases: {failures}", file=sys.stderr)
        return 1
    _emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
