"""Event-core shoot-out: vmapped ``lax.while_loop`` vs the Pallas
event-loop kernel on the lanes the kernel targets — STREAM-scale
SS/StaticSteal schedules (K ~ 6e4 chunks per instance), the slowest lanes
of the batched engine.

Both cores consume the identical shared precompute (same fold seeds, same
noise realization), so besides wall-clock the bench asserts **bit-equality**
of every makespan/LIB — the accuracy contract of
``repro.kernels.event_loop``.  Results go to
``results/bench_event_kernel.json`` with the platform recorded: on CPU the
Pallas core runs in interpret mode (a correctness vehicle, not a speed
claim — the default core stays ``while_loop`` there); on TPU the same call
runs the Mosaic-compiled kernel.

``--smoke`` is the CI gate: a reduced-K lane through both cores, asserting
bit-equality of the batch results and the serving what-if path, and
recording a smoke-sized JSON so the artifact is always uploaded.
"""

from __future__ import annotations

import json
import os
import time

import numpy as np

OUT = os.path.join(os.path.dirname(__file__), "..", "results")

#: (lane name, alg, chunk_param, N, P, B) — alg 1 = SS, 5 = StaticSteal
LANES = (
    ("ss_K65536", 1, 64, 4_194_304, 20, 8),
    # N sized so the steal replay (own chunks + steal slack) stays inside
    # the 65536 buffer bucket
    ("staticsteal_K62504", 5, 64, 4_000_000, 20, 4),
    ("gss_K256", 2, 0, 1_048_576, 20, 32),
)


def _lane(alg, cp, N, P, B):
    import dataclasses

    from repro.sim import LoopProfile, get_system
    from repro.sim.backends import InstanceSpec

    system = dataclasses.replace(get_system("cascadelake"), P=P)
    profile = LoopProfile(name="u", N=N, memory_bound=0.3,
                          locality_sens=0.2, c_loc=64, unit=1e-8)
    specs = [InstanceSpec(0, alg, cp, (alg, cp, i)) for i in range(B)]
    return profile, system, specs


def _write(res: dict) -> None:
    try:
        from ._meta import stamp
    except ImportError:          # run as a script, not as benchmarks.*
        from _meta import stamp

    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, "bench_event_kernel.json"), "w") as f:
        json.dump(stamp(res), f, indent=2)


def run(lanes=LANES, reps: int = 3) -> dict:
    import jax

    from repro.sim.backends.jax_batched import JaxBatchedBackend

    # explicit kernel= so a REPRO_EVENT_CORE override can never turn the
    # shoot-out into pallas-vs-pallas
    cores = {"while_loop": JaxBatchedBackend(kernel="while_loop"),
             "pallas": JaxBatchedBackend(kernel="pallas")}
    out = {"platform": jax.default_backend(),
           "interpret": jax.default_backend() != "tpu",
           "lanes": {}}
    for name, alg, cp, N, P, B in lanes:
        if out["lanes"]:
            _write(out)          # checkpoint the lanes finished so far
        profile, system, specs = _lane(alg, cp, N, P, B)
        rec = {"alg": alg, "chunk_param": cp, "N": N, "P": P, "B": B}
        results = {}
        for core, bk in cores.items():
            bk.run_batch([profile], system, specs)       # compile + caches
            best = float("inf")
            for _ in range(reps):                        # min of reps: the
                t0 = time.perf_counter()                 # least-disturbed run
                results[core] = bk.run_batch([profile], system, specs)
                best = min(best, time.perf_counter() - t0)
            rec[f"{core}_s"] = round(best, 4)
        rec["K"] = int(results["pallas"].n_chunks[0])
        rec["speedup"] = round(rec["while_loop_s"]
                               / max(rec["pallas_s"], 1e-9), 2)
        rec["bitexact"] = bool(
            (results["while_loop"].loop_time
             == results["pallas"].loop_time).all()
            and (results["while_loop"].lib == results["pallas"].lib).all())
        assert rec["bitexact"], f"cores diverged on lane {name}"
        out["lanes"][name] = rec
    return out


def smoke() -> None:
    """CI gate: reduced-K lanes through BOTH cores — bit-equality of batch
    results and the serving what-if path, and a smoke-sized artifact."""
    from repro.sim.backends.jax_batched import JaxBatchedBackend

    res = run(lanes=(("ss_K4096_smoke", 1, 64, 262_144, 8, 4),
                     ("staticsteal_K4096_smoke", 5, 64, 262_144, 8, 2)))
    for name, rec in res["lanes"].items():
        assert rec["bitexact"], name
        print(f"smoke {name}: K={rec['K']} while_loop={rec['while_loop_s']}s "
              f"pallas={rec['pallas_s']}s bitexact={rec['bitexact']}")
    rng = np.random.default_rng(0)
    prefix = np.concatenate([[0.0], np.cumsum(rng.random(256) * 1e-3)])
    avail = rng.random(8) * 1e-3
    ww = JaxBatchedBackend(kernel="while_loop").what_if_wave(
        prefix, 8, avail, 2e-4, 1e-3, list(range(12)))
    wp = JaxBatchedBackend(kernel="pallas").what_if_wave(
        prefix, 8, avail, 2e-4, 1e-3, list(range(12)))
    assert (ww == wp).all(), "what-if wave diverged across event cores"
    print("smoke: what-if wave bit-identical across event cores")
    res["mode"] = "smoke"
    _write(res)


def main() -> list:
    res = run()
    res["mode"] = "full"
    _write(res)
    rows = []
    for name, rec in res["lanes"].items():
        rows.append((f"event_kernel_{name}", rec["pallas_s"] * 1e6,
                     f"K={rec['K']},speedup={rec['speedup']}x,"
                     f"bitexact={rec['bitexact']}"))
    return rows


if __name__ == "__main__":
    import argparse
    import sys

    # allow `python benchmarks/bench_event_kernel.py` from the repo root
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()
    if args.smoke:
        smoke()
    else:
        for row in main():
            print(f"{row[0]},{row[1]:.3f},{row[2]}")
