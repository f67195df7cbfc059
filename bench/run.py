"""One run of one benchmark cell on the chip.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1

The cell is looked up by name in ``BENCHMARK.json`` at the checkout's root.
Its configuration is ``bench/configs/<config>.json``, checked against what
the program builds by ``bench/guards/<family>.py``; its traffic mix is
``bench/traffic/<traffic>.json``, whose ``driver`` names the generator in
``bench/drivers/``; each per-layer metric is read by
``bench/metrics/<metric>.py``.

The run fails, printing no result, when JAX finds no TPU or fewer chips than
the cell asks for.  It warms every shape the cell's traffic uses (set-up),
measures for ``--seconds``, then checks the window's outputs against the
plain reference.  With ``--trace 1`` the window runs under the JAX profiler
and the result holds the per-layer metrics instead of the end-to-end ones.
The last line of standard output is the result; the numbers compared, each
with its limit, are the last lines of standard error.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
OUT = os.path.join(BENCH, "out")
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_file(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Ctx:
    """What a driver and the metric readers see of one run."""

    def __init__(self, config, traffic, seed, spans, compiles):
        self.config = config
        self.traffic = traffic
        self.seed = seed
        self.spans = spans
        self.compiles = compiles
        self.window = (0.0, 0.0)
        self.chips = 1
        self.trace = None
        self.driver = None
        self.device_kind = None
        self.peaks = None
        self.notes = {}


def _profile_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


class Refused(Exception):
    """The run cannot be made: no chip, or not the configured program."""


def prepare(workload: str, seed: int, traced: bool, require_tpu: bool = True,
            traffic_override=None):
    """Load the cell, point JAX at the checkout's compile cache, look for
    the chips, check the program against the configuration, and build the
    cell's driver.  Returns (spec, ctx, used devices)."""
    spec = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise Refused(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    config = load_json(BENCH, "configs", f"{cell['config']}.json")
    traffic = load_json(BENCH, "traffic", f"{cell['traffic']}.json")
    traffic.update(traffic_override or {})

    import jax

    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(OUT, "jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise Refused(f"no TPU: JAX found {devices[0].platform}; nothing "
                      "measured")
    if len(devices) < cell["chips"]:
        raise Refused(f"{workload} needs {cell['chips']} chips, JAX found "
                      f"{len(devices)}")

    from harness.spans import Compiles, Spans

    guard = importlib.import_module(f"guards.{config['family']}")
    bad = guard.mismatches(config)
    if bad:
        raise Refused("the program does not build the configuration "
                      f"{config['name']}: " + "; ".join(bad[:10]))
    peaks = load_json(BENCH, "peaks.json")["devices"]
    kind = devices[0].device_kind
    if require_tpu and kind not in peaks:
        raise Refused(f"no published peaks for device kind {kind!r} in "
                      "bench/peaks.json")
    ctx = Ctx(config, traffic, seed, Spans(traced=traced),
              Compiles().install())
    ctx.chips = cell["chips"]
    ctx.device_kind = kind
    ctx.peaks = peaks.get(kind)
    ctx.driver = importlib.import_module(
        f"drivers.{traffic['driver']}").Driver(ctx)
    return spec, ctx, devices[:cell["chips"]]


def judge(out: dict, limits: dict) -> bool:
    """A run is correct when it attempted work, none failed, the sample is
    not empty, and every number compared is within its limit."""
    checks = out["checks"]
    return (out["attempted"] > 0 and out["failed"] == 0
            and out["sampled"] > 0 and set(checks) == set(limits)
            and all(checks[k] <= limits[k] for k in limits))


def main(argv=None, require_tpu: bool = True, traffic_override=None,
         hooks=None) -> int:
    """Run one cell.  The keyword arguments serve the benchmark's own tests:
    ``require_tpu=False`` skips the look for a chip, ``traffic_override``
    merges into the traffic mix, and ``hooks(driver)`` may break the timed
    path before the window."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        spec, ctx, used = prepare(args.workload, args.seed, bool(args.trace),
                                  require_tpu, traffic_override)
    except Refused as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2

    import jax

    from harness import trace as trace_mod

    driver, traffic, compiles = ctx.driver, ctx.traffic, ctx.compiles
    kind = ctx.device_kind
    driver.setup()
    if hooks is not None:
        hooks(driver)
    trace_dir = os.path.join(OUT, "trace", args.workload)
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        # host annotations and device operations only: the Python function
        # tracer would add millions of events and slow the host it measures
        jax.profiler.start_trace(trace_dir, profiler_options=(
            _profile_options()))
    setup_s = time.perf_counter() - T_START
    lo = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        result = driver.window(args.seconds)
    hi = time.perf_counter()
    if args.trace:
        jax.profiler.stop_trace()
    ctx.window = (lo, hi)
    ctx.compiles_in_window = compiles.between(lo, hi)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in used)
    device = {"platform": used[0].platform, "kind": kind,
              "count": len(used), "memory_peak_bytes": int(peak)}
    breakdown = None
    if args.trace:
        ctx.trace = trace_mod.reduce(trace_dir, n_devices=len(used))
        if ctx.trace.busy:
            device["busy_s"] = ctx.trace.busy_s
            device["window_s"] = ctx.trace.window_s
            breakdown = ctx.trace.breakdown()

    driver.release()
    out = driver.check()
    checks = out["checks"]
    limits = traffic["check"]["limits"]
    correct = judge(out, limits)

    names = {m["name"]: m for m in spec["end_to_end"] + spec["per_layer"]}
    metrics = {}
    if not args.trace:
        for m in spec["end_to_end"]:
            if args.workload not in m.get("workloads", [args.workload]):
                continue
            v = setup_s if m["name"] == "setup_s" else result.get(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in spec["per_layer"]:
            if args.workload not in m.get("workloads", []):
                continue
            reader = load_file(os.path.join(BENCH, "metrics",
                                            f"{m['name']}.py"),
                               f"metric_{len(metrics)}")
            v = reader.read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v,
                                      "unit": names[m["name"]]["unit"]}

    extra = {k: v for k, v in result.items() if k.startswith("_")}
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "trace": args.trace, "setup_s": setup_s,
                      "compiles_in_window": ctx.compiles_in_window,
                      "compiles_total": len(compiles.events), **extra,
                      **ctx.notes}),
          flush=True)
    line = {"correct": bool(correct), "attempted": int(out["attempted"]),
            "failed": int(out["failed"]), "metrics": metrics,
            "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = {k: {"value": checks.get(k), "limit": lim}
                      for k, lim in limits.items()}
    for k, lim in limits.items():
        print(f"check {k} {checks.get(k)!r} limit {lim!r} "
              f"(sampled {out['sampled']})", file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
