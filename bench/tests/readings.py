"""Readings that set a cell's limits: the program's numbers over many seeds
and the control's on the same samples, with one set-up in one process.

    python bench/tests/readings.py --workload <cell> --seeds 21 22 ... \\
        --seconds 5 [--faults altered same_choice ... --fault-seeds 31 32 33]

Per seed it prints one JSON line: the numbers compared for the program's
window (``checks``) and for the control, the reference computed in bfloat16
in the program's place (``control``).  Then, for each fault named (from
``faults.py``) and each fault seed, one line of the numbers with that fault
planted under the timed path.  The benchmark's own runs never run the
control or a fault.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import run  # noqa: E402

import faults  # noqa: E402


def main(argv=None, require_tpu: bool = True, traffic_override=None) -> list:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--faults", nargs="*", default=[])
    ap.add_argument("--fault-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    _, ctx, _ = run.prepare(args.workload, args.seeds[0], False, require_tpu,
                            traffic_override)
    driver = ctx.driver
    driver.setup()
    print(json.dumps({"workload": args.workload,
                      "setup_s": time.perf_counter() - run.T_START,
                      "compiles": len(ctx.compiles.events)}), flush=True)
    out = []
    for seed in args.seeds:
        driver.reset(seed)
        res = driver.window(args.seconds)
        got = driver.check(control=True)
        rec = {"workload": args.workload, "seed": seed,
               "attempted": got["attempted"], "failed": got["failed"],
               "sampled": got["sampled"], "checks": got["checks"],
               "control": got["control"],
               "correct": run.judge(got, ctx.traffic["check"]["limits"]),
               **{k: v for k, v in res.items() if not k.startswith("_")}}
        print(json.dumps(rec), flush=True)
        out.append(rec)
    known = {f.__name__: f for f in faults.FAULTS[args.workload]}
    for name in args.faults:
        for seed in args.fault_seeds:
            with known[name](driver):
                driver.reset(seed)
                driver.window(args.seconds)
            got = driver.check()
            rec = {"workload": args.workload, "fault": name, "seed": seed,
                   "sampled": got["sampled"], "checks": got["checks"],
                   "correct": run.judge(got, ctx.traffic["check"]["limits"])}
            print(json.dumps(rec), flush=True)
            out.append(rec)
    return out


if __name__ == "__main__":
    main()
