"""The control, the reference computed in bfloat16 in the program's place,
fails one of each cell's limits on the cell's own sampled outputs, while the
program (float32, as the configuration states) passes them all."""

import pytest

import readings
from test_faults import SMALL


@pytest.mark.parametrize("workload", sorted(SMALL))
def test_control_fails_the_limit(workload):
    rec, = readings.main(["--workload", workload, "--seeds", "31415926535",
                          "--seconds", "2"], require_tpu=False,
                         traffic_override=SMALL[workload])
    limits = run_limits(workload)
    assert rec["correct"], rec
    for k, lim in limits.items():
        assert rec["checks"][k] <= lim
    assert any(rec["control"][k] > limits[k] for k in rec["control"])


def run_limits(workload):
    import json
    import os

    import run

    spec = run.load_json(run.ROOT, "BENCHMARK.json")
    cell = {w["name"]: w for w in spec["workloads"]}[workload]
    with open(os.path.join(run.BENCH, "traffic",
                           f"{cell['traffic']}.json")) as f:
        return json.load(f)["check"]["limits"]
