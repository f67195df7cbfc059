"""Drive whole runs on the CPU, with the chip check skipped, and break the
timed path underneath: each fault a cell can have must turn ``correct``
false, and the unbroken run must stay correct."""

import contextlib
import json

import pytest

import run
from faults import FAULTS

SMALL = {
    "campaign_lockstep": {"apps": ["tc", "hacc"], "systems": ["broadwell"]},
    "consult_simpolicy": {"systems": ["broadwell"],
                          "loops": [["mandelbrot", 1]]},
}


def _result(capsys, workload, fault=None):
    with contextlib.ExitStack() as stack:
        hooks = None if fault is None else (
            lambda d: stack.enter_context(fault(d)))
        rc = run.main(["--workload", workload, "--seed", "2718281828",
                       "--seconds", "2", "--trace", "0"], require_tpu=False,
                      traffic_override=SMALL[workload], hooks=hooks)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("workload", sorted(SMALL))
def test_sound_run_is_correct(capsys, workload):
    line = _result(capsys, workload)
    assert line["correct"] is True, line
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("workload,fault", [
    (w, f) for w in sorted(FAULTS) for f in FAULTS[w]],
    ids=lambda x: getattr(x, "__name__", x))
def test_fault_is_caught(capsys, workload, fault):
    line = _result(capsys, workload, fault)
    assert line["correct"] is False, line
