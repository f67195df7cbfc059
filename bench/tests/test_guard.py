"""The config guard passes on the program as it is and refuses any change
to the sizes the configuration states."""

import copy
import json
import os

import pytest

from guards import lb4omp

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def cfg(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


def test_paper_grid_matches_program():
    assert lb4omp.mismatches(cfg("lb4omp_paper_grid")) == []


@pytest.mark.parametrize("path,value", [
    (("apps", "stream", "N"), 1_000_000),
    (("apps", "mandelbrot", "T"), 50),
    (("systems", "epyc", "P"), 64),
    (("systems", "broadwell", "noise_sigma"), 0.0),
    (("selector_grid",), [["RandomSel", None]]),
    (("selectors", "rl", "alpha"), 0.25),
    (("selectors", "rl", "reward_levels"), [0.0, -2.0, -4.0]),
    (("selectors", "exhaustive", "min_samples"), 5),
    (("selectors", "expert", "diff", "rules"), []),
    (("selectors", "simpolicy", "confidence_threshold"), 0.0),
])
def test_paper_grid_refuses_other_sizes(path, value):
    c = copy.deepcopy(cfg("lb4omp_paper_grid"))
    node = c
    for k in path[:-1]:
        node = node[k]
    node[path[-1]] = value
    assert lb4omp.mismatches(c)


def test_paper_grid_refuses_other_cost_pattern(monkeypatch):
    from repro.sim import workloads

    monkeypatch.setattr(workloads.Mandelbrot, "UNIT", 1.0e-6)
    assert lb4omp.mismatches(cfg("lb4omp_paper_grid"))
