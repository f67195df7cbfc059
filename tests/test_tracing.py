"""The program's ``repro.*`` profiler spans (``repro.tracing``): a 2-lane,
T=2 lockstep replay and one ``SimPolicy`` decision under
``jax.profiler.trace`` write every span of the catalogue, nested as the
call tree nests, with consistent stats, and tracing changes no result."""

import glob

import numpy as np
import pytest

import jax
from jax.profiler import ProfileData

from repro.core import SimPolicy
from repro.sim import LoopWhatIf, get_application, get_system
from repro.sim.backends.jax_batched import JaxBatchedBackend
from repro.sim.campaign import CellSpec, ReplayBatch
from repro.tracing import SPANS

LANES = [CellSpec("mandelbrot", "broadwell", "ExhaustiveSel"),
         CellSpec("mandelbrot", "broadwell", "ExpertSel")]


def _run():
    """A fresh backend (empty schedule caches, so builds happen), the
    replay, then one consultation; returns what both computed."""
    bk = JaxBatchedBackend(kernel="while_loop", data_parallel=1)
    runs = ReplayBatch(LANES, T=2, seed=3, backend=bk).run()
    wi = LoopWhatIf(get_system("broadwell"), backend=bk)
    wi.set_context(get_application("mandelbrot").loops(1)[0], 0)
    pol = SimPolicy(wi)
    d = pol.decide()
    return [r.history for r in runs], (d.action, d.chunk_param,
                                       pol._last_pred)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    off = _run()
    out = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(out)):
        on = _run()
    path, = glob.glob(str(out / "**" / "*.xplane.pb"), recursive=True)
    spans = []                  # (line, name, start, end, stats)
    for plane in ProfileData.from_file(path).planes:
        for k, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("repro."):
                    spans.append(((plane.name, k), ev.name[len("repro."):],
                                  ev.start_ns, ev.end_ns, dict(ev.stats)))
    return off, on, spans


def _inside(span, parents, spans):
    line, _, s, e, _ = span
    return any(p[0] == line and p[1] in parents and p[2] <= s and e <= p[3]
               for p in spans)


def test_every_catalogue_span_appears_with_its_stats(traced):
    _, _, spans = traced
    seen = {}
    for _, name, _, _, stats in spans:
        seen.setdefault(name, []).append(stats)
    assert set(seen) == set(SPANS)
    for name, keys in SPANS.items():
        for stats in seen[name]:
            assert set(keys) <= set(stats), (name, stats)


def test_spans_nest_as_the_calls_do(traced):
    _, _, spans = traced
    parents = {
        "replay.decide": {"replay.step"},
        "replay.learn": {"replay.step"},
        "backend.lockstep": {"replay.step"},
        "whatif.price": {"simpolicy.decide"},
        "backend.batch": {"whatif.price"},
        "backend.host_instances": {"backend.lockstep", "backend.batch"},
        "events.rows": {"backend.lockstep", "backend.batch"},
        "events.pack": {"backend.lockstep", "backend.batch"},
        "events.dispatch": {"backend.lockstep", "backend.batch"},
        "events.wait": {"backend.lockstep", "backend.batch"},
        "sched.build": {"events.rows"},
    }
    assert set(parents) | {"replay.step", "simpolicy.decide"} == set(SPANS)
    for sp in spans:
        if sp[1] in parents:
            assert _inside(sp, parents[sp[1]], spans), sp[1]
    # the replay's packing and device wait sit in run_lockstep, in the step
    steps = [sp for sp in spans if sp[1] == "replay.step"]
    lockstep = [sp for sp in spans if sp[1] == "backend.lockstep"]
    for sp in spans:
        if sp[1] in ("events.pack", "events.wait") and _inside(
                sp, {"backend.lockstep"}, spans):
            assert _inside(sp, {"replay.step"}, steps)
    assert len(steps) == 2 and lockstep


def test_stats_are_consistent(traced):
    _, _, spans = traced
    by = {}
    for _, name, _, _, stats in spans:
        by.setdefault(name, []).append(stats)
    inst = sorted(s["instances"] for s in by["backend.lockstep"]
                  + by["backend.batch"])
    parts = sorted(s["closed"] + s["event"]
                   for s in by["backend.host_instances"])
    assert inst == parts
    # 2 lanes x 3 mandelbrot loops per step; 24 candidates priced
    assert [s["requests"] for s in by["replay.decide"]] == [6, 6]
    assert [s["lanes"] for s in by["replay.step"]] == [2, 2]
    assert [s["t"] for s in by["replay.step"]] == [0, 1]
    assert [s["candidates"] for s in by["simpolicy.decide"]] == [24]
    assert [s["cached"] for s in by["whatif.price"]] == [0]
    for s in by["events.pack"]:
        assert 0 < s["real"] <= s["rows"]
        assert s["real"] <= s["chunks"] <= s["real"] * s["K"]
        # the precompute's live tiles hold every chunk, within the batch
        assert s["chunks"] <= s["live"] <= s["rows"] * s["K"]
    for s in by["events.dispatch"]:
        assert s["P"] == get_system("broadwell").P
    waits = sorted(s["rows"] for s in by["events.wait"])
    assert waits == sorted(s["rows"] for s in by["events.pack"])
    assert {s["kind"] for s in by["sched.build"]} <= {0, 1, 2}
    builds = len(by["sched.build"])
    misses = sum(s["sched_misses"] + s["steal_misses"]
                 for s in by["events.rows"])
    assert builds == misses > 0


def test_tracing_changes_no_result(traced):
    off, on, _ = traced
    assert off[1] == on[1]
    for h_off, h_on in zip(off[0], on[0]):
        assert h_off.keys() == h_on.keys()
        for loop in h_off:
            a, b = np.array(h_off[loop]), np.array(h_on[loop])
            assert a.tobytes() == b.tobytes()
