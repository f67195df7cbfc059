"""The program's spans and device scopes (``harness.program``) and the
per-layer metrics that read them: a small hand-made trace of each cell whose
answers are worked out by hand, a trace without the program's spans, and a
traced CPU run of each cell at small sizes."""

import json
import os

import pytest

import run
from harness.program import attach, reduce_program, resolve_scopes
from harness.trace import reduce_planes
from test_faults import SMALL

MS = 1_000_000       # ns
JIT = "jit(_batched_events_impl)/"


def _span(name, lo, hi, **stats):
    return (name, lo * MS, (hi - lo) * MS, stats)


def _op(name, lo, hi):
    return (name, lo * MS, (hi - lo) * MS, {})


#: what the compiled event program says of the hand-made operations
PATHS = {"fusion.1": {JIT + "precompute/mul"},
         "fusion.2": {JIT + "precompute/add"},
         "event_finish": {JIT + "event_core/jit(event_finish)/pallas"},
         "reduce.4": {JIT + "event_core/reduce_max"}}


def campaign_planes():
    host = ("/host:CPU", [("python", [
        _span("bench.window", 0, 100),
        _span("bench.ReplayBatch.step", 0, 60),
        _span("repro.replay.step", 0, 60, t=0, lanes=2),
        _span("repro.replay.decide", 0, 10, requests=4),
        _span("bench.run_lockstep", 10, 50),
        _span("repro.backend.lockstep", 10, 50, instances=4),
        _span("repro.backend.host_instances", 10, 14, closed=1, event=3),
        _span("repro.events.rows", 14, 24, sched_hits=1, sched_misses=1,
              steal_hits=0, steal_misses=0),
        _span("repro.sched.build", 16, 20, kind=0),
        _span("repro.events.pack", 24, 28, K=256, rows=8, real=3,
              chunks=300),
        _span("repro.events.dispatch", 28, 30, P=20, K=256, rows=8,
              grid_rows=16, grid_cols=129),
        _span("repro.events.wait", 30, 50, rows=8),
        _span("repro.replay.learn", 50, 58, lanes=2),
        _span("bench.ReplayBatch.step", 70, 100),
        _span("repro.replay.step", 70, 100, t=1, lanes=2),
        _span("repro.replay.decide", 70, 80, requests=4),
        _span("repro.backend.lockstep", 80, 100, instances=4),
        _span("repro.events.pack", 85, 90, K=1024, rows=16, real=10,
              chunks=5000),
        _span("repro.events.wait", 90, 110, rows=16),   # past the window
    ])])
    tpu = ("/device:TPU:0", [("XLA Modules", [
        _span("jit__batched_events_impl", 30, 48),
        _span("jit__batched_events_impl", 70, 72),
        _span("jit__batched_events_impl", 95, 105)]), ("XLA Ops", [
        _op("fusion.1", 30, 40),
        _op("fusion.2", 40, 45),
        _op("event_finish", 45, 48),
        _op("copy.3", 52, 58),                  # outside the event program
        _op("fusion.1", 70, 72),
        _op("reduce.4", 95, 105),
    ])])
    return [host, tpu]


def consult_planes():
    host = ("/host:CPU", [("python", [
        _span("bench.window", 0, 50),
        _span("bench.SimPolicy.decide", 0, 20),
        _span("repro.simpolicy.decide", 0, 20, candidates=24),
        _span("repro.whatif.price", 1, 19, cached=0),
        _span("bench.run_batch", 2, 18),
        _span("repro.backend.batch", 2, 18, instances=24),
        _span("repro.backend.host_instances", 2, 4, closed=2, event=22),
        _span("repro.events.rows", 4, 10, sched_hits=20, sched_misses=0,
              steal_hits=0, steal_misses=2),
        _span("repro.sched.build", 5, 9, kind=1),
        _span("repro.events.pack", 10, 12, K=4096, rows=32, real=22,
              chunks=9000),
        _span("repro.events.dispatch", 12, 13, P=20, K=4096, rows=32,
              grid_rows=8, grid_cols=129),
        _span("repro.events.wait", 13, 18, rows=32),
        _span("bench.SimPolicy.decide", 25, 45),
        _span("repro.simpolicy.decide", 25, 45, candidates=24),
        _span("repro.whatif.price", 26, 44, cached=0),
        _span("repro.backend.batch", 27, 43, instances=24),
        _span("repro.backend.host_instances", 27, 28, closed=2, event=22),
        _span("repro.events.rows", 28, 30, sched_hits=20, sched_misses=0,
              steal_hits=2, steal_misses=0),
        _span("repro.events.pack", 30, 33, K=4096, rows=32, real=22,
              chunks=9000),
        _span("repro.events.dispatch", 33, 34, P=20, K=4096, rows=32,
              grid_rows=8, grid_cols=129),
        _span("repro.events.wait", 34, 43, rows=32),
    ])])
    tpu = ("/device:TPU:0", [("XLA Ops", [
        _op("fusion.1", 13, 16),
        _op("event_finish", 16, 17),
    ])])
    return [host, tpu]


class Backend:
    event_core = "while_loop"
    mesh = None


class Driver:
    bk = Backend()


class Ctx:
    def __init__(self, planes, with_program=True, paths=PATHS):
        self.notes = {}
        self.driver = Driver()
        self.trace = reduce_planes(
            [(p, [(ln, [ev[:3] for ev in evs]) for ln, evs in lines])
             for p, lines in planes], n_devices=1)
        if with_program:
            prog = attach(self, reduce_program(planes, n_devices=1))
            prog.hlo_paths = dict(paths)
            resolve_scopes(self, prog)


def metric(name):
    return run.load_file(os.path.join(run.BENCH, "metrics", f"{name}.py"),
                         f"metric_{name}").read


def test_campaign_readers_by_hand():
    ctx = Ctx(campaign_planes())
    assert metric("closed_form_share.campaign")(ctx) == pytest.approx(4.0)
    # rows 14-24 and pack 24-28 less the build 16-20, then pack 85-90
    assert metric("pack_share.campaign")(ctx) == pytest.approx(15.0)
    # wait 30-50 and 90-110 clipped to the window's end
    assert metric("device_wait_share.campaign")(ctx) == pytest.approx(30.0)
    assert metric("dispatch_share.campaign")(ctx) == pytest.approx(2.0)
    # fusion.1, fusion.2 and the second fusion.1: 10 + 5 + 2 ms
    assert metric("precompute_share.campaign")(ctx) == pytest.approx(17.0)
    assert ctx.notes["precompute_s"] == pytest.approx(0.017)
    assert ctx.notes["event_core_s"] == pytest.approx(0.008)
    assert metric("pad_efficiency.campaign")(ctx) == pytest.approx(
        100 * 5300 / (8 * 256 + 16 * 1024))
    assert (ctx.notes["pad_chunks"], ctx.notes["pad_slots"]) == (5300,
                                                                18432)


def test_scopes_from_the_compiled_program():
    """The scopes come from the compiled event program's instructions, by
    name where the programs agree, and only inside its module."""
    planes = campaign_planes()
    ctx = Ctx(planes, with_program=False)
    prog = attach(ctx, reduce_program(planes, n_devices=1))
    assert prog.dispatch_shapes() == [(20, 256, 8, 16, 129)]
    assert prog.scope_seconds("precompute") is None
    prog.hlo_paths = {"fusion.1": {JIT + "precompute/mul"},
                      "fusion.2": {JIT + "precompute/add",
                                   JIT + "event_core/add"},
                      "event_finish": {JIT + "event_core/pallas"},
                      "copy.3": {JIT + "precompute/copy"},
                      "reduce.4": {""}}
    resolve_scopes(ctx, prog)
    # fusion.1 twice (12 ms); fusion.2's programs disagree, so no scope;
    # reduce.4 carries no op name; copy.3 runs outside the event program's
    # module
    assert metric("precompute_share.campaign")(ctx) == pytest.approx(12.0)
    assert ctx.notes["event_core_s"] == pytest.approx(0.003)
    assert ctx.notes["top_ops_by_scope"]["fusion.1"] == {
        "precompute": pytest.approx(0.012)}
    assert ctx.notes["top_ops_by_scope"]["copy.3"] == {
        "other": pytest.approx(0.006)}
    assert ctx.notes["top_ops_by_scope"]["fusion.2"] == {
        "": pytest.approx(0.005)}
    assert (ctx.notes["unmatched_op_s"], ctx.notes["events_op_s"]) == (
        0.0, pytest.approx(0.025))


@pytest.mark.parametrize("missing", [("reduce.4",), tuple(PATHS)])
def test_ops_the_compiled_programs_do_not_name_read_none(missing):
    """Where the compiled programs' names do not match the trace's (a later
    change of the program's arguments or compile options), the event
    program's operations are unmatched, not unscoped: past 1% of its device
    time the scope metric reads None."""
    planes = campaign_planes()
    paths = {k: v for k, v in PATHS.items() if k not in missing}
    paths.update({k + "_renamed": v for k, v in PATHS.items()
                  if k in missing})
    ctx = Ctx(planes, paths=paths)
    assert metric("precompute_share.campaign")(ctx) is None
    assert ctx.notes["precompute_s"] is None
    # reduce.4 ran 5 of the event program's 25 ms in the window
    lost = 0.005 if missing == ("reduce.4",) else 0.025
    assert ctx.notes["unmatched_op_s"] == pytest.approx(lost)
    assert ctx.notes["events_op_s"] == pytest.approx(0.025)
    # the program's spans read as before
    assert metric("pack_share.campaign")(ctx) == pytest.approx(15.0)


def test_op_keys_and_instruction_paths():
    from harness.scopes import instruction_paths, op_key

    long = ("%fusion.3 = f32[2097152]{0:T(1024)S(1)} fusion(f32[16,16385]"
            "{1,0:T(8,128)S(1)} %copy-done), kind=kCustom, "
            "calls=%fused_computation.3")
    assert op_key(long) == "fusion.3 f32[2097152]{0:T(1024)S(1)}"
    assert op_key("fusion.3") == "fusion.3"
    assert op_key("%t = (s32[], f32[8]{0}) tuple(%a, %b)") == "t"
    text = ('  ROOT %fusion.3 = f32[2097152]{0:T(1024)S(1)} fusion(%c, %b), '
            'kind=kCustom, metadata={op_name="jit(f)/precompute/gather" '
            'stack_frame_id=3}\n  %p = f32[8]{0} parameter(0)\n')
    assert instruction_paths(text) == {
        "fusion.3": "jit(f)/precompute/gather",
        "fusion.3 f32[2097152]{0:T(1024)S(1)}": "jit(f)/precompute/gather",
        "p": "", "p f32[8]{0}": ""}


def test_instruction_paths_of_the_event_program():
    """The event program's compiled HLO names both scopes (CPU compile of a
    small shape)."""
    from harness.scopes import event_program_paths

    paths = event_program_paths("while_loop", [(20, 256, 8, 8, 129)])
    scopes = {p.split("/")[1] for ps in paths.values() for p in ps
              if p.startswith("jit(_batched_events_impl)/")}
    assert {"precompute", "event_core"} <= scopes


def test_gaps_are_named_by_the_innermost_span():
    ctx = Ctx(campaign_planes())
    # busy 30-48, 52-58, 70-72, 95-100: gaps 0-30 (middle 15, in the rows
    # span inside run_lockstep), 72-95 (83.5, in backend.lockstep), 58-70
    # (64, between the steps: the window), 48-52 (50, in replay.learn)
    assert ctx.notes["idle_gaps_named"] == [
        ["repro.events.rows", pytest.approx(0.030)],
        ["repro.backend.lockstep", pytest.approx(0.023)],
        ["window", pytest.approx(0.012)],
        ["repro.replay.learn", pytest.approx(0.004)]]
    assert ctx.notes["window_gap_s"] == pytest.approx(0.012)
    # decide, lockstep and learn cover 88 of the steps' 90 ms; the backend's
    # phases cover all of run_lockstep
    assert ctx.notes["step_cover"] == pytest.approx(88 / 90)
    assert ctx.notes["lockstep_cover"] == pytest.approx(1.0)
    # the benchmark's own breakdown is unchanged
    assert ctx.trace.breakdown()["idle_gaps"][0][0] == "run_lockstep"


def test_consult_readers_by_hand():
    ctx = Ctx(consult_planes())
    # over 40 ms of decisions
    assert metric("closed_form_share.consult")(ctx) == pytest.approx(7.5)
    assert metric("schedule_build_share.consult")(ctx) == pytest.approx(
        10.0)
    assert metric("pack_share.consult")(ctx) == pytest.approx(22.5)
    assert metric("device_wait_share.consult")(ctx) == pytest.approx(35.0)
    assert metric("dispatch_share.consult")(ctx) == pytest.approx(5.0)
    assert ctx.notes["decide_cover"] == pytest.approx(36 / 40)
    assert ctx.notes["batch_cover"] == pytest.approx(1.0)


NEW = {m["name"]: m for m in run.load_json(run.ROOT, "BENCHMARK.json")[
    "per_layer"] if m["name"].split(".")[0] in (
        "closed_form_share", "pack_share", "device_wait_share",
        "precompute_share", "pad_efficiency", "schedule_build_share",
        "dispatch_share")}


def test_eleven_new_metrics_with_readers():
    assert len(NEW) == 11
    for name in NEW:
        assert os.path.exists(os.path.join(run.BENCH, "metrics",
                                           f"{name}.py"))


@pytest.mark.parametrize("planes", [campaign_planes, consult_planes])
def test_a_program_without_spans_reads_none(planes):
    """The parent program writes no ``repro.*`` span and no scope: every new
    reader returns None and none raises."""
    bare = [(p, [(ln, [ev[:3] + ({},) for ev in evs
                       if not ev[0].startswith("repro.")])
                 for ln, evs in lines]) for p, lines in planes()]
    ctx = Ctx(bare, paths={})
    for name in NEW:
        assert metric(name)(ctx) is None, name


@pytest.mark.parametrize("workload", sorted(SMALL))
def test_traced_cpu_run_prints_every_new_metric(capsys, workload):
    rc = run.main(["--workload", workload, "--seed", "3141592653",
                   "--seconds", "2", "--trace", "1"], require_tpu=False,
                  traffic_override=SMALL[workload])
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    line = json.loads(out[-1])
    assert line["correct"] is True
    for name, m in NEW.items():
        if workload not in m["workloads"] or m["source"] == "device_trace":
            continue
        value = line["metrics"][name]["value"]
        assert 0.0 <= value <= 100.0, (name, value)
    notes = json.loads(out[-2])
    cover = notes["step_cover" if "campaign" in workload else "decide_cover"]
    assert cover > 0.9
