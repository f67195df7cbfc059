"""Trace reduction: busy union, idle share, kernel time and the breakdown,
on a small hand-made trace whose answers are worked out by hand."""

import pytest

from harness.trace import reduce_planes, short_name, union

MS = 1_000_000       # ns
KERNEL = ('%custom-call.4 = f32[20,128]{1,0} custom-call(f32[512,128]{1,0} '
          '%a), custom_call_target="tpu_custom_call"')


def planes():
    host = ("/host:CPU", [("python", [
        ("bench.window", 0, 100 * MS),
        ("bench.ReplayBatch.step", 0, 60 * MS),
        ("bench.run_lockstep", 40 * MS, 20 * MS),
        ("bench.ReplayBatch.step", 60 * MS, 40 * MS),
    ])])
    tpu0 = ("/device:TPU:0", [
        ("XLA Modules", [("jit__batched_events", 40 * MS, 30 * MS)]),
        ("XLA Ops", [
            ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", 40 * MS, 5 * MS),
            (KERNEL, 44 * MS, 10 * MS),            # overlaps fusion.1
            ("%copy.2 = f32[8]{0} copy(f32[8]{0} %q)", 70 * MS, 5 * MS),
            (KERNEL, 95 * MS, 10 * MS),            # half past the window
        ])])
    tpu1 = ("/device:TPU:1", [("XLA Ops", [("fusion.9", 0, 50 * MS)])])
    return [host, tpu1, tpu0]


def test_short_name():
    assert short_name(KERNEL) == "custom-call.4"
    assert short_name("jit_step") == "jit_step"


def test_union_merges_overlaps():
    assert union([(5, 9), (0, 3), (2, 4), (9, 10)]) == [(0, 4), (5, 10)]


def test_busy_idle_and_kernel_time():
    red = reduce_planes(planes(), n_devices=1)
    assert red.window_s == pytest.approx(0.1)
    # TPU:0 busy 40-54, 70-75, 95-100 (clipped): 24 ms
    assert red.busy_s == pytest.approx(0.024)
    assert red.idle_share == pytest.approx(0.76)
    assert red.kernel_s == pytest.approx(0.015)


def test_busy_is_averaged_over_the_chips_used():
    red = reduce_planes(planes(), n_devices=2)
    assert red.busy_s == pytest.approx((0.024 + 0.050) / 2)


def test_breakdown_names_gaps_by_the_host_span():
    bd = reduce_planes(planes(), n_devices=1).breakdown()
    assert bd["device_ops"][0] == ["custom-call.4", pytest.approx(0.015)]
    gaps = bd["idle_gaps"]
    # 0-40 in the step's decide part, 75-95 in the next step, 54-70
    # straddles the end of run_lockstep (its middle, 62, is in the step)
    assert gaps[0] == ["ReplayBatch.step", pytest.approx(0.040)]
    assert [g[1] for g in gaps] == pytest.approx([0.040, 0.020, 0.016])


def test_a_trace_without_a_window_is_refused():
    with pytest.raises(ValueError):
        reduce_planes([("/host:CPU", [("python", [("x", 0, 1)])])], 1)


def test_recorded_chip_trace():
    """A 0.3 s traced consult_simpolicy window recorded on a TPU v5e (its
    device operations and benchmark annotations, times from the window's
    start): the reduction gives what that run printed."""
    import gzip
    import json
    import os

    path = os.path.join(os.path.dirname(__file__), "data",
                        "consult_trace.json.gz")
    with gzip.open(path, "rt") as f:
        red = reduce_planes(json.load(f), n_devices=1)
    assert red.window_s == pytest.approx(0.309353723, rel=1e-12)
    assert red.busy_s == pytest.approx(0.075532348, rel=1e-12)
    assert 100 * red.idle_share == pytest.approx(75.58382447525935)
    assert red.kernel_s == pytest.approx(0.002224575)
    bd = red.breakdown()
    assert bd["device_ops"][0] == ["while.22", pytest.approx(0.033782796)]
    assert bd["idle_gaps"][0] == ["run_batch", pytest.approx(0.007809071)]
