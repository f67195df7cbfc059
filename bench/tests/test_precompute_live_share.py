"""``precompute_live_share.campaign``: the share of the padded slots that
the precompute's live tiles cover, read from the ``live`` stat of the
window's ``repro.events.pack`` spans, worked out by hand; a program whose
pack spans carry no ``live`` (the parent of the change that added it) reads
None."""

import pytest

from test_program_trace import Ctx, _op, _span, campaign_planes, metric

READ = "precompute_live_share.campaign"


def live_planes():
    host = ("/host:CPU", [("python", [
        _span("bench.window", 0, 100),
        _span("repro.backend.lockstep", 0, 60, instances=40),
        # one lane of 65538 chunks in an (8, 262144) batch: 129 tiles of
        # 8 x 512
        _span("repro.events.pack", 2, 6, K=262144, rows=8, real=1,
              chunks=65538, live=129 * 8 * 512),
        # 16 rows of 4096, one row block full and one empty
        _span("repro.events.pack", 10, 12, K=4096, rows=16, real=8,
              chunks=20000, live=8 * 4096),
        _span("repro.events.pack", 98, 104, K=256, rows=8, real=2,
              chunks=300, live=8 * 256),
        # starts past the window's end: not counted
        _span("repro.events.pack", 101, 103, K=256, rows=8, real=8,
              chunks=2048, live=8 * 256),
    ])])
    tpu = ("/device:TPU:0", [("XLA Ops", [_op("fusion.1", 6, 9)])])
    return [host, tpu]


def test_live_share_by_hand():
    ctx = Ctx(live_planes())
    live = 129 * 8 * 512 + 8 * 4096 + 8 * 256
    slots = 8 * 262144 + 16 * 4096 + 8 * 256
    assert metric(READ)(ctx) == pytest.approx(100.0 * live / slots)
    assert ctx.notes["live_slots"] == live
    # padding efficiency reads the same spans: chunks over slots
    assert metric("pad_efficiency.campaign")(ctx) == pytest.approx(
        100.0 * (65538 + 20000 + 300) / slots)


def test_pack_spans_without_live_read_none():
    assert metric(READ)(Ctx(campaign_planes())) is None
