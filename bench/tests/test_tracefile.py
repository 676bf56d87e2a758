"""The reduction from trace to numbers, on small traces with known answers
and on a trace recorded on the chip."""

import os

import pytest

from bench import tracefile
from bench.tracefile import Event

DEV = "/device:TPU:0"
HOST = "/host:CPU"
OPS = tracefile.OPS_LINE


def ev(plane, name, start, dur, line=OPS):
    return Event(plane, line if plane == DEV else "python", name, float(start), float(dur))


@pytest.fixture
def small():
    # window 0..1000 ns; device busy 100-300 (two overlapping ops), 500-600, 900-1100 (clipped)
    return [
        ev(HOST, tracefile.WINDOW_SPAN, 0, 1000),
        ev(HOST, "bench.step", 50, 600),
        ev(HOST, "bench.decode", 80, 250),
        ev(HOST, "bench.prefill", 400, 220),
        ev(DEV, "%while.2 = (s32[], bf16[8]) while(%tuple.1), body=%body", 100, 200),
        ev(DEV, "%fusion.12 = bf16[8] fusion(bf16[8] %p), kind=kLoop", 100, 150),
        ev(DEV, "fusion.7", 200, 100),
        ev(DEV, "%gqa_flash_attention.3 = bf16[16,384,64] custom-call(%a, %b, %c)", 500, 100),
        ev(DEV, "%fusion.9 = bf16[8] fusion(bf16[16,384,64] %gqa_flash_attention.3)", 600, 0),
        ev(DEV, "copy.1", 900, 200),
        ev(HOST, "not_ours", 0, 1000),
    ]


def test_busy_and_window(small):
    assert tracefile.window_seconds(small) == pytest.approx(1e-6)
    # union: 100-300, 500-600, 900-1000 (clipped) = 400 ns
    assert tracefile.busy_seconds(small) == pytest.approx(400e-9)


def test_kernel_by_stable_name(small):
    secs, n = tracefile.kernel_seconds(small, "flash_attention")
    assert n == 1 and secs == pytest.approx(100e-9)  # not the fusion that reads its output
    assert tracefile.kernel_seconds(small, "no_such_kernel") == (0.0, 0)


def test_top_ops_merge_instances(small):
    top = dict(tracefile.top_ops(small))
    assert "while" not in top  # a loop's event spans its body's events
    assert top["fusion"] == pytest.approx(250e-9)
    assert top["gqa_flash_attention"] == pytest.approx(100e-9)
    assert top["copy"] == pytest.approx(100e-9)


def test_idle_gaps_by_host_span(small):
    gaps = dict(tracefile.idle_gaps(small))
    # 0-100: step (mid 50 is in step only from 50 on) -> step; 300-500: mid 400 -> prefill
    # 600-900: mid 750 -> no bench span open
    assert gaps["bench.step"] == pytest.approx(100e-9)
    assert gaps["bench.prefill"] == pytest.approx(200e-9)
    assert gaps["host.other"] == pytest.approx(300e-9)
    assert sum(gaps.values()) == pytest.approx(600e-9)


def test_no_device_ops_means_no_busy_time():
    assert tracefile.busy_seconds([ev(HOST, tracefile.WINDOW_SPAN, 0, 10)]) is None


RECORDED = os.path.join(os.path.dirname(__file__), "data", "trace_events.json")


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded chip trace in bench/tests/data")
def test_recorded_chip_trace():
    events = tracefile.read_saved(RECORDED)
    w = tracefile.window_seconds(events)
    busy = tracefile.busy_seconds(events)
    assert 0 < busy <= w
    gaps = tracefile.idle_gaps(events)
    assert sum(s for _, s in gaps) == pytest.approx(w - busy, rel=1e-6, abs=1e-9)
    top = tracefile.top_ops(events)
    assert 0 < len(top) <= 10 and all(s > 0 for _, s in top)
