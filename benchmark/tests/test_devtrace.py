"""The trace reduction, checked without a chip: on a hand-made trace whose
numbers are known, and on a slice of a trace recorded on a TPU v5e
(``data/replay_trace.json.gz``, a ``--trace 1`` run of replay-agg512)
against a brute-force reading of the same events."""

import os

import numpy as np
import pytest

from benchmark import devtrace
from benchmark.devtrace import Op, Trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
W = devtrace.WINDOW_SPAN


def test_hand_made_trace():
    trace = Trace(
        devices={"/device:TPU:0": [
            Op("while", 0, 70, False),             # clipped to [10, 70)
            Op("mosaic", 20, 30, True),            # nested in the while
            Op("fusion", 55, 10, False),           # nested in the while
            Op("fusion", 100, 20, False),          # [100, 120)
            Op("fusion", 190, 40, False),          # clipped to [190, 210)
            Op("late", 300, 10, False),            # outside the window
        ]},
        spans=[(W, 10, 210), ("bench.verify_call", 0, 80),
               ("bench.wait", 80, 150), ("bench.verify_call", 150, 400)],
    )
    s = devtrace.reduce(trace)
    assert s.window_s == pytest.approx(200e-9)
    assert s.busy_s == pytest.approx((60 + 20 + 20) * 1e-9)
    assert s.mosaic_s == pytest.approx(30e-9)
    assert dict(s.ops) == pytest.approx(
        {"while": 20e-9, "mosaic": 30e-9, "fusion": 50e-9})
    # gaps [70, 100) under bench.wait, [120, 190) midpoint 155 under verify
    assert dict(s.idle_by_span) == pytest.approx(
        {"bench.wait": 30e-9, "bench.verify_call": 70e-9})


def test_short_name():
    assert devtrace.short_name(
        '%body.46 = f32[2,256,128]{2,1,0:T(8,128)S(1)} custom-call(f32[2,256,128]'
        '{2,1,0:T(8,128)S(1)} %pad.5952), custom_call_target="tpu_custom_call", '
        'frontend_attributes={kernel_metadata={}}'
    ) == "tpu_custom_call body f32[2,256,128]"
    assert devtrace.short_name(
        "%while.104 = (u32[]{:T(128)}, u32[2,25]{0,1:T(8,128)S(1)}) "
        "while((u32[]{:T(128)}, u32[2,25]{0,1:T(8,128)S(1)}) %tuple.16174), "
        "condition=%c, body=%b") == "while while tuple"
    assert devtrace.short_name(
        "%fusion.12 = u32[64,25]{1,0:T(8,128)} fusion(u32[64,25]{1,0} %p), "
        "kind=kLoop") == "fusion fusion u32[64,25]"


def test_no_window_or_no_device_reads_nothing():
    ops = {"/device:TPU:0": [Op("f", 0, 10, False)]}
    assert devtrace.reduce(Trace(devices=ops, spans=[])) is None
    assert devtrace.reduce(Trace(devices={}, spans=[(W, 0, 10)])) is None
    assert devtrace.reduce(Trace(devices=ops, spans=[(W, 20, 30)])) is None


def test_recorded_trace_against_brute_force():
    trace = Trace.read(os.path.join(DATA, "replay_trace.json.gz"))
    s = devtrace.reduce(trace)
    (w0, w1), = [(a, b) for n, a, b in trace.spans if n == W]
    # brute force: a boolean mask over every nanosecond of the window
    busy = mosaic = 0
    for ops in trace.devices.values():
        mask = np.zeros(int(w1 - w0), dtype=bool)
        for o in ops:
            a = int(max(o.start_ns, w0) - w0)
            b = int(min(o.start_ns + o.dur_ns, w1) - w0)
            if b > a:
                mask[a:b] = True
                mosaic += (b - a) * o.mosaic
        busy += int(mask.sum())
    n = len(trace.devices)
    # ns rounding of the mask: at most 1 ns per event edge
    tol = 2e-9 * sum(len(ops) for ops in trace.devices.values())
    assert s.busy_s == pytest.approx(busy / n * 1e-9, abs=tol)
    assert s.mosaic_s == pytest.approx(mosaic / n * 1e-9, abs=tol)
    assert 0 < s.mosaic_s <= s.busy_s < s.window_s
    assert sum(v for _, v in s.ops) == pytest.approx(s.busy_s, rel=1e-9)
    assert sum(v for _, v in s.idle_by_span) == pytest.approx(
        s.window_s - s.busy_s, rel=1e-9)
