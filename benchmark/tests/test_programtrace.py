"""The reduction of the program's spans and stage executions, checked
without a chip: on a hand-made trace whose numbers are known, and on a
slice of a trace recorded on a TPU v5e
(``data/replay_program_trace.json.gz``, a ``--trace 1`` run of
replay-agg512) against a brute-force reading of the same events."""

import os

import numpy as np
import pytest

from benchmark import programtrace
from benchmark.programtrace import ProgramTrace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
DEV = "/device:TPU:0"


def hand_made() -> ProgramTrace:
    """Two calls inside the window [100, 1000), one cut by its start."""
    spans = []
    for batch, (c0, prep_end, disp, c1) in enumerate(
            [(50, 120, 125, 300), (400, 520, 525, 800)], start=1):
        spans += [("chain.batch_verify", c0, c1 + 5, batch),
                  ("resilience.rung", c0 + 1, c1 + 4, batch),
                  ("bls.verify_indexed", c0 + 2, c1, batch),
                  ("bls.host_prep", c0 + 2, prep_end, batch),
                  ("bls.dispatch", disp, disp + 10, batch),
                  ("bls.await_verdict", disp + 10, c1, batch)]
    return ProgramTrace(
        window=(100, 1000),
        spans=sorted(spans, key=lambda s: (s[1], -s[2])),
        stages={DEV: [("jit_bls_h2c", 130, 160), ("jit_bls_prep", 160, 240),
                      ("jit_bls_pair", 240, 299),
                      ("jit_bls_h2c", 530, 570), ("jit_bls_prep", 570, 650),
                      ("jit_bls_pair", 650, 800.5)]},
        busy={DEV: [(60, 90), (130, 299), (530, 800.5), (900, 950)]},
    )


def test_hand_made_trace():
    t = hand_made()
    # only the second call's host prep lies wholly inside the window
    assert programtrace.host_prep_ms(t) == pytest.approx((520 - 402) / 1e6)
    # idle in the window: [100,130) [299,530) [800.5,900) [950,1000)
    idle = 30 + 231 + 99.5 + 50
    in_prep = (120 - 100) + (520 - 402)
    assert programtrace.idle_in(t, "bls.host_prep") == pytest.approx(
        100 * in_prep / idle)
    assert programtrace.stage_device_ms(t, "jit_bls_h2c") == pytest.approx(
        statistics_median([30, 40]) / 1e6)
    assert programtrace.stage_device_ms(t, "jit_bls_pair") == pytest.approx(
        statistics_median([59, 150.5]) / 1e6)
    assert programtrace.stage_device_ms(t, "jit_run") is None
    by = programtrace.idle_by_span(t)
    assert sum(by.values()) == pytest.approx(idle / 1e9)
    assert by["bls.host_prep"] == pytest.approx(in_prep / 1e9)
    # [120,125) and [520,525): between host prep and dispatch
    assert by["bls.verify_indexed"] == pytest.approx(10 / 1e9)
    # [125,130) and [525,530): dispatched, the device not yet started
    assert by["bls.dispatch"] == pytest.approx(10 / 1e9)
    # [299,300) awaiting; the rung around the call: [300,304), [401,402),
    # [800.5,804); the caller around the rung: [304,305), [400,401),
    # [804,805)
    assert by["bls.await_verdict"] == pytest.approx(1 / 1e9)
    assert by["resilience.rung"] == pytest.approx((4 + 1 + 3.5) / 1e9)
    assert by["chain.batch_verify"] == pytest.approx(3 / 1e9)
    assert by["no program span"] == pytest.approx(
        (400 - 305 + 900 - 805 + 50) / 1e9)
    clock = programtrace.clock_checks(t)
    assert clock == {"calls": 1, "pair_end_past_call_ms": 0.5 / 1e6,
                     "stage_start_before_dispatch_ms": (525 - 530) / 1e6}


def statistics_median(xs):
    return float(np.median(xs))


def test_nothing_to_read_reads_none():
    t = ProgramTrace(window=(0, 10), busy={DEV: [(0, 5)]})
    assert programtrace.host_prep_ms(t) is None
    assert programtrace.idle_in(t, "bls.host_prep") is None
    assert programtrace.stage_device_ms(t, "jit_bls_h2c") is None
    assert programtrace.clock_checks(t)["calls"] == 0


def test_json_round_trip(tmp_path):
    t = hand_made()
    path = str(tmp_path / "t.json.gz")
    t.save(path)
    assert ProgramTrace.read(path) == t


def test_recorded_trace_against_brute_force():
    t = ProgramTrace.read(os.path.join(DATA, "replay_program_trace.json.gz"))
    w0, w1 = (int(x) for x in t.window)
    n = w1 - w0
    # brute force: boolean masks over every nanosecond of the window
    busy = np.zeros(n, dtype=bool)
    for s, e in t.busy[DEV]:
        busy[max(int(s) - w0, 0):max(int(e) - w0, 0)] = True
    prep = np.zeros(n, dtype=bool)
    for name, s, e, _ in t.spans:
        if name == "bls.host_prep":
            prep[max(int(s) - w0, 0):max(int(e) - w0, 0)] = True
    idle = ~busy
    want = 100.0 * (idle & prep).sum() / idle.sum()
    # ns rounding of the masks: at most 1 ns per interval edge
    tol = 100.0 * 2 * (len(t.busy[DEV]) + len(t.spans)) / idle.sum()
    assert programtrace.idle_in(t, "bls.host_prep") == pytest.approx(
        want, abs=tol)
    assert sum(programtrace.idle_by_span(t).values()) == pytest.approx(
        idle.sum() / 1e9, abs=2e-9 * len(t.busy[DEV]))
    preps = [e - s for name, s, e, _ in t.spans
             if name == "bls.host_prep" and s >= w0 and e <= w1]
    assert programtrace.host_prep_ms(t) == pytest.approx(
        float(np.median(preps)) / 1e6)
    for module in programtrace.STAGES:
        runs = [e - s for m, s, e in t.stages[DEV]
                if m == module and s >= w0 and e <= w1]
        assert runs, module
        assert programtrace.stage_device_ms(t, module) == pytest.approx(
            float(np.median(runs)) / 1e6)
        # the device is busy through a stage's execution, but for the
        # microseconds between its ops
        assert all(busy[int(s) - w0:int(e) - w0].mean() > 0.999
                   for m, s, e in t.stages[DEV]
                   if m == module and s >= w0 and e <= w1)
