"""The program's own spans and device stages in a traced run, reduced to the
per-layer metrics of the batch-verify path.

``load`` reads the newest ``.xplane.pb`` under a trace directory (a
``--trace 1`` run traces into ``.bench_cache/trace/<workload>``) into a
compact ``ProgramTrace``: the traced window (the benchmark's
``bench.traced_window`` span), the program's host spans (``chain.*``,
``resilience.*``, ``bls.*``, written by ``lighthouse_tpu/utils/tracing.py``)
with their ``batch`` attribute, the executions of the three device stages
(``jit_bls_h2c``, ``jit_bls_prep``, ``jit_bls_pair``) and the device's
busy intervals, the union of its op events as ``devtrace`` reads them. A
``ProgramTrace`` saves to and loads from JSON, so the reductions are checked
on a small recorded trace without a chip (``tests/test_programtrace.py``).

What a TPU v5e trace holds (read with ``devtrace.describe`` from a
replay-agg512 ``--trace 1`` run, JAX 0.9). The ``/device:TPU:0`` plane has
an ``XLA Modules`` line with one event per execution of a compiled
program, named ``<module name>(<fingerprint>)``, e.g.
``jit_bls_h2c(1796...)``, beside ``XLA Ops`` (the ops, which ``devtrace``
reads), ``Async XLA Ops`` (copy-start/done) and two empty lines. A stage's
execution on ``XLA Modules`` is busy on ``XLA Ops`` but for a few
microseconds between its ops. The ``/host:CPU`` plane has one line per
host thread; the caller's spans and those of the supervisor's watchdog
worker lie on two lines, both named ``python3``. A ``TraceAnnotation``'s
keyword attributes are the event's stats, under their own names and with
their types (``batch``: int, ``stage``: str).

Every reduction reads only what lies inside the traced window and returns
None where the trace holds nothing to read (a program without these spans
or stage names), so that no metric reads 0 for lack of data.
"""

from __future__ import annotations

import functools
import gzip
import json
import os
import statistics
from dataclasses import dataclass, field

from . import devtrace

PROGRAM_PREFIXES = ("chain.", "resilience.", "bls.")
STAGES = ("jit_bls_h2c", "jit_bls_prep", "jit_bls_pair")
MODULE_LINE = "XLA Modules"


@dataclass
class ProgramTrace:
    window: tuple = (0.0, 0.0)                       # (start_ns, end_ns)
    spans: list = field(default_factory=list)        # [(name, s, e, batch)]
    stages: dict = field(default_factory=dict)       # device -> [(module, s, e)]
    busy: dict = field(default_factory=dict)         # device -> [(s, e)] merged

    def to_json(self) -> dict:
        return {"window": list(self.window),
                "spans": [list(s) for s in self.spans],
                "stages": {d: [list(m) for m in ms]
                           for d, ms in self.stages.items()},
                "busy": {d: [list(b) for b in bs] for d, bs in self.busy.items()}}

    @classmethod
    def from_json(cls, obj: dict) -> "ProgramTrace":
        return cls(window=tuple(obj["window"]),
                   spans=[tuple(s) for s in obj["spans"]],
                   stages={d: [tuple(m) for m in ms]
                           for d, ms in obj["stages"].items()},
                   busy={d: [tuple(b) for b in bs]
                         for d, bs in obj["busy"].items()})

    def save(self, path: str) -> None:
        with gzip.open(path, "wt") as fh:
            json.dump(self.to_json(), fh)

    @classmethod
    def read(cls, path: str) -> "ProgramTrace":
        with gzip.open(path, "rt") as fh:
            return cls.from_json(json.load(fh))


def merge(intervals) -> list:
    """Union of [s, e) intervals as sorted disjoint intervals."""
    out: list[list] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(iv) for iv in out]


def overlap(a: list, b: list) -> float:
    """Length of the intersection of two sorted disjoint interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def load(trace_dir: str) -> ProgramTrace | None:
    """The newest trace under ``trace_dir``; None without a window."""
    from jax.profiler import ProfileData

    ops = devtrace.load(trace_dir)
    windows = [(s, e) for n, s, e in ops.spans if n == devtrace.WINDOW_SPAN]
    if not windows:
        return None
    trace = ProgramTrace(window=windows[0])
    trace.busy = {d: merge((o.start_ns, o.start_ns + o.dur_ns) for o in os_)
                  for d, os_ in ops.devices.items()}
    data = ProfileData.from_file(devtrace._newest_xplane(trace_dir))
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            mods = []
            for line in plane.lines:
                if line.name != MODULE_LINE:
                    continue
                for ev in line.events:
                    name = ev.name.split("(")[0]
                    if name in STAGES:
                        mods.append((name, ev.start_ns,
                                     ev.start_ns + ev.duration_ns))
            trace.stages[plane.name] = sorted(mods, key=lambda m: m[1])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PROGRAM_PREFIXES):
                        trace.spans.append(
                            (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                             int(dict(ev.stats).get("batch", 0))))
    trace.spans.sort(key=lambda s: (s[1], -s[2]))
    return trace


@functools.lru_cache(maxsize=4)
def _load_cached(trace_dir: str, mtime_ns: int) -> ProgramTrace | None:
    return load(trace_dir)


def of_run(run) -> ProgramTrace | None:
    """The program trace of a traced run (``harness.Run``), read once for
    all the metrics that read it; None for an untraced run."""
    from .harness import CACHE

    if run.trace is None:
        return None
    trace_dir = os.path.join(CACHE, "trace", run.workload)
    try:
        path = devtrace._newest_xplane(trace_dir)
    except FileNotFoundError:
        return None
    return _load_cached(trace_dir, os.stat(path).st_mtime_ns)


def _inside(trace: ProgramTrace, name: str) -> list:
    w0, w1 = trace.window
    return [s for s in trace.spans if s[0] == name and s[1] >= w0 and s[2] <= w1]


def _idle(trace: ProgramTrace, device: str) -> list:
    """Sorted disjoint intervals of the window in which ``device`` ran
    nothing."""
    w0, w1 = trace.window
    out, t = [], w0
    for s, e in trace.busy[device]:
        if e <= w0 or s >= w1:
            continue
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if w1 > t:
        out.append((t, w1))
    return out


def host_prep_ms(trace: ProgramTrace) -> float | None:
    """Median duration of the ``bls.host_prep`` spans wholly inside the
    window, in ms."""
    spans = _inside(trace, "bls.host_prep")
    if not spans:
        return None
    return statistics.median(e - s for _, s, e, _ in spans) / 1e6


def idle_in(trace: ProgramTrace, name: str) -> float | None:
    """Share of the device's idle time in the window that falls inside a
    ``name`` span, in percent (mean over devices)."""
    w0, w1 = trace.window
    spans = merge((max(s, w0), min(e, w1)) for n, s, e, _ in trace.spans
                  if n == name and e > w0 and s < w1)
    if not spans or not trace.busy:
        return None
    shares = []
    for device in trace.busy:
        idle = _idle(trace, device)
        total = sum(e - s for s, e in idle)
        if total > 0:
            shares.append(100.0 * overlap(idle, spans) / total)
    return statistics.mean(shares) if shares else None


def stage_device_ms(trace: ProgramTrace, module: str) -> float | None:
    """Median device time of one execution of a stage's module (its event
    on the device's module line) wholly inside the window, in ms."""
    w0, w1 = trace.window
    times = [e - s for ms in trace.stages.values() for m, s, e in ms
             if m == module and s >= w0 and e <= w1]
    return statistics.median(times) / 1e6 if times else None


def idle_by_span(trace: ProgramTrace) -> dict:
    """The device's idle time in the window, in seconds (mean over
    devices), by the innermost program span open at each instant; what no
    program span covers is under ``"no program span"``."""
    w0, w1 = trace.window
    spans = [(s, e, n) for n, s, e, _ in trace.spans if e > w0 and s < w1]
    out: dict[str, float] = {}
    for device in trace.busy:
        for a, b in _idle(trace, device):
            cuts = sorted({a, b} | {t for s, e, _ in spans for t in (s, e)
                                    if a < t < b})
            for lo, hi in zip(cuts, cuts[1:]):
                open_ = [(s, -e, n) for s, e, n in spans if s <= lo and e >= hi]
                name = max(open_)[2] if open_ else "no program span"
                out[name] = out.get(name, 0.0) + (hi - lo) / 1e9
    n = max(len(trace.busy), 1)
    return {k: v / n for k, v in sorted(out.items(), key=lambda kv: -kv[1])}


def clock_checks(trace: ProgramTrace) -> dict:
    """Whether host spans and device events share a clock, for each call
    wholly inside the window: how far past the end of its call's
    ``bls.verify_indexed`` span each ``jit_bls_pair`` execution ends (ms,
    most), and how long before its call's ``bls.dispatch`` span began the
    first stage execution of each call starts (ms, most; 0 or less means
    none did)."""
    w0, w1 = trace.window
    dispatch = {b: s for n, s, _, b in _inside(trace, "bls.dispatch")}
    mods = sorted((s, e, m) for ms in trace.stages.values() for m, s, e in ms)
    late, early, n = [], [], 0
    prev_end = float("-inf")
    for name, v0, v1, batch in trace.spans:
        if name != "bls.verify_indexed":
            continue
        # a call's stage executions end after the previous call ended and
        # no later than 1 ms past its own end
        mine = [(s, e, m) for s, e, m in mods if prev_end < e <= v1 + 1e6]
        prev_end = v1
        if not (w0 <= v0 and v1 <= w1) or not mine or batch not in dispatch:
            continue
        n += 1
        early.append((dispatch[batch] - min(s for s, _, _ in mine)) / 1e6)
        late += [(e - v1) / 1e6 for _, e, m in mine if m == "jit_bls_pair"]
    return {"calls": n,
            "pair_end_past_call_ms": max(late) if late else None,
            "stage_start_before_dispatch_ms": max(early) if early else None}


def compile_seconds() -> float | None:
    """Sum of ``jax_compile_phase_seconds`` over every phase and function,
    as the program's registry exposes it; None where the program has no
    such counter."""
    from lighthouse_tpu.utils.metrics import REGISTRY

    sums = [float(line.rsplit(" ", 1)[1])
            for line in REGISTRY.render().splitlines()
            if line.startswith("jax_compile_phase_seconds_sum")]
    return sum(sums) if sums else None


if __name__ == "__main__":
    # python3 -m benchmark.programtrace <trace dir> [<out.json.gz>]: the
    # numbers of the newest trace, and optionally its compact form
    import sys

    t = load(sys.argv[1])
    if t is None:
        raise SystemExit("no traced window")
    print(json.dumps({
        "host_prep_ms": host_prep_ms(t),
        "idle_in_host_prep": idle_in(t, "bls.host_prep"),
        **{m: stage_device_ms(t, m) for m in STAGES},
        "idle_by_span": idle_by_span(t),
        "clock": clock_checks(t),
    }, indent=1))
    if len(sys.argv) > 2:
        t.save(sys.argv[2])
