"""The device trace of a traced run, and its reduction to numbers.

``Capture`` runs ``jax.profiler`` over the last stretch of the measured
window and marks that stretch with the host span ``bench.traced_window``.
``load`` reads the ``.xplane.pb`` the profiler wrote into a compact
``Trace``: the device ops of each chip and the benchmark's own host spans
(``bench.*`` TraceAnnotations). ``reduce`` turns a ``Trace`` into busy time,
idle gaps named by what the host was doing, self time per op, and time in
Mosaic kernels. A ``Trace`` saves to and loads from JSON, so the reduction
is checked on a small recorded trace without a chip
(``tests/test_devtrace.py``).

What a TPU v5e trace holds (read by hand from a replay-agg512 trace, JAX
0.9): each ``/device:TPU:<n>`` plane has an ``XLA Ops`` line whose events
are named by the op's full HLO text (``%name = shape opcode(...), ...``)
and nest: a ``while`` event spans the ops of its body. The device is busy
where any op runs, so busy time is the union of all events; an op's self
time is its time minus that of the events nested in it. A Pallas kernel
runs as a custom call whose text names ``custom_call_target=
"tpu_custom_call"``; the other custom calls of this program are XLA's
u64 emulation (``X64SplitLow``, ``X64SplitHigh``, ``X64Combine``) and
count as glue. The events carry no category stat.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
import shutil
from dataclasses import dataclass, field

WINDOW_SPAN = "bench.traced_window"
MOSAIC_TARGET = 'custom_call_target="tpu_custom_call"'

# Which benchmark span names an idle gap of the device, when several are
# open at its midpoint: the innermost call into the program first.
GAP_PRIORITY = (
    "bench.verify_call", "bench.prepare", "bench.submit", "bench.wait",
)

_TARGET = re.compile(r'custom_call_target="([^"]+)"')


@dataclass
class Op:
    name: str          # short name: "<opcode or custom-call target> <base> <shape>"
    start_ns: float
    dur_ns: float
    mosaic: bool


def short_name(hlo: str) -> str:
    """``%fusion.12 = u32[64,25]{1,0:T(8,128)} fusion(...), kind=kLoop`` ->
    ``fusion fusion u32[64,25]``: what stays the same from run to run."""
    inst, _, rest = hlo.partition(" = ")
    base = re.sub(r"\.\d+$", "", inst.lstrip("%"))
    if rest.startswith("("):                       # tuple-shaped result
        depth = 0
        for i, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                break
        shape, rest = "tuple", rest[i + 1:].lstrip()
    else:
        shape, _, rest = rest.partition(" ")
        shape = shape.split("{")[0]
    opcode = rest.split("(")[0]
    target = _TARGET.search(rest)
    return f"{target.group(1) if target else opcode} {base} {shape}"


@dataclass
class Trace:
    devices: dict = field(default_factory=dict)   # plane name -> [Op]
    spans: list = field(default_factory=list)     # [(name, start_ns, end_ns)]

    def to_json(self) -> dict:
        return {
            "devices": {d: [[o.name, o.start_ns, o.dur_ns, o.mosaic] for o in ops]
                        for d, ops in self.devices.items()},
            "spans": [list(s) for s in self.spans],
        }

    @classmethod
    def from_json(cls, obj: dict) -> "Trace":
        return cls(
            devices={d: [Op(*o) for o in ops] for d, ops in obj["devices"].items()},
            spans=[tuple(s) for s in obj["spans"]],
        )

    def save(self, path: str) -> None:
        with gzip.open(path, "wt") as fh:
            json.dump(self.to_json(), fh)

    @classmethod
    def read(cls, path: str) -> "Trace":
        with gzip.open(path, "rt") as fh:
            return cls.from_json(json.load(fh))


def _newest_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(trace_dir: str) -> Trace:
    """The newest ``.xplane.pb`` under ``trace_dir`` as a ``Trace``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(_newest_xplane(trace_dir))
    trace = Trace()
    names: dict[str, tuple[str, bool]] = {}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    hlo = ev.name
                    if hlo not in names:
                        names[hlo] = (short_name(hlo), MOSAIC_TARGET in hlo)
                    name, mosaic = names[hlo]
                    ops.append(Op(name, ev.start_ns, ev.duration_ns, mosaic))
            trace.devices[plane.name] = sorted(ops, key=lambda o: o.start_ns)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        trace.spans.append(
                            (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    return trace


@dataclass
class Summary:
    window_s: float
    busy_s: float                 # mean over chips
    mosaic_s: float               # mean over chips
    ops: list                     # [(short name, self seconds)] summed over chips, desc
    idle_by_span: list            # [(host span, seconds)] mean over chips, desc


def _span_at(spans, t: float) -> str:
    open_names = {n for n, s, e in spans if s <= t < e and n != WINDOW_SPAN}
    for name in GAP_PRIORITY:
        if name in open_names:
            return name
    return sorted(open_names)[0] if open_names else "no bench span"


def reduce(trace: Trace) -> Summary | None:
    """Numbers of the traced window; None when the trace holds no window or
    no device op in it, so that no metric reads 0 for lack of data."""
    windows = [(s, e) for n, s, e in trace.spans if n == WINDOW_SPAN]
    if not windows or not trace.devices:
        return None
    w0, w1 = windows[0]
    mosaic = 0.0
    self_time: dict[str, float] = {}
    idle: dict[str, float] = {}

    def gap(a: float, b: float) -> None:
        name = _span_at(trace.spans, (a + b) / 2)
        idle[name] = idle.get(name, 0.0) + (b - a)

    for ops in trace.devices.values():
        clipped = sorted(
            ((max(o.start_ns, w0), min(o.start_ns + o.dur_ns, w1), o)
             for o in ops if min(o.start_ns + o.dur_ns, w1) > max(o.start_ns, w0)),
            key=lambda c: (c[0], -c[1]))
        # an event that starts inside an open one is nested in it
        stack: list[list] = []          # [end, name, self ns] of open events
        union_end = w0
        for s, e, o in clipped:
            while stack and stack[-1][0] <= s:
                _, name, own = stack.pop()
                self_time[name] = self_time.get(name, 0.0) + own
            if stack:
                stack[-1][2] -= e - s
            stack.append([e, o.name, e - s])
            if o.mosaic:
                mosaic += e - s
            if s > union_end:
                gap(union_end, s)
            union_end = max(union_end, e)
        for _, name, own in stack:
            self_time[name] = self_time.get(name, 0.0) + own
        if w1 > union_end:
            gap(union_end, w1)
    n_dev = len(trace.devices)
    busy = n_dev * (w1 - w0) - sum(idle.values())
    if busy <= 0:
        return None
    return Summary(
        window_s=(w1 - w0) / 1e9,
        busy_s=busy / n_dev / 1e9,
        mosaic_s=mosaic / n_dev / 1e9,
        ops=sorted(((k, v / 1e9) for k, v in self_time.items()),
                   key=lambda kv: -kv[1]),
        idle_by_span=sorted(((k, v / n_dev / 1e9) for k, v in idle.items()),
                            key=lambda kv: -kv[1]),
    )


def describe(trace_dir: str, samples: int = 5) -> dict:
    """Every plane and line of the newest trace under ``trace_dir``, with
    event counts and a few events and their stats: what to read before
    writing code against a trace."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(_newest_xplane(trace_dir))
    out = {}
    for plane in data.planes:
        lines = {}
        for line in plane.lines:
            events = list(line.events)
            lines[line.name] = {
                "events": len(events),
                "samples": [[ev.name, ev.start_ns, ev.duration_ns,
                             {k: str(v)[:80] for k, v in ev.stats}]
                            for ev in events[:samples]],
            }
        out[plane.name] = lines
    return out


class Capture:
    """Profiler session over the end of the window, marked by WINDOW_SPAN."""

    def __init__(self, trace_dir: str):
        self.trace_dir = trace_dir
        self._ann = None

    def start(self) -> None:
        import jax

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        os.makedirs(self.trace_dir)
        # host annotations and device ops only: tracing every Python call
        # would slow the host path this window measures
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        self._ann = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._ann.__enter__()

    def stop(self) -> None:
        import jax

        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()


if __name__ == "__main__":
    # python3 benchmark/devtrace.py <trace dir> <out dir>: the trace's
    # planes (describe.json) and its compact form (trace.json.gz)
    import sys

    src, dst = sys.argv[1], sys.argv[2]
    os.makedirs(dst, exist_ok=True)
    with open(os.path.join(dst, "describe.json"), "w") as fh:
        json.dump(describe(src), fh, indent=1)
    load(src).save(os.path.join(dst, "trace.json.gz"))
