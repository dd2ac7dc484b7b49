"""One run of one cell: set-up, the measured window, the comparison with the
reference, and the result line.

Everything the harness knows of a cell comes from ``BENCHMARK.json`` and the
files it names: ``configs/<config>.json``, ``traffic/<traffic>.json`` and, for
each metric, end-to-end or per-layer, ``metrics/<metric>.py`` with
``read(run: Run) -> float | None`` (None: nothing to read in this run). A
cell, a mix or a metric is added by adding such files and entries.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE = os.path.join(ROOT, ".bench_cache")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_S = 2.0           # a --trace 1 run traces the window's last TRACE_S s
WORKERS = 12            # host threads for signing and the reference


def use_checkout_cache() -> None:
    """JAX's persistent compile cache at a fixed path in the checkout, with
    no size cap (the cells' programs take some 200 MB; an evicted one would
    compile again inside a later run's set-up), so that only a checkout's
    first run of a cell compiles. Call before JAX is imported."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(CACHE, "jax")
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


class NotAMeasurement(SystemExit):
    """The window did not run the device path as deployed: a batch left the
    full device rung, or something compiled inside the window."""


def load_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def cell_files(workload: str) -> tuple[dict, dict, dict, dict]:
    """(benchmark, cell, configuration, traffic mix) for a workload name."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(os.path.join(ROOT, configs[cell["config"]]["file"]))
    mix = load_json(os.path.join(HERE, "traffic", f"{cell['traffic']}.json"))
    return bench, cell, config, mix


def metric_reader(name: str):
    path = os.path.join(HERE, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclass
class Run:
    """What a metric reader may read (monotonic clock, seconds)."""

    workload: str
    seconds: float
    t_start: float                     # process start
    spans: object                      # windows.Spans
    window: tuple                      # (t_open, t_close)
    work: object                       # generator.Work
    replay: object                     # windows.ReplayResult
    trace: object = None               # devtrace.Summary


def _ladder_events() -> dict:
    """Supervisor events that mean a batch left the full device rung."""
    from lighthouse_tpu.resilience import snapshot_all

    keys = ("faults", "retries", "demotions", "fallback_calls",
            "watchdog_timeouts")
    return {f"{name}.{k}": snap[k] for name, snap in snapshot_all().items()
            for k in keys if snap[k]}


def _device(jax, chips: int) -> dict:
    devs = jax.devices()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs[:chips])
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak)}


def plain_verify(s) -> bool:
    """The reference's verdict on one signature set: e(pk, H(m)) ==
    e(g1, sig), with pk = sk * G1 from the set's aggregate secret key."""
    from .reference import native

    return native.verify_sets([native.pubkey_raw(s.sk)], [s.message],
                              [s.signature], [1])


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_start: float, require_chip: bool = True, wrap=None,
             files=None) -> dict:
    """One run; returns the result line.

    ``wrap(program_verify, keys) -> verify`` puts something else in the
    place of the chain's batch verifier (the control and the fault tests);
    ``files`` replaces the cell's files (the tests' small configurations).
    """
    bench, cell, config, mix = files or cell_files(workload)
    import jax

    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu" or len(devs) < cell["chips"]):
        raise SystemExit(
            f"{workload} needs {cell['chips']} TPU chip(s); JAX found "
            f"{len(devs)} {devs[0].platform} device(s)")

    import lighthouse_tpu  # noqa: F401 — enables x64 before any array exists

    from . import devtrace, generator, windows
    from .keys import Keys, device_registry
    from .reference import native

    compiles = {"open": False, "n": 0}

    def on_event(event, duration, **kw):
        if event == COMPILE_EVENT and compiles["open"]:
            compiles["n"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)

    def log(msg: str) -> None:
        print(f"# {time.monotonic() - t_start:7.1f} s  {msg}", file=sys.stderr,
              flush=True)

    with ThreadPoolExecutor(WORKERS) as pool:
        native.load()
        keys = Keys(config["validators"], seed)
        log("reference library loaded, keys drawn")
        cache = device_registry(keys, keys.base_pubkeys_raw(pool))
        log("registry dispatched")
        work = generator.build(config, mix, keys, seed, pool)
        log("traffic built and signed")
        cache.block_until_ready()
        log("registry on the device")
        spans = windows.Spans()
        chain = windows.registry_chain(
            cache, work, spans,
            None if wrap is None else (lambda verify: wrap(verify, keys)))
        capture = devtrace.Capture(os.path.join(CACHE, "trace", workload)) \
            if trace else None
        trace_from = max(0.0, seconds - TRACE_S)
        ladder0 = _ladder_events()
        windows.warm_replay(chain, work)
        log("warmed up")
        compiles["open"] = True
        res = windows.replay(chain, work, seconds, capture, trace_from)
        compiles["open"] = False
        log("window closed")
        device = _device(jax, cell["chips"])
        ladder = {k: v - ladder0.get(k, 0) for k, v in _ladder_events().items()
                  if v != ladder0.get(k, 0)}
        del chain, cache
        if ladder or compiles["n"]:
            raise NotAMeasurement(
                f"{workload}: not a measurement: {compiles['n']} compilation(s) "
                f"inside the window, supervisor events {ladder}")

        summary = None
        if trace:
            summary = devtrace.reduce(devtrace.load(capture.trace_dir))
            if summary is not None:
                device["busy_s"] = summary.busy_s
                device["window_s"] = summary.window_s

        run = Run(workload=workload, seconds=seconds, t_start=t_start,
                  spans=spans, window=(res.t_open, res.t_close), work=work,
                  replay=res, trace=summary)
        # the reference's verdict on every batch the window called: the AND
        # of its sets' own verdicts
        called = sorted({b for b, _, _, _ in res.calls})
        sets = [(b, s) for b in called for s in work.batches[b]]
        want = dict.fromkeys(called, True)
        for (b, _), ok in zip(sets, pool.map(plain_verify, [s for _, s in sets])):
            want[b] = want[b] and ok
        wrong = sum(ok is not want[b] for b, ok, _, _ in res.calls)
        checks = {"wrong_verdicts": (wrong, 0)}
        print(f"# replay: {len(res.calls)} batch calls over {len(called)} "
              f"distinct batches ({sum(not w for w in want.values())} with a "
              f"bad set)", file=sys.stderr)
        log("compared with the reference")

    metrics = {}
    for m in bench["per_layer"] if trace else bench["end_to_end"]:
        if workload not in m.get("workloads", [workload]):
            continue
        value = metric_reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {
        "correct": all(value <= limit for value, limit in checks.values()),
        "attempted": len(res.calls),
        "failed": wrong,
        "metrics": metrics,
        "device": device,
    }
    if trace and summary is not None:
        result["breakdown"] = {
            "device_ops": [[k, v] for k, v in summary.ops[:10]],
            "idle_gaps": [[k, v] for k, v in summary.idle_by_span[:10]],
        }
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result
