"""``python -m lighthouse_tpu.analysis`` — run the six-pass certifier suite.

Exit code 0 iff every selected pass is clean. ``--json`` emits one machine-
readable report on stdout; the default
output is human-oriented. ``--bounds`` / ``--lint`` / ``--recompile`` /
``--supervisor`` / ``--concurrency`` / ``--memory`` select individual
passes; with no selection all six run:

1. **bounds** — the static limb-bound certifier (``BOUNDS_CERT.json``);
2. **lint** — the trace-hygiene linter;
3. **recompile** — the runtime sentinel probe (a warm jit loop must stay
   at zero compiles; the deep serving loops are covered by
   ``tests/test_analysis.py`` and the bench rungs);
4. **supervisor** — the supervisor-transparency probe;
5. **concurrency** — the lock-discipline certifier + lock-order deadlock
   graph (``CONCURRENCY_CERT.json``), merging a ``LOCKDEP_OBSERVED.json``
   runtime graph when one is present (see ``LIGHTHOUSE_LOCKDEP=1``);
6. **memory** — the device-memory certifier & static footprint planner
   (``MEMORY_CERT.json``): graph footprints under every conv backend x
   batch regime, pallas VMEM tile walk, the five subsystem residency
   models, per-tier margins, and the ``max_safe_shape`` planner.
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def _repo_root() -> str:
    return os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="lighthouse_tpu.analysis")
    ap.add_argument("--json", action="store_true", help="machine-readable report")
    ap.add_argument("--bounds", action="store_true", help="run only the limb-bound certifier")
    ap.add_argument("--lint", action="store_true", help="run only the trace-hygiene linter")
    ap.add_argument(
        "--recompile", action="store_true",
        help="run only the recompilation-sentinel probe",
    )
    ap.add_argument(
        "--supervisor", action="store_true",
        help="run only the supervisor-transparency probe (lint-clean "
        "resilience wrappers + zero steady-state recompiles)",
    )
    ap.add_argument(
        "--concurrency", action="store_true",
        help="run only the concurrency certifier (lock discipline + "
        "deadlock graph + lockdep cross-check)",
    )
    ap.add_argument(
        "--memory", action="store_true",
        help="run only the device-memory certifier & footprint planner",
    )
    ap.add_argument(
        "--memory-cert-out",
        default=None,
        help="write MEMORY_CERT.json here (default: repo root when the"
        " memory pass runs, '-' to skip)",
    )
    ap.add_argument(
        "--cert-out",
        default=None,
        help="write BOUNDS_CERT.json here (default: repo root when the bounds"
        " pass runs, '-' to skip)",
    )
    ap.add_argument(
        "--concurrency-cert-out",
        default=None,
        help="write CONCURRENCY_CERT.json here (default: repo root when the"
        " concurrency pass runs, '-' to skip)",
    )
    ap.add_argument(
        "--observed",
        default=None,
        help="lockdep observed-graph JSON to merge into the concurrency cert"
        " (default: LOCKDEP_OBSERVED.json beside the cert when present)",
    )
    ap.add_argument(
        "--graphs", nargs="*", default=None,
        help="restrict certification to graphs whose name contains any substring",
    )
    ap.add_argument(
        "--batches", nargs="*", type=int, default=None,
        help="batch regimes to certify (default 1 32)",
    )
    args = ap.parse_args(argv)
    any_selected = (
        args.bounds or args.lint or args.recompile or args.supervisor
        or args.concurrency or args.memory
    )
    run_bounds = args.bounds or not any_selected
    run_lint = args.lint or not any_selected
    run_recompile = args.recompile or not any_selected
    run_supervisor = args.supervisor or not any_selected
    run_concurrency = args.concurrency or not any_selected
    run_memory = args.memory or not any_selected

    report: dict = {"ok": True}
    rc = 0

    if run_supervisor:
        from .supervised import supervisor_probe

        sup_rep = supervisor_probe()
        report["supervisor"] = sup_rep
        if not sup_rep["ok"]:
            report["ok"] = False
            rc = 1
        if not args.json:
            print(
                "supervisor: "
                f"{len(sup_rep['lint_findings'])} lint finding(s), "
                f"{len(sup_rep['steady_state_compiles'])} steady-state "
                f"recompile(s), transparent={sup_rep['transparent']} — "
                f"{'ok' if sup_rep['ok'] else 'FAIL'}",
                file=sys.stderr,
            )

    if run_recompile:
        from .recompile import recompile_probe

        rec_rep = recompile_probe()
        report["recompile"] = rec_rep
        if not rec_rep["ok"]:
            report["ok"] = False
            rc = 1
        if not args.json:
            print(
                f"recompile: {len(rec_rep['steady_state_compiles'])} steady-"
                f"state compile(s) — {'ok' if rec_rep['ok'] else 'FAIL'}",
                file=sys.stderr,
            )

    if run_lint:
        from .durability import lint_tree as durability_lint_tree
        from .hygiene import lint_tree

        findings, suppressed = lint_tree()
        # durability rider (ISSUE 12): multi-key persistence sequences
        # bypassing do_atomically on the block-import/finalization paths
        dur_findings, dur_suppressed = durability_lint_tree()
        report["lint"] = {
            "ok": not findings and not dur_findings,
            "n_findings": len(findings),
            "n_baseline_suppressed": suppressed,
            "findings": [f.as_dict() for f in findings],
            "n_durability_findings": len(dur_findings),
            "n_durability_baseline_suppressed": dur_suppressed,
            "durability_findings": [f.as_dict() for f in dur_findings],
        }
        if findings or dur_findings:
            report["ok"] = False
            rc = 1
        if not args.json:
            for f in findings + dur_findings:
                print(str(f), file=sys.stderr)
            print(
                f"lint: {len(findings)} finding(s), {suppressed} baseline-"
                f"suppressed; durability: {len(dur_findings)} finding(s), "
                f"{dur_suppressed} baseline-suppressed — "
                f"{'FAIL' if findings or dur_findings else 'ok'}",
                file=sys.stderr,
            )

    if run_concurrency:
        from .concurrency import certify_concurrency
        from .concurrency import write_cert as write_ccert

        observed = args.observed
        if observed is None:
            default_obs = os.path.join(_repo_root(), "LOCKDEP_OBSERVED.json")
            observed = default_obs if os.path.exists(default_obs) else None
        ccert = certify_concurrency(observed_path=observed)
        out = args.concurrency_cert_out
        if out is None:
            out = os.path.join(_repo_root(), "CONCURRENCY_CERT.json")
        if out != "-":
            write_ccert(ccert, out)
        report["concurrency"] = {
            "ok": ccert["ok"],
            "n_findings": ccert["n_findings"],
            "n_baseline_suppressed": ccert["n_baseline_suppressed"],
            "n_lock_classes": ccert["n_lock_classes"],
            "n_edges": len(ccert["lock_graph"]["edges"]),
            "cycles": ccert["cycles"],
            "lockdep_ok": ccert["lockdep"]["ok"],
            "n_observed_edges": ccert["lockdep"]["n_observed_edges"],
            "findings": ccert["findings"],
            "cert_path": None if out == "-" else out,
        }
        if not ccert["ok"]:
            report["ok"] = False
            rc = 1
        if not args.json:
            for f in ccert["findings"]:
                print(
                    f"{f['path']}:{f['line']}: [{f['rule']}] {f['message']}",
                    file=sys.stderr,
                )
            for cyc in ccert["cycles"]:
                print(f"lock-order cycle: {cyc}", file=sys.stderr)
            print(
                f"concurrency: {ccert['n_findings']} finding(s),"
                f" {ccert['n_baseline_suppressed']} baseline-suppressed,"
                f" {len(ccert['lock_graph']['edges'])} lock-order edge(s),"
                f" {len(ccert['cycles'])} cycle(s),"
                f" {ccert['lockdep']['n_observed_edges']} observed edge(s) —"
                f" {'ok' if ccert['ok'] else 'FAIL'}",
                file=sys.stderr,
            )

    if run_bounds:
        from .bounds import certify, write_cert

        kw = {}
        if args.batches:
            kw["batches"] = tuple(args.batches)
        cert = certify(graphs=args.graphs, **kw)
        out = args.cert_out
        if out is None:
            out = os.path.join(_repo_root(), "BOUNDS_CERT.json")
        if out != "-":
            write_cert(cert, out)
        report["bounds"] = {
            "ok": cert["ok"],
            "n_obligations": cert["n_obligations"],
            "n_failed": cert["n_failed"],
            "min_margin_bits": cert["min_margin_bits"],
            "cert_path": None if out == "-" else out,
        }
        if not cert["ok"]:
            report["ok"] = False
            rc = 1
        if not args.json:
            for r in cert["obligations"]:
                if not r["ok"]:
                    print(f"UNPROVEN {r}", file=sys.stderr)
            print(
                f"bounds: {cert['n_obligations']} obligations,"
                f" {cert['n_failed']} failed, min margin"
                f" {cert['min_margin_bits']} bits —"
                f" {'ok' if cert['ok'] else 'FAIL'}",
                file=sys.stderr,
            )

    if run_memory:
        from .memory import certify_memory
        from .memory import write_cert as write_mcert

        kw = {}
        if args.batches:
            kw["batches"] = tuple(args.batches)
        mcert = certify_memory(graphs=args.graphs, **kw)
        out = args.memory_cert_out
        if out is None:
            out = os.path.join(_repo_root(), "MEMORY_CERT.json")
        if out != "-":
            write_mcert(mcert, out)
        report["memory"] = {
            "ok": mcert["ok"],
            "n_rows": mcert["n_rows"],
            "n_failed": mcert["n_failed"],
            "tiers": sorted(mcert["tiers"]),
            "default_tier": mcert["default_tier"],
            "peaks": mcert["peaks"],
            "planner": mcert["planner"],
            "failed_rows": [r for r in mcert["rows"] if not r["ok"]],
            "cert_path": None if out == "-" else out,
        }
        if not mcert["ok"]:
            report["ok"] = False
            rc = 1
        if not args.json:
            for r in mcert["rows"]:
                if not r["ok"]:
                    print(f"OVER-BUDGET {r}", file=sys.stderr)
            print(
                f"memory: {mcert['n_rows']} row(s),"
                f" {mcert['n_failed']} over budget,"
                f" tiers {'/'.join(sorted(mcert['tiers']))} —"
                f" {'ok' if mcert['ok'] else 'FAIL'}",
                file=sys.stderr,
            )

    if args.json:
        print(json.dumps(report))
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
