"""Test configuration: force a virtual 8-device CPU platform.

Tier-1 runs on the CPU; sharding tests run on a virtual CPU mesh
(``--xla_force_host_platform_device_count=8``). The recipe lives in devcpu.py,
shared with dev scripts. tests/test_tpu_compile.py compiles for a described
TPU without a chip.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import devcpu  # noqa: F401  (side effect: CPU platform + 8 virtual devices)

# -- runtime lockdep (ISSUE 9) ------------------------------------------------
# LIGHTHOUSE_LOCKDEP=1 swaps the threading lock factories for instrumented
# wrappers BEFORE the package under test creates its locks, so a whole
# pytest run (the chaos scenario, the local_network suites) records every
# actual lock-acquisition order. pytest_sessionfinish writes the observed
# graph to LOCKDEP_OBSERVED.json at the repo root and fails the session if
# the observed orders alone contain a cycle; the analysis CLI then merges
# the file into CONCURRENCY_CERT.json for static/runtime cross-validation.

_LOCKDEP = os.environ.get("LIGHTHOUSE_LOCKDEP", "") == "1"
if _LOCKDEP:
    from lighthouse_tpu.analysis import concurrency as _lockdep

    _lockdep.install()


def pytest_sessionfinish(session, exitstatus):
    if not _LOCKDEP:
        return
    import json

    report = _lockdep.observed_report()
    merged = _lockdep.merge_observed({}, report["edges"])
    out = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "LOCKDEP_OBSERVED.json",
    )
    with open(out, "w") as f:
        json.dump(
            {
                "head": _lockdep.git_head(),
                "edges": report["edges"],
                "holds": report["holds"],
                "n_locks": report["n_locks"],
                "observed_acyclic": merged["ok"],
                "observed_cycles": merged["merged_cycles"],
            },
            f, indent=1, sort_keys=True,
        )
        f.write("\n")
    if not merged["ok"]:
        raise RuntimeError(
            "lockdep: observed lock-acquisition orders contain a cycle: "
            + "; ".join(merged["merged_cycles"])
        )
