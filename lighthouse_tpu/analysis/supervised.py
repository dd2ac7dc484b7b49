"""Pass 4 — the supervisor-transparency probe (ISSUE 7).

The fault-domain supervisor (``resilience.supervisor``) wraps every jitted
device call on the serving path. The wrapper must be *invisible* to XLA:
it passes arguments through untouched (same shapes, same dtypes, same
callable identity), so it may add exactly ZERO steady-state recompiles —
one stray recompile per supervised call is the hazard the recompilation
sentinel exists to catch, multiplied across the whole hot path.

This pass proves three properties, cheaply enough for every CLI run:

1. the ``resilience`` package itself lints clean under the trace-hygiene
   rules (its jit-facing wrappers introduce no host-sync/tracer-branch
   anti-patterns);
2. running a jitted kernel through ``run_ladder`` triggers no compilation
   after warm-up (watchdog thread included — jit dispatch from the worker
   thread must hit the same executable cache);
3. the supervised result is the kernel's result, bit for bit.
"""

from __future__ import annotations

import os


def supervisor_probe(steps: int = 4) -> dict:
    """Run the three checks; returns a report dict with ``ok``."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from ..resilience.supervisor import BackendSupervisor, SupervisorConfig
    from .hygiene import lint_tree
    from .recompile import steady_state_compiles

    res_root = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "resilience",
    )
    findings, _suppressed = lint_tree(root=res_root)

    kern = jax.jit(lambda x: (x * 3 + 1).sum())
    x = jnp.arange(128, dtype=jnp.int32)
    bare = int(np.asarray(kern(x)))
    # direct construction: the probe supervisor stays OUT of the global
    # registry so it never shows up in /health or bench integrity stamps
    sup = BackendSupervisor(
        "analysis.supervisor_probe", SupervisorConfig(deadline_s=60.0)
    )

    def step():
        return sup.run_ladder(
            "analysis.probe", (("device_full", lambda: kern(x)),)
        )

    recompiles = steady_state_compiles(step, warmup=2, steps=steps)
    supervised = int(np.asarray(step()))
    transparent = supervised == bare
    return {
        "ok": not findings and not recompiles and transparent,
        "lint_findings": [f.as_dict() for f in findings],
        "steady_state_compiles": recompiles,
        "transparent": transparent,
        "supervised_calls": sup.calls,
    }
