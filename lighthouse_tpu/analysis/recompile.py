"""Pass 3 — the recompilation sentinel: prove steady-state loops stay warm.

A steady-state serving loop (the firehose verify pipeline, the epoch-engine
sweep, a bench rung) must trigger ZERO XLA compilations after warm-up —
one stray recompile per step is exactly the hazard that burns a scarce TPU
window on compiling instead of measuring. The program's compile listener
(``utils.tracing``) counts every backend compilation (or persistent-cache
load) per function in ``jax_compile_phase_seconds{phase="compile"}``; the
sentinel reads those counts and exposes the compiles since it was entered
as a list of function names.

Usage::

    with CompilationSentinel() as sentinel:
        warmup()
        mark = sentinel.snapshot()
        for _ in range(steps):
            steady_step()
        assert sentinel.compiles_since(mark) == []   # names of new compiles

or the one-shot helper ``steady_state_compiles(step_fn, warmup=2, steps=3)``.

The counts are process-wide (XLA compilation is process-wide state), so a
compile on another thread counts too.
"""

from __future__ import annotations

from ..utils.tracing import compile_counts

__all__ = ["CompilationSentinel", "steady_state_compiles", "recompile_probe"]


def _names_since(before: dict[str, int], after: dict[str, int]) -> list[str]:
    return [fun for fun, n in after.items()
            for _ in range(n - before.get(fun, 0))]


class CompilationSentinel:
    """Context manager counting XLA compilations while active."""

    def __init__(self):
        self._entered: dict[str, int] = {}
        self._exited: dict[str, int] | None = None

    def __enter__(self) -> "CompilationSentinel":
        self._entered = compile_counts()
        self._exited = None
        return self

    def __exit__(self, *exc) -> None:
        self._exited = compile_counts()

    def _now(self) -> dict[str, int]:
        return compile_counts() if self._exited is None else self._exited

    # -- counters ----------------------------------------------------------

    @property
    def events(self) -> list[str]:
        """Function names, one per compilation, grouped by function."""
        return _names_since(self._entered, self._now())

    @property
    def total(self) -> int:
        return len(self.events)

    def snapshot(self) -> dict[str, int]:
        """Mark the current compile counts (call after warm-up)."""
        return self._now()

    def compiles_since(self, mark: dict[str, int]) -> list[str]:
        """Names of functions compiled since ``snapshot()`` — empty means
        the loop is steady-state clean."""
        return _names_since(mark, self._now())


def steady_state_compiles(step_fn, warmup: int = 2, steps: int = 3) -> list[str]:
    """Run ``step_fn()`` ``warmup`` times, then ``steps`` more under the
    sentinel; return the names of kernels compiled during the steady phase
    (empty = zero recompiles after warm-up)."""
    with CompilationSentinel() as sentinel:
        for _ in range(warmup):
            step_fn()
        mark = sentinel.snapshot()
        for _ in range(steps):
            step_fn()
        return sentinel.compiles_since(mark)


def recompile_probe(steps: int = 4) -> dict:
    """The CLI's runtime sentinel check: a warm jit loop must stay at zero
    compiles. Proves the counting plumbing (the compile listener) works in
    this process — the deep loops (firehose, epoch engine) are
    sentinel-checked by tests/test_analysis.py and the bench rungs where
    their compile cost belongs."""
    import jax
    import jax.numpy as jnp

    kern = jax.jit(lambda x: (x * 2 + 1).sum())
    x = jnp.arange(64, dtype=jnp.int32)
    names = steady_state_compiles(
        lambda: kern(x).block_until_ready(), warmup=2, steps=steps
    )
    return {"ok": names == [], "steady_state_compiles": names}
