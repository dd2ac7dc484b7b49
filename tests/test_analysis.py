"""The static-analysis subsystem (ISSUE 5): limb-bound certifier,
trace-hygiene linter, recompilation sentinel.

Three kinds of coverage:
  * clean-tree runs — the shipped kernels certify and lint clean (this is
    the tier-1 gate every future kernel PR must pass);
  * a fixture corpus of known-bad kernels — overflowing lincomb, wrapped
    accumulator, tracer-dependent branch, per-step recompile — asserting
    each pass flags its hazard;
  * seeded mutations — widening a lazy chain interior (the acceptance
    criterion's "one extra squaring" bound blow-up) must fail certification
    on each backend's own obligation.
"""

from __future__ import annotations

import os
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lighthouse_tpu.analysis import bounds, hygiene
from lighthouse_tpu.analysis.recompile import (
    CompilationSentinel,
    steady_state_compiles,
)
from lighthouse_tpu.ops.bls import fq, plans, tower


def _e2(batch=4):
    return jax.ShapeDtypeStruct((batch, 2, fq.NLIMBS), jnp.uint64)


def _e1(batch=4):
    return jax.ShapeDtypeStruct((batch, fq.NLIMBS), jnp.uint64)


# =============================================================================
# Pass 1 — limb-bound certifier
# =============================================================================


@pytest.mark.kernel
class TestCertifier:
    @pytest.mark.slow
    def test_clean_tree_proves_every_callsite_both_backends(self):
        """The whole public op-graph surface certifies under BOTH conv
        backends (acceptance criterion). Batch 32 exercises the f64-walk
        dispatch regime; the u64-walk regime is covered below. Slow lane:
        the full sweep re-derives every obligation (~2.5 min); tier-1 keeps
        the memoized six-pass CLI gate plus the per-module subset tests."""
        cert = bounds.certify(backends=("f64", "digits"), batches=(32,))
        bad = [r for r in cert["obligations"] if not r["ok"]]
        assert cert["ok"] and not bad, bad[:5]
        graphs = {r["graph"] for r in cert["obligations"]}
        for mod in ("fq.", "tower.", "curve.", "h2c.", "pairing.", "pallas.",
                    "kzg."):
            assert any(mod in g for g in graphs), f"no obligations from {mod}*"
        for backend in ("f64@", "digits@"):
            assert any(g.startswith(backend) for g in graphs)
        kinds = {r["kind"] for r in cert["obligations"]}
        assert {
            "conv_f64_exact",          # (a) f64 partial products < 2^53
            "conv_digit_f32_exact",    # (a) f32 digit products < 2^24
            "conv_digit_u32_nowrap",   # (b) u32 cast cannot wrap
            "fold_acc_nowrap",         # (b) fold accumulators cannot wrap
            "execute_wide_acc",        # (b) out-row accumulators in cap
            "reduce_value",            # (c) walks land on declared targets
            "reduce_limb",
            "out_bound_top_sound",     # (c) declared CHAIN/out_bound sound
            "lincomb_limb_budget",
            # the fused Pallas kernels (ISSUE 13) register their digit-
            # domain schedule obligations through the same sink — proven
            # here under the f64/digits regimes via the explicit pallas.*
            # registry graphs (the kernels are backend-independent entries)
            "pallas_conv_digit_f32_exact",
            "pallas_fold_f32_exact",
            "pallas_reduce_value",
            "pallas_reduce_limb",
        } <= kinds

    def test_kzg_graphs_certify_both_backends(self):
        """Tier-1 sized: the PR-16 Fr limb graphs (kzg.fr_*) certify under
        both conv backends — the all-graph sweep above rides the slow
        lane."""
        cert = bounds.certify(
            backends=("f64", "digits"), batches=(4,), graphs=["kzg."]
        )
        bad = [r for r in cert["obligations"] if not r["ok"]]
        assert cert["ok"] and not bad, bad[:5]
        graphs = {r["graph"] for r in cert["obligations"]}
        # fr_bits traces too but emits no obligations (pure bit split —
        # no conv product or wide accumulation to bound)
        for g in ("kzg.fr_mul", "kzg.fr_dot", "kzg.fr_weighted_sum",
                  "kzg.fr_wide_reduce"):
            assert any(g in name for name in graphs), f"no obligations from {g}"

    def test_u64_walk_regime_certifies(self, monkeypatch):
        """The u64 reduction walk is dead-by-default since
        fq.F64_WALK_MIN_ROWS dropped to 0, but still invocable (the
        threshold is a tunable) — force the threshold up so batch-1
        dispatches it, and certify that schedule on its own."""
        from lighthouse_tpu.ops.bls import fq

        monkeypatch.setattr(fq, "F64_WALK_MIN_ROWS", 1 << 30)
        cert = bounds.certify(
            backends=("f64",),
            batches=(1,),
            graphs=["fq.mont_mul", "fq.canonical", "tower.fq2_mul"],
        )
        assert cert["ok"] and cert["n_failed"] == 0

    def test_pallas_regime_certifies(self):
        """The third backend regime: the representative graph subset
        re-executes THROUGH the fused pallas kernels (plans.execute and
        mont_mul dispatch there under LIGHTHOUSE_CONV_IMPL=pallas) and
        stays green — the full pallas sweep is the analysis CLI's job."""
        cert = bounds.certify(
            backends=("pallas",),
            batches=(1, 32),
            graphs=["fq.mont", "tower.fq12_mul", "tower.fq2_sqrt",
                    "curve.point_dbl", "pallas."],
        )
        assert cert["ok"] and cert["n_failed"] == 0
        kinds = {r["kind"] for r in cert["obligations"]}
        assert "pallas_conv_digit_f32_exact" in kinds
        assert "pallas_out_bound_top_sound" in kinds
        # pallas.execute_fq12_mul_packed runs the packed layout at batch 1
        assert "pallas_out_terms_f32_exact" in kinds

    def test_seeded_mutation_widened_interior_fails(self, monkeypatch):
        """Widening one lazy interior by one squaring (declared CHAIN bound
        becomes the square's unreduced bound) must fail certification —
        the limb budget blows past 2^22."""
        widened = plans._Bound(
            plans.CHAIN_BOUND.value_p ** 2,
            plans.CHAIN_BOUND.limb ** 2,
            plans.CHAIN_BOUND.top,
        )
        monkeypatch.setattr(plans, "CHAIN_BOUND", widened)
        rows = bounds.certify_callable(tower.fq2_sqr_lazy, (_e2(),), "f64")
        assert any(not r["ok"] for r in rows)

    def test_seeded_mutation_wider_chain_limb_fails_digits(self, monkeypatch):
        """A wider chain limb target breaks the digit backend's f32
        exactness (a different pass obligation than the f64 mutation)."""
        monkeypatch.setattr(fq, "CHAIN_LIMB_TARGET", (1 << 27) - 1)
        rows = bounds.certify_callable(
            lambda a, b: fq.mont_mul_lazy(a, b), (_e1(), _e1()), "digits"
        )
        assert any(
            not r["ok"]
            and r["kind"] in ("conv_digit_f32_exact", "unproven_bound")
            for r in rows
        )

    def test_fixture_overflowing_lincomb_flagged(self):
        """Known-bad kernel: a lincomb coefficient that pushes the operand
        limb bound past the lazy conv budget."""
        p = plans.Plan(2, 2)
        x, y = plans.vbasis(2), plans.vbasis(2)
        lane = p.lane(x[0].scale(1 << 21), y[0])
        p.out_rows = [lane, lane]
        rows = bounds.certify_callable(
            lambda a, b: plans.execute(p, a, b, name="bad_lincomb"),
            (_e2(), _e2()),
            "f64",
        )
        assert any(
            not r["ok"]
            and r["kind"] in ("lincomb_limb_budget", "unproven_bound")
            for r in rows
        )

    def test_fixture_wrapped_accumulator_flagged(self):
        """Known-bad kernel: conv inputs wide enough that the u64 (shear)
        accumulators wrap."""
        def bad(a, b):
            t = fq._conv_product(a, b)
            lb = fq.conv_limb_bounds(1 << 32)  # asserts: 25 * 2^64 wraps
            return fq.reduce_limbs(t, lb, (1 << 32 * 25) - 1)

        rows = bounds.certify_callable(bad, (_e1(), _e1()), "shear")
        assert any(
            not r["ok"] and r["kind"] in ("conv_u64_acc", "unproven_bound")
            for r in rows
        )

    def test_chain_bound_is_derived_and_sound(self):
        """plans.CHAIN_BOUND is derived from fq's named constants — the
        derivation (not hand-maintained prose) is what keeps them in sync."""
        assert plans.CHAIN_BOUND.value_p == fq.CHAIN_VALUE_P
        assert plans.CHAIN_BOUND.limb == fq.CHAIN_LIMB_TARGET
        assert plans.CHAIN_BOUND.top == fq.chain_top_limb()
        # the sound top bound: limbs non-negative => limb24 <= value >> 384
        assert plans.CHAIN_BOUND.top == min(
            fq.CHAIN_LIMB_TARGET, fq.CHAIN_VALUE_LIMIT >> (16 * 24)
        )


# =============================================================================
# Pass 2 — trace-hygiene linter
# =============================================================================


_BAD_MODULE = textwrap.dedent(
    '''
    import functools
    import time
    import numpy as np
    import jax
    import jax.numpy as jnp

    LOG = []

    @jax.jit
    def host_syncs(x):
        v = x.sum()
        return float(v) + v.item()

    @jax.jit
    def tracer_branch(x):
        if x > 0:                     # fixture: branch on a tracer
            return x
        return -x

    @jax.jit
    def impure(x):
        LOG.append(time.time())
        return np.asarray(x) + 1

    @functools.partial(jax.jit, static_argnames=("n",))
    def windowed(x, n):
        return x[:n]

    def caller(x):
        return windowed(x, n=[1, 2])  # fixture: unhashable static

    def scan_user(xs):
        def body(carry, x):
            if carry:                 # fixture: branch inside a scan body
                carry = carry + x
            return carry, x
        return jax.lax.scan(body, 0, xs)

    @functools.partial(jax.jit, static_argnums=(0,))
    def static_ok(flag, x):
        if flag:                      # static argnum — NOT a finding
            return x
        return x * 2

    @jax.jit
    def shape_ok(x):
        if x.shape[0] > 4:            # shape read — static, NOT a finding
            return x
        return jnp.pad(x, (0, 4 - x.shape[0]))

    @jax.jit
    def pragma_ok(x):
        return int(x[0])              # lint: allow(host-sync)

    from jax.experimental import pallas as pl

    def pallas_user(x):
        def kern(x_ref, o_ref):       # fixture: pallas kernel body is a
            v = x_ref[...]            # jit scope (ISSUE 13)
            LOG.append(v)             # fixture: impure closure in kernel
            if v[0] > 0:              # fixture: tracer branch in kernel
                o_ref[...] = v
            o_ref[...] = v * 2

        return pl.pallas_call(kern, out_shape=x)(x)
    '''
)


class TestHygieneLinter:
    @pytest.fixture()
    def bad_module(self, tmp_path):
        p = tmp_path / "bad_kernels.py"
        p.write_text(_BAD_MODULE)
        return str(p)

    def test_fixture_corpus_flags_each_rule(self, bad_module):
        findings = hygiene.lint_file(bad_module, "bad_kernels.py")
        rules = {f.rule for f in findings}
        assert rules == {
            "host-sync", "tracer-branch", "impure-closure",
            "static-unhashable",
        }
        flagged_fns = " ".join(f.message for f in findings)
        assert "host_syncs" in flagged_fns
        assert "tracer_branch" in flagged_fns
        assert "impure" in flagged_fns
        assert "body" in flagged_fns          # lax.scan body covered
        # pallas_call kernel bodies are jit scopes (ISSUE 13): both the
        # impure closure mutation and the tracer branch inside `kern` fire
        kern_rules = {f.rule for f in findings if "kern" in f.message}
        assert {"impure-closure", "tracer-branch"} <= kern_rules
        # negative space: statics and shape reads are not findings
        assert "static_ok" not in flagged_fns
        assert "shape_ok" not in flagged_fns
        assert "pragma_ok" not in flagged_fns  # pragma suppression

    def test_baseline_suppression(self, bad_module):
        findings = hygiene.lint_file(bad_module, "bad_kernels.py")
        baseline = {f.key() for f in findings}
        left = [f for f in findings if f.key() not in baseline]
        assert findings and not left

    def test_clean_tree(self):
        """The shipped lighthouse_tpu tree lints clean (the firehose and
        epoch-engine hot paths carry zero findings — fixed or pragma'd)."""
        findings, _ = hygiene.lint_tree()
        assert not findings, "\n".join(str(f) for f in findings)


# =============================================================================
# Pass 3 — recompilation sentinel
# =============================================================================


@pytest.mark.kernel
class TestRecompilationSentinel:
    def test_fixture_per_step_recompile_flagged(self):
        """Known-bad loop: the batch shape grows every step, forcing a
        compile per step — the exact hazard the sentinel exists to catch."""

        @jax.jit
        def kernel(x):
            return jnp.sum(x * 2)

        n = [8]

        def leaky_step():
            n[0] += 1  # unbucketed shape: recompiles every step
            kernel(jnp.ones(n[0])).block_until_ready()

        names = steady_state_compiles(leaky_step, warmup=1, steps=3)
        assert len(names) >= 3
        assert any("kernel" in s for s in names)

    def test_steady_jit_loop_is_clean(self):
        @jax.jit
        def kernel(x):
            return jnp.sum(x + 1)

        names = steady_state_compiles(
            lambda: kernel(jnp.ones(16)).block_until_ready(),
            warmup=1,
            steps=4,
        )
        assert names == []

    def test_firehose_steady_state_zero_recompiles(self):
        """The firehose loop — batcher forming, prep, bucketed device
        dispatch — triggers zero compiles after warm-up. The device stage is
        a stand-in kernel honoring the same power-of-two bucket contract as
        the real backend (tpu_backend.bucket); the full BLS stages are
        sentinel-checked by the bench rungs, where their compile cost
        belongs."""
        from lighthouse_tpu.bls import tpu_backend as tb
        from lighthouse_tpu.firehose import FirehoseConfig, FirehoseEngine

        @jax.jit
        def device_stage(x):
            return jnp.sum(x)

        def verify(items):
            n_pad = tb.bucket(len(items))
            buf = np.zeros((n_pad, 4))
            buf[: len(items)] = 1.0
            return bool(device_stage(jnp.asarray(buf)) >= 0)

        engine = FirehoseEngine(
            prepare_fn=lambda ps: [([p], None) for p in ps],
            verify_items_fn=verify,
            config=FirehoseConfig(max_batch=8),
            synchronous=True,
        )

        def step():
            for i in range(8):
                assert engine.submit(i)
            engine.drain()

        names = steady_state_compiles(step, warmup=2, steps=4)
        assert names == [], names

    def test_epoch_engine_steady_state_zero_recompiles(self):
        """Successive epoch boundaries through the device epoch engine —
        same registry bucket — compile once and never again (acceptance
        criterion: zero steady-state recompiles after warm-up)."""
        from lighthouse_tpu import epoch_engine
        from lighthouse_tpu.state_transition.genesis import (
            interop_genesis_state,
        )
        from lighthouse_tpu.types.spec import minimal_spec

        spec = minimal_spec(altair_fork_epoch=0)
        state = interop_genesis_state(spec, 64)
        slots = spec.preset.SLOTS_PER_EPOCH
        state.slot = 5 * slots - 1  # at an epoch boundary

        def step():
            assert epoch_engine.maybe_process_epoch_on_device(spec, state)
            state.slot += slots  # next boundary, same shape bucket

        prev = epoch_engine.get_backend()
        epoch_engine.set_backend("device")
        try:
            names = steady_state_compiles(step, warmup=2, steps=3)
        finally:
            epoch_engine.set_backend(prev)
        assert names == [], names


# =============================================================================
# Pass 5 — concurrency certifier (ISSUE 9)
# =============================================================================


from lighthouse_tpu.analysis import concurrency  # noqa: E402


_RACY_MODULE = textwrap.dedent(
    '''
    import threading

    class Worker:
        def __init__(self):
            self._lock = threading.Lock()
            self.count = 0
            self._thread = threading.Thread(target=self._loop, daemon=True)
            self._thread.start()

        def _loop(self):
            while True:
                self.count += 1          # fixture: unguarded mutation

        def snapshot(self):
            with self._lock:
                return self.count

        def reset(self):
            with self._lock:
                self.count = 0

        def stop(self):
            self._thread.join(timeout=1.0)
    '''
)

_INVERTED_MODULE = textwrap.dedent(
    '''
    import threading

    class Pair:
        def __init__(self):
            self._a = threading.Lock()
            self._b = threading.Lock()

        def ab(self):
            with self._a:
                with self._b:
                    return 1

        def ba(self):
            with self._b:
                with self._a:          # fixture: order inversion
                    return 2
    '''
)

_BLOCKED_MODULE = textwrap.dedent(
    '''
    import threading

    class Waiter:
        def __init__(self):
            self._meta = threading.Lock()
            self._cv_lock = threading.Lock()
            self._cv = threading.Condition(self._cv_lock)

        def stall(self):
            with self._meta:
                with self._cv:
                    self._cv.wait()    # fixture: untimed wait under _meta
    '''
)

_UNJOINED_MODULE = textwrap.dedent(
    '''
    import threading

    class FireAndForget:
        def start(self):
            threading.Thread(target=self._loop, daemon=True).start()

        def _loop(self):
            pass
    '''
)

_PRAGMA_MODULE = textwrap.dedent(
    '''
    import threading

    class Probe:
        def start(self):
            # short-lived probe worker, reclaimed by its own deadline wait
            threading.Thread(target=self._loop, daemon=True).start()  # lint: allow(unjoined-thread)

        def _loop(self):
            pass
    '''
)


def _analyze_dir(tmp_path, name: str, src: str):
    pkg = tmp_path / "fixmod"
    pkg.mkdir(exist_ok=True)
    (pkg / f"{name}.py").write_text(src)
    _index, findings, edges, cycles = concurrency.analyze_tree(str(pkg))
    return findings, edges, cycles


class TestConcurrencyCertifier:
    def test_seeded_unguarded_mutation_fails(self, tmp_path):
        findings, _, _ = _analyze_dir(tmp_path, "racy", _RACY_MODULE)
        hits = [f for f in findings if f.rule == "unguarded-write"]
        assert hits, findings
        assert "count" in hits[0].message and "_loop" in hits[0].message

    def test_seeded_lock_order_inversion_fails(self, tmp_path):
        findings, edges, cycles = _analyze_dir(
            tmp_path, "inverted", _INVERTED_MODULE
        )
        assert cycles, edges
        assert any(f.rule == "lock-order-cycle" for f in findings)

    def test_seeded_untimed_wait_under_second_lock_fails(self, tmp_path):
        findings, _, _ = _analyze_dir(tmp_path, "blocked", _BLOCKED_MODULE)
        hits = [f for f in findings if f.rule == "blocking-under-lock"]
        assert hits, findings
        assert ".wait()" in hits[0].message
        assert "_meta" in hits[0].message

    def test_seeded_unjoined_thread_fails(self, tmp_path):
        findings, _, _ = _analyze_dir(tmp_path, "unjoined", _UNJOINED_MODULE)
        assert any(f.rule == "unjoined-thread" for f in findings)

    def test_pragma_suppression(self, tmp_path):
        findings, _, _ = _analyze_dir(tmp_path, "pragma", _PRAGMA_MODULE)
        assert not [f for f in findings if f.rule == "unjoined-thread"], findings

    def test_baseline_suppression(self, tmp_path):
        findings, _, _ = _analyze_dir(tmp_path, "racy", _RACY_MODULE)
        assert findings
        baseline = {f.key() for f in findings}
        left = [f for f in findings if f.key() not in baseline]
        assert not left
        # line-number churn does not invalidate the baseline: the key is
        # (path, rule, context line), not the line number
        shifted = _RACY_MODULE.replace("import threading", "import threading\n")
        findings2, _, _ = _analyze_dir(tmp_path, "racy", shifted)
        assert findings2
        assert all(f.key() in baseline for f in findings2)

    def test_clean_tree(self):
        """The shipped lighthouse_tpu thread fabric certifies clean: every
        real race the pass surfaced was FIXED in this PR (firehose stats,
        discovery ENR re-sign, gossipsub IHAVE counter, serve-loop joins)
        rather than baselined — the checked-in baseline is empty."""
        cert = concurrency.certify_concurrency(observed_path="")
        assert cert["ok"], cert["findings"]
        assert cert["n_findings"] == 0
        assert cert["cycles"] == []
        # the certifier actually covered the thread fabric
        assert cert["n_modules_threading"] >= 20
        assert cert["n_lock_classes"] >= 20
        edges = {
            (e["from"], e["to"]) for e in cert["lock_graph"]["edges"]
        }
        # a known acquires-while-holding edge: supervisor state machine
        # bumps metrics counters under its own lock
        assert (
            "resilience.supervisor.BackendSupervisor._lock",
            "utils.metrics._Metric._lock",
        ) in edges

    def test_baseline_file_is_empty(self):
        """Guard the discipline: new findings must be fixed or pragma'd
        with justification, not quietly baselined."""
        assert concurrency.load_baseline() == set()


class TestLockdepRuntime:
    def test_lockdep_under_chaos_acyclic(self):
        """The acceptance run: instrumented locks while a threaded firehose
        rides its supervisor ladder through injected transient faults and a
        2-node loopback network runs slots under seeded gossip loss with a
        crash/restart — the OBSERVED lock-order graph must be cycle-free,
        alone and merged with the static graph."""
        from lighthouse_tpu import bls
        from lighthouse_tpu.firehose import FirehoseConfig, FirehoseEngine
        from lighthouse_tpu.resilience import injector
        from lighthouse_tpu.resilience.supervisor import (
            BackendSupervisor,
            SupervisorConfig,
        )
        from lighthouse_tpu.testing.local_network import LocalNetwork
        from lighthouse_tpu.types.spec import minimal_spec

        # under LIGHTHOUSE_LOCKDEP=1 conftest owns the session-wide
        # instrumentation — never tear that down from inside a test
        owned = not concurrency.installed()
        if owned:
            concurrency.install()
        try:
            injector.install(
                "stage=firehose.device_verify;mode=raise;kind=transient;every=3"
            )
            sup = BackendSupervisor(
                "lockdep.acceptance",
                SupervisorConfig(
                    deadline_s=10.0, backoff_base_s=0.001,
                    backoff_max_s=0.002,
                ),
            )
            engine = FirehoseEngine(
                prepare_fn=lambda ps: [([p], None) for p in ps],
                verify_items_fn=lambda items: True,
                config=FirehoseConfig(max_batch=8),
                supervisor=sup,
                fallback_verify_fn=lambda items: True,
            )
            for i in range(64):
                engine.submit(i)
            engine.flush(timeout=20.0)
            assert engine.stop(drain_timeout=20.0)

            prev = bls.get_backend()
            bls.set_backend("native")
            try:
                net = LocalNetwork(minimal_spec(), n_nodes=2, n_validators=8)
                net.transport.set_gossip_loss(0.05, seed=7)
                for slot in range(1, 7):
                    net.run_slot(slot)
                    if slot == 2:
                        net.crash_node(1)
                    if slot == 4:
                        net.restart_node(1)
            finally:
                bls.set_backend(prev)
                injector.clear()

            report = concurrency.observed_report()
            assert report["n_locks"] > 0
            assert report["edges"], "chaos run recorded no lock orders"
            merged_alone = concurrency.merge_observed({}, report["edges"])
            assert merged_alone["ok"], merged_alone["merged_cycles"]
            # cross-validation: observed orders merge into the static graph
            # without creating a cycle either
            _index, _f, static_edges, _c = concurrency.analyze_tree()
            merged = concurrency.merge_observed(static_edges, report["edges"])
            assert merged["ok"], merged["merged_cycles"]
            assert merged["n_observed_edges"] > 0
            # hold times came out of the run
            assert any(
                v["acquisitions"] > 0 for v in report["holds"].values()
            )
        finally:
            if owned:
                concurrency.uninstall()


# =============================================================================
# the six-pass CLI suite, end to end (ISSUE 9 CI satellite)
# =============================================================================


@pytest.mark.kernel
class TestSixPassSuite:
    def test_cli_green_certificate(self, tmp_path):
        """``python -m lighthouse_tpu.analysis --json`` runs all six passes
        (bounds, hygiene, recompile, supervisor, concurrency, memory) end to
        end and the certificate is green — a red cert fails tier-1.
        The bounds + memory passes are restricted to a representative graph
        subset at batch 1 to stay inside the tier-1 wall clock; the full
        sweeps are TestCertifier's / TestMemoryCertifier's job."""
        import subprocess
        import sys

        bounds_out = tmp_path / "BOUNDS_CERT.json"
        cc_out = tmp_path / "CONCURRENCY_CERT.json"
        mem_out = tmp_path / "MEMORY_CERT.json"
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.run(
            [
                sys.executable, "-m", "lighthouse_tpu.analysis", "--json",
                "--graphs", "fq.mont_mul", "tower.fq2_mul",
                "--batches", "1",
                "--cert-out", str(bounds_out),
                "--concurrency-cert-out", str(cc_out),
                "--memory-cert-out", str(mem_out),
            ],
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            env=env, capture_output=True, text=True, timeout=600,
        )
        assert proc.returncode == 0, proc.stderr[-3000:]
        import json as _json

        rep = _json.loads(proc.stdout.strip().splitlines()[-1])
        assert rep["ok"]
        for pass_name in (
            "bounds", "lint", "recompile", "supervisor", "concurrency",
            "memory",
        ):
            assert pass_name in rep, rep.keys()
            assert rep[pass_name]["ok"], rep[pass_name]
        assert rep["bounds"]["n_obligations"] > 0
        assert rep["concurrency"]["n_lock_classes"] >= 20
        # all three certificates landed where asked
        assert bounds_out.exists() and cc_out.exists() and mem_out.exists()
        cc = _json.loads(cc_out.read_text())
        assert cc["ok"] and cc["cycles"] == []
        mc = _json.loads(mem_out.read_text())
        assert mc["ok"] and mc["n_failed"] == 0
        # the restricted run still covers all three conv backends, every
        # residency family, and emits the planner rung_fit consumes
        regimes = {
            r["graph"].split("/", 1)[0]
            for r in mc["rows"] if r["kind"] == "graph_footprint"
        }
        assert {"f64@b1", "digits@b1", "pallas@b1"} <= regimes
        assert rep["memory"]["planner"]["tpu_v5e"]


_EXC_ANN_MODULE = textwrap.dedent(
    '''
    import threading

    class Worker:
        def __init__(self):
            self._lock: threading.Lock = threading.Lock()  # annotated decl
            self.count = 0
            self._thread = threading.Thread(target=self._loop, daemon=True)
            self._thread.start()

        def _loop(self):
            while True:
                try:
                    pass
                except Exception:
                    self.count: int = self.count + 1  # fixture: except path

        def snapshot(self):
            with self._lock:
                return self.count

        def reset(self):
            with self._lock:
                self.count = 0

        def stop(self):
            self._thread.join(timeout=1.0)
    '''
)


class TestConcurrencyBlindSpots:
    def test_except_handler_and_annassign_covered(self, tmp_path):
        """Regression (review findings): mutations on except paths and
        annotated assignments — including an annotated lock declaration —
        must feed the same rules as plain statements; the fault path is
        exactly where ISSUE 9's races live."""
        findings, _, _ = _analyze_dir(tmp_path, "annexc", _EXC_ANN_MODULE)
        hits = [f for f in findings if f.rule == "unguarded-write"]
        assert hits, findings
        assert "count" in hits[0].message and "_loop" in hits[0].message


# =============================================================================
# Pass 2 rider — durability lint (ISSUE 12)
# =============================================================================

from lighthouse_tpu.analysis import durability  # noqa: E402

_TORN_MODULE = textwrap.dedent(
    '''
    """Seeded torn-write corpus for the durability lint."""

    COL = object()


    def torn_pair(store, root, blk, st):
        store.hot.put(COL, root, blk)
        store.hot.put(COL, root + b"s", st)


    def torn_loop(store, roots):
        for r in roots:
            store.cold.delete(COL, r)


    def atomic_ok(store, root, blk, st):
        store.hot.do_atomically(
            [("put", COL, root, blk), ("put", COL, root + b"s", st)]
        )


    def single_ok(store, key, value):
        store.put_meta(key, value)


    def non_store_ok(cache, a, b):
        cache.put(a, 1)  # receiver is store-shaped? no hints -> skip
        cache.put(b, 2)


    def do_atomically(self, ops):
        for op in ops:
            self.put(op[1], op[2], op[3])


    # independent single-key writes per item, justified
    # lint: allow(torn-write)
    def pragma_ok(store, pairs):
        for k, v in pairs:
            store.hot.put(COL, k, v)
    '''
)


class TestDurabilityLint:
    @pytest.fixture()
    def torn_module(self, tmp_path):
        p = tmp_path / "torn_fixture.py"
        p.write_text(_TORN_MODULE)
        return str(p)

    def test_fixture_corpus(self, torn_module):
        findings = durability.lint_file(torn_module, "torn_fixture.py")
        flagged = {f.context.split("(")[0].replace("def ", "") for f in findings}
        assert flagged == {"torn_pair", "torn_loop"}, findings
        assert all(f.rule == "torn-write" for f in findings)
        # the looped single put counts as a multi-key sequence
        looped = [f for f in findings if "torn_loop" in f.context]
        assert looped and "looped" in looped[0].message

    def test_pragma_and_atomic_exemptions(self, torn_module):
        findings = durability.lint_file(torn_module, "torn_fixture.py")
        joined = " ".join(f.context for f in findings)
        assert "atomic_ok" not in joined
        assert "single_ok" not in joined
        assert "pragma_ok" not in joined      # pragma on the line above
        assert "do_atomically" not in joined  # the seam itself is exempt

    def test_baseline_suppression(self, torn_module, tmp_path):
        """Through the REAL path: lint_tree over a scoped tree plus a
        baseline-file round trip (load_baseline parsing, key-scheme
        match, suppression count) — not a set built from the findings
        themselves, which would pass vacuously."""
        import json as _json
        import shutil

        pkg = tmp_path / "pkg"
        (pkg / "store").mkdir(parents=True)
        shutil.copy(torn_module, pkg / "store" / "torn.py")
        findings, suppressed = durability.lint_tree(
            root=str(pkg), baseline=set()
        )
        assert findings and suppressed == 0
        bl_path = tmp_path / "baseline.json"
        bl_path.write_text(_json.dumps(
            [{"path": f.path, "rule": f.rule, "context": f.context}
             for f in findings]
        ))
        left, suppressed = durability.lint_tree(
            root=str(pkg), baseline=durability.load_baseline(str(bl_path))
        )
        assert not left
        assert suppressed == len(findings)

    def test_clean_tree_and_empty_baseline(self):
        """The shipped persistence scope lints clean AND the checked-in
        baseline is empty — every real multi-key sequence was batched
        through do_atomically (or pragma'd with justification in place)."""
        findings, suppressed = durability.lint_tree()
        assert not findings, "\n".join(str(f) for f in findings)
        assert suppressed == 0
        assert durability.load_baseline() == set()


# =============================================================================
# Pass 6 — device-memory certifier & footprint planner (ISSUE 20)
# =============================================================================

from lighthouse_tpu.analysis import memory as amem  # noqa: E402

# representative tier-1 subset: one fq graph, one tower graph, the fused
# pallas entries (exercises the VMEM sink). The full sweep rides the slow
# lane.
_MEM_GRAPHS = ["fq.mont_mul", "tower.fq2_mul", "pallas.fused_mul"]


@pytest.mark.kernel
class TestMemoryCertifier:
    def test_restricted_cert_green_all_three_backends(self):
        """Clean tree: the representative subset certifies under all three
        conv backends with every row kind present — graph footprints with
        arg/out/temp/peak bytes + per-tier margins, pallas VMEM tile rows,
        and all five subsystem residency families."""
        cert = amem.certify_memory(
            backends=("f64", "digits", "pallas"), batches=(1,),
            graphs=_MEM_GRAPHS,
        )
        bad = [r for r in cert["rows"] if not r["ok"]]
        assert cert["ok"] and cert["n_failed"] == 0, bad[:5]
        kinds = {r["kind"] for r in cert["rows"]}
        assert {"graph_footprint", "vmem_tile", "residency"} <= kinds
        regimes = {
            r["graph"].split("/", 1)[0]
            for r in cert["rows"] if r["kind"] == "graph_footprint"
        }
        assert {"f64@b1", "digits@b1", "pallas@b1"} <= regimes
        fams = [r["graph"] for r in cert["rows"] if r["kind"] == "residency"]
        for fam in ("epoch_mirror", "slasher_spans", "lc_committee_cache",
                    "kzg_tables", "firehose_staging"):
            assert any(fam in g for g in fams), f"no residency row for {fam}"
        row = next(r for r in cert["rows"] if r["kind"] == "graph_footprint")
        for k in ("arg_bytes", "out_bytes", "temp_bytes", "peak_bytes",
                  "min_tier", "margin_bytes"):
            assert k in row, row
        assert row["peak_bytes"] >= row["arg_bytes"] + row["out_bytes"]
        # the certified clean-tree VMEM tiles all fit the declared caps
        vrows = [r for r in cert["rows"] if r["kind"] == "vmem_tile"]
        assert vrows
        assert all(r["est_vmem_bytes"] <= 16 * 2**20 for r in vrows)
        # XLA's lowered-computation cost analysis cross-checks the
        # representative allowlist rows
        assert any("xla_bytes_accessed" in r for r in cert["rows"])

    @pytest.mark.slow
    def test_full_cert_every_registry_graph(self):
        """The acceptance sweep: EVERY bounds-registry graph certifies under
        all three backends x both batch regimes (the analysis CLI's
        default-on configuration)."""
        cert = amem.certify_memory()
        bad = [r for r in cert["rows"] if not r["ok"]]
        assert cert["ok"] and cert["n_failed"] == 0, bad[:5]
        covered = {
            r["graph"].split("/", 1)[1]
            for r in cert["rows"] if r["kind"] == "graph_footprint"
        }
        registry = {name for name, _, _ in bounds.graph_registry(1)}
        assert registry <= covered, registry - covered

    def test_mutation_widened_plane_fails(self, monkeypatch):
        """Seeded over-budget mutation #1: a widened slasher span plane
        (LIGHTHOUSE_SLASHER_HISTORY at 2^24 epochs — ~128 TB at 1M
        validators) must turn the cert red on its residency row."""
        monkeypatch.setenv("LIGHTHOUSE_SLASHER_HISTORY", str(1 << 24))
        cert = amem.certify_memory(
            backends=("f64",), batches=(1,), graphs=["fq.mont_mul"]
        )
        bad = [r for r in cert["rows"] if not r["ok"]]
        assert not cert["ok"]
        assert any("slasher_spans" in r["graph"] for r in bad), bad
        assert all(r["min_tier"] is None for r in bad)

    def test_mutation_unbounded_pad_fails(self):
        """Seeded over-budget mutation #2: an unbounded pad (a graph
        materializing a 1 TiB temp) fits no declared finite tier and fails
        exactly like a tripped bound."""
        def padded(x):
            return jnp.zeros((1 << 38,), jnp.uint32)  # 2^40 B = 1 TiB

        rows = amem.certify_graph_callable(
            padded, (jax.ShapeDtypeStruct((1,), jnp.uint32),)
        )
        assert rows and not rows[0]["ok"]
        assert rows[0]["min_tier"] is None
        assert all(m < 0 for m in rows[0]["margin_bytes"].values())

    def test_packed_vmem_tile_rows(self):
        """The packed fq12 product (128 rows at batch 1) records its VMEM
        tile under the packed layout, 8 lanes a block, within the declared
        16 MiB tier; at batch 32 (4,096 rows) the same graph records a
        full-block digit-major tile."""
        cert = amem.certify_memory(
            backends=("pallas",), batches=(1, 32),
            graphs=["pallas.execute_fq12_mul_packed"],
        )
        assert cert["ok"] and cert["n_failed"] == 0
        vrows = [r for r in cert["rows"] if r["kind"] == "vmem_tile"]
        by_layout = {r["layout"]: r for r in vrows}
        assert set(by_layout) == {"packed", "digit_major"}
        assert by_layout["packed"]["group"] == 8
        assert by_layout["digit_major"]["group"] == 1
        assert all(r["est_vmem_bytes"] <= 16 * 2**20 for r in vrows)

    def test_mutation_oversized_vmem_tile_fails(self, monkeypatch):
        """Seeded over-budget mutation #3: an undeclared-tier pallas tile
        (row tile forced to 8192 — ~24 MiB of double-buffered operand
        blocks vs the declared 16 MiB VMEM cap) must turn the cert red on
        its VMEM row."""
        from lighthouse_tpu.ops.bls import pallas_kernels as pk

        monkeypatch.setattr(pk, "_row_tile", lambda rows, L: 8192)
        cert = amem.certify_memory(
            backends=("pallas",), batches=(1,), graphs=["pallas.fused_mul"]
        )
        bad = [
            r for r in cert["rows"]
            if not r["ok"] and r["kind"] == "vmem_tile"
        ]
        assert bad and not cert["ok"]
        assert all(r["est_vmem_bytes"] > 16 * 2**20 for r in bad)

    def test_planner_monotone_in_tier(self):
        """max_safe_shape is monotone: a larger tier certifies a batch at
        least as large, for every certified graph."""
        cert = amem.certify_memory(
            backends=("f64",), batches=(1, 32), graphs=["fq.mont_mul"]
        )
        order = ["tpu_v5e", "tpu_v4", "tpu_v5p", "cpu_proxy"]
        assert cert["peaks"]
        for graph in cert["peaks"]:
            batches = [
                amem.max_safe_shape(graph, tier, cert=cert) for tier in order
            ]
            assert all(b is not None for b in batches), (graph, batches)
            assert batches == sorted(batches), (graph, batches)

    def test_rung_fit_gates_oversized_shapes(self, monkeypatch):
        """The rung gate arithmetic: a 1M-validator slasher rung at the
        reference 4096-epoch history (~32 GB of span planes) cannot fit the
        16 GiB tpu_v5e tier, while the 32k rung fits with margin."""
        monkeypatch.setenv("BENCH_SLASHER_HISTORY", "4096")
        v = amem.rung_fit("slasher", 0, 0, 1_048_576, 0, tier="tpu_v5e")
        assert not v["fits"] and v["margin_bytes"] < 0
        assert v["domain"] == "slasher"
        v2 = amem.rung_fit("slasher", 0, 0, 32_768, 0, tier="tpu_v5e")
        assert v2["fits"] and v2["margin_bytes"] > 0
        # unbounded CPU proxy never blocks
        v3 = amem.rung_fit("slasher", 0, 0, 1_048_576, 0, tier="cpu_proxy")
        assert v3["fits"] and v3["cap_bytes"] is None

    def test_oom_fault_record_carries_memory_context(self):
        """Satellite: an oom-classified fault record is enriched with the
        faulting domain's static-memory context (tier cap + margins), so a
        demotion report says what the model predicted."""
        from lighthouse_tpu.resilience import faults

        rec = faults.record_fault(
            "slasher.sweep", MemoryError("RESOURCE_EXHAUSTED"),
            domain="slasher_device",
        )
        assert rec.kind is faults.FaultKind.OOM
        assert rec.memory is not None
        assert rec.memory["tier_hbm_bytes"] == amem.DEVICE_TIERS[
            amem.DEFAULT_TIER
        ]["hbm_bytes"]
        assert rec.as_dict()["memory"] == rec.memory
        # non-OOM faults stay unenriched
        rec2 = faults.record_fault(
            "slasher.sweep", RuntimeError("UNAVAILABLE: reset by peer"),
            domain="slasher_device",
        )
        assert rec2.memory is None and "memory" not in rec2.as_dict()


@pytest.mark.kernel
class TestResidencyParity:
    """The five static resident_bytes models vs the subsystems' ACTUAL
    device_put accounting — the cert's residency rows are only as good as
    these formulas."""

    def test_pow2_bucket_twins_every_allocation_site(self):
        from lighthouse_tpu.epoch_engine import kernels as ek
        from lighthouse_tpu.firehose import sharding as fs
        from lighthouse_tpu.slasher import engine as se

        for n in (1, 7, 255, 256, 257, 5000, 262_144, 1_048_576):
            assert amem._pow2_bucket(n, 256) == ek.bucket(n)
            assert amem._pow2_bucket(n, 256) == se._bucket(n, 256)
            assert amem._pow2_bucket(n, 4) == fs._bucket(n, floor=4)

    def test_epoch_mirror_vs_device_put_accounting(self):
        """A real full gather uploads EXACTLY the modeled registry-column
        bytes (MirrorStats counts device_put nbytes), and the residency
        gauge lands on the same figure."""
        from types import SimpleNamespace

        from lighthouse_tpu.epoch_engine.kernels import FAR_FUTURE_EPOCH
        from lighthouse_tpu.epoch_engine.mirror import RegistryMirror
        from lighthouse_tpu.utils import metrics

        vs = [
            SimpleNamespace(
                effective_balance=32_000_000_000, slashed=False,
                activation_epoch=0, exit_epoch=FAR_FUTURE_EPOCH,
                withdrawable_epoch=FAR_FUTURE_EPOCH,
                activation_eligibility_epoch=0,
                withdrawal_credentials=b"\x01" + bytes(31),
            )
            for _ in range(5)
        ]
        state = SimpleNamespace(validators=vs)
        m = RegistryMirror()
        m._full_gather(state, len(vs))
        want = amem.epoch_mirror_bytes(5, include_epoch_planes=False)
        assert m.stats.host_to_device_bytes == want
        assert [v for _, _, v in metrics.EPOCH_MIRROR_BYTES.collect()] == [
            want
        ]

    def test_slasher_planes_vs_allocation(self):
        """empty_planes_np allocates exactly the modeled bytes (the device
        upload device_puts those same arrays)."""
        from lighthouse_tpu.slasher import engine as se

        for v, hist in ((1000, 64), (32_768, 4096)):
            planes = se.empty_planes_np(se._bucket(v, 256), hist)
            assert sum(p.nbytes for p in planes) == amem.slasher_span_bytes(
                v, history=hist
            )

    def test_lc_committee_cache_vs_allocation(self):
        """The model equals the nbytes of the exact array _cache_arr
        device-transfers: [bucket(p, 4), 512, 3, 25] u64."""
        from lighthouse_tpu.firehose.sharding import _bucket

        for p in (1, 4, 5, 64):
            arr = np.zeros((_bucket(p, floor=4), 512, 3, 25), np.uint64)
            assert amem.lc_committee_cache_bytes(p) == arr.nbytes

    def test_kzg_tables_vs_built_tables(self):
        """The model equals the ACTUAL table bytes a CellEngine builds (the
        tiny insecure-setup geometry pins every term, including the
        [cells, 6, 25] z2 chain table), and the gauge lands on it."""
        from lighthouse_tpu.kzg import Kzg
        from lighthouse_tpu.kzg.cells import CellContext
        from lighthouse_tpu.kzg.engine import CellEngine
        from lighthouse_tpu.kzg.setup import insecure_setup
        from lighthouse_tpu.utils import metrics

        ctx = CellContext(Kzg(insecure_setup(16, n_g2=5)),
                          cells_per_ext_blob=8)
        eng = CellEngine(ctx)
        tables = eng._build_tables()
        got = sum(a.nbytes for a in tables) + eng._z2_tab.nbytes
        assert got == amem.kzg_table_bytes(cells=ctx.cells, k=ctx.k)
        assert [v for _, _, v in metrics.KZG_TABLE_BYTES.collect()] == [got]

    def test_firehose_staging_vs_staged_arrays(self):
        """stage_indexed_shards produces exactly the modeled per-tick
        bytes across the arrays put_staged device-transfers."""
        from lighthouse_tpu.bls import tpu_backend as tb

        items = [[([0, 1, 2], b"msg-%d" % i, bytes(96)) for i in range(2)]]
        staged = tb.stage_indexed_shards(items, shard_cap=4)
        got = sum(
            np.asarray(staged[k]).nbytes for k in tb._STAGED_SET_KEYS
        )
        assert got == amem.staged_tick_bytes(staged["n_pad"],
                                             staged["k_pad"])
        assert got == amem.firehose_staging_bytes(
            max_batch=4, prep_depth=0, k_pad=staged["k_pad"]
        )


class TestBoundedCacheAudit:
    def test_declared_cache_bounds_hold(self):
        """Satellite: the existing bounded caches enforce their declared
        bounds — the data-column/blob pending cache evicts past
        MAX_PENDING, the early-attester cache is a single slot by
        construction, and the LC update store's hot map prunes to keep."""
        from lighthouse_tpu.beacon_chain.data_availability import (
            DataAvailabilityChecker,
        )
        from lighthouse_tpu.beacon_chain.early_attester_cache import (
            EarlyAttesterCache,
        )
        from lighthouse_tpu.light_client.update_store import (
            LightClientUpdateStore,
        )

        da = DataAvailabilityChecker(spec=None)
        assert da.MAX_PENDING == 64
        with da._lock:
            for i in range(da.MAX_PENDING + 16):
                da._touch(i.to_bytes(32, "big"))
            assert len(da._pending) == da.MAX_PENDING
        eac = EarlyAttesterCache()
        unbounded = {
            k: v for k, v in vars(eac).items()
            if isinstance(v, (dict, list, set))
        }
        assert not unbounded, unbounded
        us = LightClientUpdateStore(spec=None)
        us._best = {i: object() for i in range(100)}
        assert us.prune_hot(8) == 92
        assert len(us) == 8

