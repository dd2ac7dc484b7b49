"""Fault-domain supervisor + deterministic chaos harness (ISSUE 7).

Layers under test, bottom-up:

* the fault taxonomy/classifier and the env-gated deterministic injector;
* the backend supervisor: watchdog hang detection, bounded transient
  retries, the HEALTHY -> DEGRADED -> QUARANTINED circuit breaker, and the
  degradation ladder (full -> reduced -> CPU fallback);
* the firehose engine under injected device faults: bisection fallback
  keeps exact verdicts (no false-verify) with bounded retries, and
  shutdown enforces a hard join deadline against a wedged device call;
* the chain's batched BLS path riding the ``bls_device`` ladder down to
  the pure-Python oracle (native backend, real crypto);
* the epoch engine's device -> numpy demotion with field-for-field state
  parity mid-advance, then re-promotion;
* the chaos scenario: a 4-node network for 4 epochs under injected device
  faults every K batches, seeded gossip loss, and a node crash/restart —
  asserting liveness (heads agree, finalization advances), zero
  false-verifies, the drop-rate SLO, and a visible demote/re-promote cycle.
"""

import dataclasses
import threading
import time

import numpy as np
import pytest

import lighthouse_tpu  # noqa: F401
from lighthouse_tpu import bls, epoch_engine, resilience
from lighthouse_tpu.beacon_chain.chain import BeaconChain
from lighthouse_tpu.beacon_processor.processor import WorkType
from lighthouse_tpu.firehose import FirehoseConfig, FirehoseEngine
from lighthouse_tpu.resilience import (
    BackendSupervisor,
    FaultKind,
    HealthState,
    InjectedFault,
    SupervisedFault,
    SupervisorConfig,
    WatchdogTimeout,
    classify,
    classify_text,
    injector,
    run_with_deadline,
)
from lighthouse_tpu.resilience import faults as faults_mod
from lighthouse_tpu.testing import StateHarness
from lighthouse_tpu.testing.local_network import LocalNetwork
from lighthouse_tpu.types.spec import minimal_spec
from lighthouse_tpu.utils.metrics import REGISTRY
from lighthouse_tpu.utils.slot_clock import ManualSlotClock


@pytest.fixture(autouse=True)
def _clean_fault_domain():
    """Every test starts from inert injection, HEALTHY supervisors, an
    empty fault ring, and pristine per-domain configs."""
    injector.clear()
    saved = {
        name: dataclasses.replace(s.config)
        for name, s in resilience.all_supervisors().items()
    }
    resilience.reset_all()
    faults_mod.clear_fault_log()
    yield
    for name, sup in resilience.all_supervisors().items():
        # supervisors created mid-test get the stock config back too — a
        # test-tuned policy must never leak into other test modules
        sup.config = saved.get(name, SupervisorConfig())
    injector.clear()
    resilience.reset_all()


def _fast_config(**kw) -> SupervisorConfig:
    base = dict(
        deadline_s=5.0, max_retries=2, backoff_base_s=0.001,
        backoff_max_s=0.005, promote_after=2, probe_every=2,
        probation_s=0.05,
    )
    base.update(kw)
    return SupervisorConfig(**base)


# -- taxonomy / classifier ---------------------------------------------------------


class TestClassifier:
    def test_type_first_classification(self):
        assert classify(WatchdogTimeout("s", 1.0)) == FaultKind.HANG
        assert classify(TimeoutError("whatever")) == FaultKind.HANG
        assert classify(MemoryError()) == FaultKind.OOM
        assert classify(AssertionError("limb bound")) == FaultKind.CORRUPTION
        assert classify(FloatingPointError("overflow")) == FaultKind.CORRUPTION

    def test_marker_classification(self):
        class XlaRuntimeError(Exception):
            pass

        assert classify(
            XlaRuntimeError("RESOURCE_EXHAUSTED: out of memory while trying "
                            "to allocate 2.1G")
        ) == FaultKind.OOM
        assert classify(
            XlaRuntimeError("UNAVAILABLE: connection reset by peer")
        ) == FaultKind.TRANSIENT
        assert classify(
            XlaRuntimeError("INVALID_ARGUMENT: limb bound assert tripped")
        ) == FaultKind.CORRUPTION
        assert classify(ValueError("totally novel")) == FaultKind.TRANSIENT

    def test_subprocess_note_classification(self):
        # bench.py's rung notes (run_inner)
        assert classify_text("probe hung (> 120s)") == FaultKind.HANG
        assert classify_text("shape (16x64) exceeded 1800s") == FaultKind.HANG
        assert classify_text(
            "probe exited rc=1: RESOURCE_EXHAUSTED"
        ) == FaultKind.OOM
        # OOM outranks the generic hang markers: "limit exceeded" inside a
        # RESOURCE_EXHAUSTED status must NOT be read as a hang (a retried
        # shape would just OOM again)
        assert classify_text(
            "RESOURCE_EXHAUSTED: memory limit exceeded while allocating"
        ) == FaultKind.OOM

    def test_injected_fault_carries_kind(self):
        e = InjectedFault(FaultKind.OOM, "stage", 3)
        assert classify(e) == FaultKind.OOM

    def test_record_ring_and_metrics(self):
        faults_mod.record_fault("t.stage", MemoryError(), domain="t")
        recent = resilience.recent_faults(4)
        assert recent and recent[-1]["kind"] == "oom"
        assert "resilience_faults_total" in REGISTRY.render()


# -- watchdog ----------------------------------------------------------------------


class TestWatchdog:
    def test_result_and_exception_passthrough(self):
        assert run_with_deadline("t", lambda: 41 + 1, 5.0) == 42
        with pytest.raises(KeyError):
            run_with_deadline("t", lambda: {}["missing"], 5.0)

    def test_hang_detection(self):
        t0 = time.monotonic()
        with pytest.raises(WatchdogTimeout):
            run_with_deadline("t.hang", lambda: time.sleep(2.0), 0.05)
        assert time.monotonic() - t0 < 1.0  # caller reclaimed promptly


# -- deterministic injector --------------------------------------------------------


class TestInjector:
    def test_every_and_times(self):
        injector.install("stage=u.s;mode=raise;kind=oom;every=3;times=2")
        fired = []
        for _ in range(12):
            try:
                injector.before_call("u.s")
                fired.append(False)
            except InjectedFault as e:
                assert classify(e) == FaultKind.OOM
                fired.append(True)
        assert fired == [False, False, True] * 2 + [False] * 6

    def test_at_nth_call_only(self):
        injector.install("stage=u.n;at=2")
        outcomes = []
        for _ in range(4):
            try:
                injector.before_call("u.n")
                outcomes.append("ok")
            except InjectedFault:
                outcomes.append("boom")
        assert outcomes == ["ok", "boom", "ok", "ok"]

    def test_wildcard_and_rung_targeting(self):
        injector.install("stage=u.lad/cpu_fallback;at=1")
        injector.before_call("u.lad")  # bare stage untouched
        with pytest.raises(InjectedFault):
            injector.before_call("u.lad/cpu_fallback")
        injector.clear()
        injector.install("stage=u.wild*;at=1")
        with pytest.raises(InjectedFault):
            injector.before_call("u.wildcard.anything")

    def test_corrupt_mode_classifies_as_corruption(self):
        injector.install("stage=u.c;mode=corrupt;at=1")
        with pytest.raises(InjectedFault) as ei:
            injector.before_call("u.c")
        assert classify(ei.value) == FaultKind.CORRUPTION

    def test_env_gating(self, monkeypatch):
        monkeypatch.setenv(
            resilience.INJECT_ENV_VAR, "stage=u.env;mode=raise;at=1"
        )
        injector.reload_env()
        assert injector.active()
        with pytest.raises(InjectedFault):
            injector.before_call("u.env")
        monkeypatch.delenv(resilience.INJECT_ENV_VAR)
        injector.reload_env()
        assert not injector.active()

    def test_bad_spec_rejected(self):
        with pytest.raises(ValueError):
            injector.install("mode=raise;at=1")  # no stage
        with pytest.raises(ValueError):
            injector.install("stage=x;mode=explode")


# -- supervisor / health machine ---------------------------------------------------


class TestSupervisor:
    def _ladder(self, calls):
        def full():
            calls["full"] += 1
            return "full"

        def reduced():
            calls["reduced"] += 1
            return "reduced"

        def fb():
            calls["fb"] += 1
            return "fb"

        return (("device_full", full), ("device_reduced", reduced),
                ("cpu_fallback", fb))

    def test_transient_retried_in_place(self):
        sup = BackendSupervisor("u.retry", _fast_config())
        n = {"i": 0}

        def flaky():
            n["i"] += 1
            if n["i"] < 3:
                raise ConnectionError("reset by peer")
            return "ok"

        assert sup.run_ladder("u.r", (("device_full", flaky),)) == "ok"
        assert sup.retries == 2 and sup.state == HealthState.HEALTHY
        assert sup.demotions == 0

    def test_retries_are_bounded_then_ladder_descends(self):
        sup = BackendSupervisor("u.bound", _fast_config(max_retries=1))
        calls = dict.fromkeys(("full", "reduced", "fb"), 0)
        attempts = {"n": 0}

        def always_transient():
            attempts["n"] += 1
            raise ConnectionError("reset")

        rungs = (("device_full", always_transient),) + self._ladder(calls)[1:]
        assert sup.run_ladder("u.b", rungs) == "reduced"
        assert attempts["n"] == 2  # 1 try + max_retries=1, no more
        assert sup.state == HealthState.DEGRADED

    def test_oom_demotes_without_retry(self):
        sup = BackendSupervisor("u.oom", _fast_config())
        calls = dict.fromkeys(("full", "reduced", "fb"), 0)
        tries = {"n": 0}

        def oom():
            tries["n"] += 1
            raise MemoryError()

        rungs = (("device_full", oom),) + self._ladder(calls)[1:]
        assert sup.run_ladder("u.o", rungs) == "reduced"
        assert tries["n"] == 1          # same-shape retry is futile
        assert sup.demotions == 1 and sup.fallback_calls == 1

    def test_corruption_jumps_to_cpu_and_quarantines(self):
        sup = BackendSupervisor("u.cor", _fast_config())
        calls = dict.fromkeys(("full", "reduced", "fb"), 0)

        def corrupt():
            raise AssertionError("limb bound assert tripped")

        rungs = (("device_full", corrupt),) + self._ladder(calls)[1:]
        assert sup.run_ladder("u.c", rungs) == "fb"
        assert calls["reduced"] == 0    # nothing device-shaped is trusted
        assert sup.state == HealthState.QUARANTINED

    def test_degrade_quarantine_probation_repromote(self):
        sup = BackendSupervisor("u.cycle", _fast_config())
        calls = dict.fromkeys(("full", "reduced", "fb"), 0)
        broken = {"on": True}

        def full():
            calls["full"] += 1
            if broken["on"]:
                raise MemoryError()
            return "full"

        rungs = (("device_full", full),) + self._ladder(calls)[1:]
        assert sup.run_ladder("u.y", rungs) == "reduced"
        assert sup.state == HealthState.DEGRADED
        # the probe (every probe_every-th call) fails too -> quarantine
        results = [sup.run_ladder("u.y", rungs) for _ in range(3)]
        assert sup.state == HealthState.QUARANTINED
        # quarantined: straight to the fallback, device untouched
        n_full = calls["full"]
        assert sup.run_ladder("u.y", rungs) == "fb"
        assert calls["full"] == n_full
        # device heals; probation expires; probe -> DEGRADED -> HEALTHY
        broken["on"] = False
        time.sleep(sup.config.probation_s + 0.02)
        results = [sup.run_ladder("u.y", rungs) for _ in range(6)]
        assert "full" in results
        assert sup.state == HealthState.HEALTHY, sup.snapshot()
        assert sup.promotions >= 2 and sup.demotions >= 2

    def test_exhausted_ladder_fails_closed(self):
        sup = BackendSupervisor("u.exh", _fast_config(max_retries=0))

        def boom():
            raise MemoryError()

        with pytest.raises(SupervisedFault):
            sup.run_ladder("u.e", (("device_full", boom), ("cpu", boom)))
        assert sup.exhausted == 1

    def test_hang_goes_to_watchdog_and_descends(self):
        sup = BackendSupervisor("u.hang", _fast_config(deadline_s=0.05))
        calls = dict.fromkeys(("full", "reduced", "fb"), 0)

        def wedged():
            time.sleep(0.4)
            return "late"

        rungs = (("device_full", wedged),) + self._ladder(calls)[1:]
        assert sup.run_ladder("u.h", rungs) == "reduced"
        assert sup.watchdog_timeouts == 1
        assert sup.state == HealthState.DEGRADED
        rec = resilience.recent_faults(4)[-1]
        assert rec["kind"] == "hang" and rec["domain"] == "u.hang"

    def test_hung_thread_cap_hard_quarantines(self):
        sup = BackendSupervisor(
            "u.cap", _fast_config(deadline_s=0.02, max_hung_threads=2,
                                  probation_s=0.01),
        )
        release = threading.Event()
        calls = dict.fromkeys(("full", "reduced", "fb"), 0)

        def wedged_forever():
            release.wait(5.0)

        rungs = (("device_full", wedged_forever),) + self._ladder(calls)[1:]
        for _ in range(4):
            time.sleep(0.02)  # let probation expire so the device is re-probed
            sup.run_ladder("u.k", rungs)
        snap = sup.snapshot()
        assert snap["hard_quarantined"]
        assert snap["watchdog_timeouts"] == 2  # capped: no more device probes
        assert not sup.device_allowed()
        # under hard quarantine a ladder with NO device-free (cpu*) rung
        # fails closed instead of feeding another thread into the wedge
        with pytest.raises(SupervisedFault):
            sup.run_ladder(
                "u.k", (("device_full", wedged_forever),
                        ("device_reduced", wedged_forever)),
            )
        # once the stranded calls actually return, the hard quarantine
        # lifts and the domain recovers through normal probation
        release.set()
        deadline = time.monotonic() + 5.0
        while sup.snapshot()["hard_quarantined"] and time.monotonic() < deadline:
            time.sleep(0.01)
        assert not sup.snapshot()["hard_quarantined"]
        assert sup.snapshot()["hung_threads"] == 0

    def test_seeded_backoff_is_deterministic(self):
        a = BackendSupervisor("u.da", _fast_config(seed=7))
        b = BackendSupervisor("u.db", _fast_config(seed=7))
        assert [a._backoff(i) for i in (1, 2, 3)] == [
            b._backoff(i) for i in (1, 2, 3)
        ]

    def test_injection_targets_primary_rung_only(self):
        sup = BackendSupervisor("u.inj", _fast_config())
        calls = dict.fromkeys(("full", "reduced", "fb"), 0)
        injector.install("stage=u.i;mode=raise;kind=oom;every=1")
        # the bare-stage plan hits rung 0 every time; the reduced rung's
        # injection name is "u.i/device_reduced", untouched -> serves
        assert sup.run_ladder("u.i", self._ladder(calls)) == "reduced"
        assert calls["full"] == 0


# -- firehose under injected device faults -----------------------------------------


class _ItemVerifier:
    """Batched fake verifier over ('id',) items; ids in ``bad`` fail."""

    def __init__(self, bad=()):
        self.bad = set(bad)
        self.calls = []

    def __call__(self, items):
        self.calls.append(len(items))
        return not any(it[0] in self.bad for it in items)


class TestFirehoseResilience:
    def _engine(self, verifier, sup, fallback=None, max_batch=4):
        return FirehoseEngine(
            prepare_fn=lambda ps: [([(p,)], None) for p in ps],
            verify_items_fn=verifier,
            config=FirehoseConfig(max_batch=max_batch),
            synchronous=True,
            supervisor=sup,
            fallback_verify_fn=fallback,
        )

    def test_transient_faults_are_invisible_to_verdicts(self):
        sup = BackendSupervisor("fh.t", _fast_config())
        vf = _ItemVerifier()
        injector.install(
            "stage=firehose.device_verify;mode=raise;kind=transient;every=2"
        )
        engine = self._engine(vf, sup)
        verdicts = {}
        for i in range(12):
            engine.submit(i, callback=lambda p, ok, m: verdicts.__setitem__(p, ok))
        engine.drain()
        assert all(verdicts[i] for i in range(12))
        assert sup.retries >= 1 and engine.stats().device_faults == 0

    def test_bisection_under_repeated_device_faults(self):
        """The satellite case: poisoned sets + device faults during the
        bisection cascade — exact culprits isolated, bounded retries, zero
        false verifies."""
        bad = {3, 9}
        sup = BackendSupervisor("fh.b", _fast_config())
        vf = _ItemVerifier(bad)
        injector.install(
            "stage=firehose.device_verify;mode=raise;kind=transient;every=3"
        )
        engine = self._engine(vf, sup)
        verdicts = {}
        for i in range(16):
            engine.submit(i, callback=lambda p, ok, m: verdicts.__setitem__(p, ok))
        engine.drain()
        assert verdicts == {i: i not in bad for i in range(16)}
        st = engine.stats()
        assert st.verified == 14 and st.rejected == 2 and st.errored == 0
        # bounded: every injected fault burned at most max_retries retries
        assert sup.retries <= sup.faults_seen * sup.config.max_retries
        assert sup.exhausted == 0

    def test_oom_ladder_demotes_then_repromotes(self):
        sup = BackendSupervisor(
            "fh.o", _fast_config(promote_after=1, probe_every=2)
        )
        vf = _ItemVerifier()
        served_fallback = []

        def fallback(items):
            served_fallback.append(len(items))
            return True

        injector.install(
            "stage=firehose.device_verify;mode=raise;kind=oom;at=1;times=1"
        )
        engine = self._engine(vf, sup, fallback=fallback)
        verdicts = {}
        for i in range(16):
            engine.submit(i, callback=lambda p, ok, m: verdicts.__setitem__(p, ok))
        engine.drain()
        assert all(verdicts[i] for i in range(16))
        assert sup.demotions >= 1 and sup.promotions >= 1
        assert sup.state == HealthState.HEALTHY
        assert engine.resilience()["demotions"] >= 1

    def test_corruption_serves_from_cpu_fallback_only(self):
        sup = BackendSupervisor("fh.c", _fast_config())
        vf = _ItemVerifier()
        fb = _ItemVerifier(bad={5})
        injector.install(
            "stage=firehose.device_verify;mode=corrupt;every=1"
        )
        engine = self._engine(vf, sup, fallback=fb)
        verdicts = {}
        for i in range(8):
            engine.submit(i, callback=lambda p, ok, m: verdicts.__setitem__(p, ok))
        engine.drain()
        # the fallback's OWN verdicts hold: bad id rejected, rest verified —
        # and the corrupt device never contributed a verdict
        assert verdicts == {i: i != 5 for i in range(8)}
        assert vf.calls == []  # the device rung never served anything
        assert sup.state == HealthState.QUARANTINED

    def test_exhausted_ladder_counts_errored_with_fault_record(self):
        sup = BackendSupervisor("fh.x", _fast_config(max_retries=0))
        vf = _ItemVerifier()
        injector.install(
            "stage=firehose.device_verify;mode=raise;kind=oom;every=1|"
            "stage=firehose.device_verify/device_reduced;mode=raise;kind=oom;every=1"
        )
        engine = self._engine(vf, sup)  # no CPU fallback rung attached
        verdicts = {}
        for i in range(4):
            engine.submit(i, callback=lambda p, ok, m: verdicts.__setitem__(p, ok))
        engine.drain()
        # no rung could answer: fail closed, counted + recorded, not silent
        assert verdicts == dict.fromkeys(range(4), False)
        st = engine.stats()
        assert st.errored == 4 and st.device_faults >= 1
        kinds = {r["stage"] for r in resilience.recent_faults(16)}
        assert "firehose.verify_batch" in kinds

    def test_stop_enforces_hard_join_deadline_on_wedged_device(self):
        release = threading.Event()

        def wedged(items):
            release.wait(timeout=20.0)
            return True

        engine = FirehoseEngine(
            prepare_fn=lambda ps: [([(p,)], None) for p in ps],
            verify_items_fn=wedged,
            config=FirehoseConfig(max_batch=2, deadline_s=0.001),
        )
        try:
            for i in range(8):
                engine.submit(i)
            t0 = time.monotonic()
            clean = engine.stop(drain_timeout=0.5)
            dt = time.monotonic() - t0
            assert not clean            # the wedge was detected, not waited out
            assert dt < 5.0
            stages = [r["stage"] for r in resilience.recent_faults(16)]
            assert "firehose.shutdown" in stages
            # the prep thread must have been released by the queue abort
            prep = [t for t in engine._threads if "prep" in t.name]
            for t in prep:
                t.join(timeout=2.0)
            assert not any(t.is_alive() for t in prep)
        finally:
            release.set()

    def test_watchdog_reclaims_hung_device_call(self):
        sup = BackendSupervisor("fh.h", _fast_config(deadline_s=0.05))
        fb = _ItemVerifier()
        injector.install(
            "stage=firehose.device_verify;mode=hang;hang_s=0.4;every=1;times=1"
        )
        engine = self._engine(_ItemVerifier(), sup, fallback=fb)
        verdicts = {}
        for i in range(4):
            engine.submit(i, callback=lambda p, ok, m: verdicts.__setitem__(p, ok))
        t0 = time.monotonic()
        engine.drain()
        assert time.monotonic() - t0 < 5.0
        assert all(verdicts[i] for i in range(4))
        assert sup.watchdog_timeouts == 1


# -- the chain's BLS ladder (real crypto, native backend) --------------------------


@pytest.fixture(scope="module")
def native_chain():
    prev = bls.get_backend()
    bls.set_backend("native")
    spec = minimal_spec()
    h = StateHarness(spec, n_validators=32)
    clock = ManualSlotClock(0)
    chain = BeaconChain(spec, h.state.copy(), slot_clock=clock)
    for slot in range(1, 6):
        clock.set_slot(slot)
        block = h.produce_block(slot)
        h.apply_block(block)
        chain.process_block(block)
    yield spec, h, chain
    bls.set_backend(prev)


class TestChainLadder:
    def _atts(self, h, chain):
        return h.unaggregated_attestations_for_slot(
            chain.head.state, int(chain.head.slot), chain.head.root
        )

    def test_oom_demotes_to_oracle_and_repromotes(self, native_chain):
        spec, h, chain = native_chain
        sup = resilience.bls_supervisor()
        sup.config = _fast_config(promote_after=1, probe_every=1)
        sup.reset()
        injector.install("stage=bls.batch_verify;mode=raise;kind=oom;at=1;times=1")
        atts = self._atts(h, chain)[:3]
        results = chain.verify_unaggregated_attestations(atts)
        # the faulted device rung fell through to the pure-Python oracle:
        # every honest attestation still verified
        assert all(not isinstance(r[1], Exception) for r in results)
        assert sup.demotions == 1 and sup.fallback_calls >= 1
        # next call probes the primary rung and re-promotes
        results = chain.verify_unaggregated_attestations(self._atts(h, chain)[:2])
        assert all(not isinstance(r[1], Exception) for r in results)
        assert sup.state == HealthState.HEALTHY and sup.promotions >= 1

    def test_no_false_verify_under_transient_chaos(self, native_chain):
        spec, h, chain = native_chain
        sup = resilience.bls_supervisor()
        sup.config = _fast_config()
        sup.reset()
        injector.install(
            "stage=bls.batch_verify;mode=raise;kind=transient;every=2"
        )
        atts = self._atts(h, chain)
        assert len(atts) >= 4
        atts[1].signature = atts[2].signature  # poison
        results = chain.verify_unaggregated_attestations(atts)
        errs = [i for i, r in enumerate(results) if isinstance(r[1], Exception)]
        assert errs == [1]              # exact culprit, despite the chaos
        assert sup.retries >= 1 and sup.exhausted == 0


# -- epoch engine demotion parity --------------------------------------------------


@pytest.mark.kernel
class TestEpochDemotionParity:
    def test_device_numpy_demotion_parity_mid_advance(self):
        """Three epoch boundaries on the device backend with the SECOND
        sweep faulted: the engine demotes that boundary to numpy with the
        state untouched, re-promotes for the third, and the final state is
        field-for-field identical to a pure-numpy twin."""
        from test_epoch_engine import _assert_field_parity, _random_state, _spec
        from lighthouse_tpu.state_transition.per_epoch import process_epoch

        spec = _spec("altair")
        prev_backend = epoch_engine.get_backend()
        sup = resilience.epoch_supervisor()
        sup.config = _fast_config()
        sup.reset()
        state = _random_state(spec, "altair", seed=5)
        a, b = state.copy(), state.copy()
        spe = spec.preset.SLOTS_PER_EPOCH
        injector.install("stage=epoch.sweep;mode=raise;kind=oom;at=2")
        try:
            for twin, backend in ((a, "numpy"), (b, "device")):
                epoch_engine.set_backend(backend)
                for _ in range(3):
                    process_epoch(spec, twin)
                    twin.slot += spe
        finally:
            epoch_engine.set_backend(prev_backend)
        _assert_field_parity(a, b, "altair")
        snap = sup.snapshot()
        assert snap["demotions"] >= 1          # the faulted boundary
        assert snap["fallback_calls"] >= 1     # served by the numpy twin
        assert snap["faults"] >= 1
        # the third boundary ran on the device again (mirror re-bound)
        m = epoch_engine.engine_stats(b)
        assert m is not None and m["epochs"] >= 1

    def test_quarantined_epoch_domain_skips_device_entirely(self):
        from test_epoch_engine import _random_state, _spec
        from lighthouse_tpu.state_transition.per_epoch import process_epoch

        spec = _spec("altair")
        prev_backend = epoch_engine.get_backend()
        sup = resilience.epoch_supervisor()
        sup.config = _fast_config(probation_s=60.0)
        sup.reset()
        state = _random_state(spec, "altair", seed=9)
        twin = state.copy()
        injector.install("stage=epoch.sweep;mode=raise;kind=oom;every=1;times=4")
        try:
            epoch_engine.set_backend("device")
            process_epoch(spec, state)         # fault -> DEGRADED
            state.slot += spec.preset.SLOTS_PER_EPOCH
            process_epoch(spec, state)         # fault -> QUARANTINED
            assert sup.state == HealthState.QUARANTINED
            calls_before = sup.calls
            state.slot += spec.preset.SLOTS_PER_EPOCH
            process_epoch(spec, state)         # must not even try the device
            assert sup.calls == calls_before
            epoch_engine.set_backend("numpy")
            for _ in range(3):
                process_epoch(spec, twin)
                twin.slot += spec.preset.SLOTS_PER_EPOCH
        finally:
            epoch_engine.set_backend(prev_backend)
        np.testing.assert_array_equal(
            np.asarray(state.balances), np.asarray(twin.balances)
        )


# -- chaos scenario ----------------------------------------------------------------


@pytest.mark.chaos
class TestChaosNetwork:
    def test_liveness_no_false_verify_and_slo_under_chaos(self):
        """The acceptance scenario: 4 nodes, 4 epochs, a device fault
        injected every K=5 verify batches plus one OOM demotion event,
        2% seeded gossip loss, a node crash + restart-from-genesis, and an
        adversarial tampered attestation every epoch. Asserts liveness
        (finalization advances on all nodes, heads agree), zero false
        verifies, the drop-rate SLO, and a visible supervisor
        demote/re-promote cycle. (The denser, longer variant below runs
        nightly; this case is sized for the tier-1 wall clock.)"""
        prev = bls.get_backend()
        bls.set_backend("native")
        sup = resilience.bls_supervisor()
        sup.config = _fast_config(promote_after=1, probe_every=1)
        sup.reset()
        try:
            spec = minimal_spec()
            # 24 validators keeps every property (4 epochs to finalize, 3/4
            # nodes stay > 2/3 while one is crashed) at ~2/3 the native
            # crypto cost — this case runs in tier-1's wall-clock budget
            net = LocalNetwork(spec, n_nodes=4, n_validators=24)
            net.transport.set_gossip_loss(0.02, seed=1234)
            injector.install(
                # K=5: every 5th device verify batch faults transiently
                "stage=bls.batch_verify;mode=raise;kind=transient;every=5|"
                # one mid-run OOM: forces a demotion through the CPU-oracle
                # rung (bounded to ONE oracle batch — it is slow by design)
                "stage=bls.batch_verify;mode=raise;kind=oom;at=30;times=1"
            )
            spe = spec.preset.SLOTS_PER_EPOCH
            tampered_checked = 0
            for slot in range(1, 4 * spe + 1):
                net.run_slot(slot)
                if slot == 10:
                    net.crash_node(3)
                if slot == 18:
                    net.restart_node(3)
                if slot % spe == 4:
                    # adversarial stream: a well-formed attestation carrying
                    # another validator's signature must NEVER verify, chaos
                    # or not
                    node = net.nodes[0]
                    atts = net.harness.unaggregated_attestations_for_slot(
                        node.chain.head.state, slot, node.chain.head.root
                    )
                    if len(atts) >= 2:
                        tampered = atts[0]
                        tampered.signature = atts[1].signature
                        res = node.chain.verify_unaggregated_attestations(
                            [tampered]
                        )
                        assert isinstance(res[0][1], Exception), (
                            f"slot {slot}: tampered attestation verified"
                        )
                        tampered_checked += 1
            assert tampered_checked >= 3

            # liveness: heads agree and finalization advanced on ALL nodes,
            # including the crashed-and-restarted one
            assert net.heads_agree(), f"heads diverged: {net.head_slots()}"
            fins = net.finalized_epochs()
            assert all(f >= 2 for f in fins), f"finalization stalled: {fins}"
            assert (
                net.nodes[3].chain.head.root == net.nodes[0].chain.head.root
            )

            # drop-rate SLO: seeded 2% loss must stay within the 5% budget
            delivered = net.transport.gossip_delivered
            dropped = net.transport.gossip_dropped
            assert delivered > 0
            drop_rate = dropped / (delivered + dropped)
            assert drop_rate <= 0.05, f"drop rate {drop_rate:.3f} over SLO"

            # the supervisor demoted on the OOM and re-promoted, visibly
            snap = sup.snapshot()
            assert snap["faults"] >= 5, snap      # the every-K stream fired
            assert snap["demotions"] >= 1, snap
            assert snap["promotions"] >= 1, snap
            assert snap["state"] == "HEALTHY", snap
            assert snap["exhausted"] == 0, snap   # never total loss
            rendered = REGISTRY.render()
            assert "resilience_demotions_total" in rendered
            assert "resilience_health_state" in rendered
        finally:
            bls.set_backend(prev)

    @pytest.mark.slow
    def test_long_churn_two_crash_cycles(self):
        """Nightly churn variant: 8 epochs, two crash/restart cycles on
        different nodes, denser faults (K=3) and 4% loss."""
        prev = bls.get_backend()
        bls.set_backend("native")
        sup = resilience.bls_supervisor()
        sup.config = _fast_config(promote_after=1, probe_every=1)
        sup.reset()
        try:
            spec = minimal_spec()
            net = LocalNetwork(spec, n_nodes=4, n_validators=32)
            net.transport.set_gossip_loss(0.04, seed=99)
            injector.install(
                "stage=bls.batch_verify;mode=raise;kind=transient;every=3|"
                "stage=bls.batch_verify;mode=raise;kind=oom;at=60;times=1|"
                "stage=bls.batch_verify;mode=raise;kind=oom;at=160;times=1"
            )
            spe = spec.preset.SLOTS_PER_EPOCH
            churn_slots = 8 * spe
            for slot in range(1, churn_slots + 1):
                net.run_slot(slot)
                if slot == 6:
                    net.crash_node(1)
                if slot == 12:
                    net.restart_node(1)
                if slot == 30:
                    net.crash_node(2)
                if slot == 38:
                    net.restart_node(2)
            # chaos epilogue: loss off, faults off, stragglers re-sync, one
            # clean epoch to converge — the liveness claim is that the
            # network RECOVERS, not that 4% loss never forks a tip
            net.transport.set_gossip_loss(0.0)
            injector.clear()
            net.reconnect_all()
            net.run_until(churn_slots + spe, start=churn_slots + 1)
            assert net.heads_agree(), f"heads diverged: {net.head_slots()}"
            assert all(f >= 5 for f in net.finalized_epochs())
            snap = sup.snapshot()
            assert snap["demotions"] >= 2 and snap["exhausted"] == 0
        finally:
            bls.set_backend(prev)
