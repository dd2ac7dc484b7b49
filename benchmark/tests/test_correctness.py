"""``correct`` at a size a CPU can hold: sound runs of the program read
correct; the control, and each fault a cell can have, read not correct.

Each test drives ``harness.run_cell`` past its look for a chip, with the
cell's configuration cut to a 4,096-key registry (64-key committees, 8
sets a batch) and one bad set of each kind. The comparison, the traffic
generator and the window are the benchmark's own, as on the chip.
"""

import secrets
import time
import types

import pytest

from benchmark import faults, harness

WORKLOAD = "replay-agg512"
SEED = 4_000_000_001
SECONDS = 3.0


def small_files():
    bench, cell, config, mix = harness.cell_files(WORKLOAD)
    config = dict(config, validators=4096, committees_per_slot=2,
                  sets_per_batch=8)
    mix = dict(mix, non_subgroup=1, infinity=1)
    return bench, cell, config, mix


def run(wrap=None) -> dict:
    return harness.run_cell(WORKLOAD, SEED, SECONDS, False, time.monotonic(),
                            require_chip=False, wrap=wrap, files=small_files())


@pytest.fixture
def scalars_of_13(monkeypatch):
    """Random scalars that are all multiples of 13, the order of the
    non-subgroup signature's torsion: the draw an adversary waits for,
    which a batch verifier meets once in 13 tries with fair scalars."""
    from lighthouse_tpu.bls import tpu_backend as tb

    monkeypatch.setattr(tb, "secrets", types.SimpleNamespace(
        randbits=lambda bits: 13 * secrets.randbits(bits - 4)))


def test_program_reads_correct():
    res = run()
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0
    assert list(res)[-1] == "checks"


def test_program_reads_correct_on_scalars_of_13(scalars_of_13):
    res = run()
    assert res["correct"] is True, res["checks"]


@pytest.mark.parametrize("wrap", sorted(faults.WRAPS))
def test_control_and_faults_read_not_correct(wrap, scalars_of_13):
    res = run(faults.WRAPS[wrap])
    assert res["correct"] is False, res["checks"]


def test_no_chip_no_result():
    """A run that finds no TPU exits non-zero before any result."""
    with pytest.raises(SystemExit):
        harness.run_cell(WORKLOAD, 1, 1.0, False, time.monotonic())
