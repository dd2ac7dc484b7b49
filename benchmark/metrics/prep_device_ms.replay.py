"""Median device time of one execution of the prep stage, in ms: the
duration of the ``jit_bls_prep`` events on the device's module line wholly
inside the traced window (``benchmark/programtrace.py``)."""

from benchmark import programtrace


def read(run):
    trace = programtrace.of_run(run)
    return None if trace is None else programtrace.stage_device_ms(
        trace, "jit_bls_prep")
