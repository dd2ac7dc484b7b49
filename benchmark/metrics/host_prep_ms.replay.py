"""Median host time of one batch's preparation for the device, in ms: the
duration of the program's ``bls.host_prep`` spans wholly inside the traced
window (index and signature packing, signature parsing, hash to field,
scalars; ``benchmark/programtrace.py``)."""

from benchmark import programtrace


def read(run):
    trace = programtrace.of_run(run)
    return None if trace is None else programtrace.host_prep_ms(trace)
