"""Share of the device's idle time in the traced window that falls inside
the program's ``bls.host_prep`` spans, in percent: the idle time the
device spends waiting for the host to prepare a batch (interval
intersection; ``benchmark/programtrace.py``)."""

from benchmark import programtrace


def read(run):
    trace = programtrace.of_run(run)
    return None if trace is None else programtrace.idle_in(trace,
                                                           "bls.host_prep")
