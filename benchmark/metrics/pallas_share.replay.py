"""Share of the device's busy time spent inside Pallas (Mosaic) kernels,
in percent; the rest is XLA glue: u64 <-> f32 digit-plane conversions,
lincombs, gathers, loops. The rule that tells the two apart is
``devtrace.is_mosaic``."""


def read(run):
    if run.trace is None or run.trace.mosaic_s == 0:
        return None
    return 100.0 * run.trace.mosaic_s / run.trace.busy_s
