"""Signature sets verified in the closed loop's window over the window's
length (from its opening to the end of its last call), in sets/s."""


def read(run):
    if run.replay is None:
        return None
    r = run.replay
    sets = sum(len(run.work.batches[b]) for b, _, _, _ in r.calls)
    return sets / (r.t_close - r.t_open)
