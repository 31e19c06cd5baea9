"""Device busy time in the traced window per 1,000 trial evaluations of the
requests it completed [ms]: the whole engine's device cost of a trial."""


def read(run):
    trials = sum(r.trials for r in run.requests)
    if run.trace is None or not trials or run.trace.busy_s <= 0:
        return None
    return run.trace.busy_s * 1e3 / (trials / 1e3)
