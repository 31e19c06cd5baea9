"""Trial evaluations (trials x grid points) of every request the window
completed, over the whole window: first submit to last completion."""


def read(run):
    if run.window_s <= 0:
        return None
    return sum(r.trials for r in run.requests) / run.window_s
