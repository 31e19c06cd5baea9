"""Host time of ``sweep(request)`` up to its asynchronous return
(validation, planning, jit lookup, enqueue), mean over requests [ms]."""


def read(run):
    if not run.requests:
        return None
    return 1e3 * sum(r.dispatched - r.submit for r in run.requests) / len(run.requests)
