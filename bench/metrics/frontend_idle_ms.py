"""Device-idle time in the traced window during which the innermost host
span is one of the program's ``sweep.*`` frontend steps (validation,
planning, dispatch, shaping), per completed request [ms]; None where the
trace holds no device plane or no ``sweep.*`` span (``bench/stages.py``)."""
from bench import stages


def read(run):
    summary = stages.from_run(run)
    if (summary is None or not summary.n_devices or not summary.frontend
            or not run.requests):
        return None
    return summary.frontend_idle_s * 1e3 / len(run.requests)
