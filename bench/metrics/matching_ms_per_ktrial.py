"""Device time of the engine's ``matching`` stage (bipartite matching,
``core/matching.py`` and its kernels) in the traced window per 1,000 trial
evaluations of the requests it completed [ms]; read from the ops'
``arb.matching`` op names (``bench/stages.py``)."""
from bench import stages


def read(run):
    return stages.per_ktrial_ms(run, "matching")
