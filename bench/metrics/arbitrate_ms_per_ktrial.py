"""Device time of the engine's ``arbitrate`` stage (a scheme's arbiter:
relation search and SSM) in the traced window per 1,000 trial evaluations
of the requests it completed [ms]; read from the ops' ``arb.arbitrate`` op
names (``bench/stages.py``)."""
from bench import stages


def read(run):
    return stages.per_ktrial_ms(run, "arbitrate")
