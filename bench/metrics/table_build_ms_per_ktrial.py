"""Device time of the engine's ``table_build`` stage (search tables) in the
traced window per 1,000 trial evaluations of the requests it completed [ms];
read from the ops' ``arb.table_build`` op names (``bench/stages.py``)."""
from bench import stages


def read(run):
    return stages.per_ktrial_ms(run, "table_build")
