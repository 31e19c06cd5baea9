"""Process start to the first timed submit: runtime start, the unit draw,
and the cell's program loaded from the compile cache (or compiled) and run
once."""


def read(run):
    return run.setup_s
