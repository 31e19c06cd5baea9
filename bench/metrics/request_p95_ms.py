"""95th percentile over all requests of the window of submit -> result
grids on the host."""
import numpy as np


def read(run):
    lat = [r.done - r.submit for r in run.requests]
    return float(np.percentile(lat, 95)) * 1e3 if lat else None
