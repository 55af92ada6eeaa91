"""The statistics every metric uses, in one place."""
import numpy as np


def percentile(values, q):
    """The q-th percentile over ALL of ``values`` (linear interpolation);
    None for an empty list, so that a reader with nothing to read returns
    nothing."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def median(values):
    return percentile(values, 50)
