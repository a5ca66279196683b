"""The same tail as ``answer_p90_s``, in a cell whose window holds too few
answers for ten to lie beyond the 90th percentile: read, not bounded."""
import numpy as np


def read(r):
    waits = [a.wait_s for a in r.answers]
    return float(np.percentile(waits, 90)) if waits else None
