"""Mean number of device refine dispatches per placement."""
import numpy as np


def read(rec):
    c = rec.get("refine_calls")
    return float(np.mean(c)) if c else None
