"""75th percentile over all placements of the window, from the call to
the returned plan: the highest percentile with about ten of the
window's ~40 placements beyond it."""
import numpy as np


def read(rec):
    if not rec.get("place_ms"):
        return None
    return float(np.percentile(rec["place_ms"], 75))
