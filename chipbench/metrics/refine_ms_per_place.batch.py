"""Mean wall time inside ``refine_many`` per placement."""
import numpy as np


def read(rec):
    return float(np.mean(rec["refine_ms"])) if rec.get("refine_ms") else None
