"""Mean host time of a placement: its wall time less the time inside
``refine_many`` (which returns a host array, so that includes the device)."""
import numpy as np


def read(rec):
    return float(np.mean(rec["host_ms"])) if rec.get("host_ms") else None
