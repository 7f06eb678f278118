"""Placements completed over the whole window, per second."""


def read(rec):
    return rec["placements"] / rec["window_s"]
