"""The program's own per-placement records (``repro.core.spans``), read
for the window's placements.

Each public engine call closes one record, kept in the program's
``recent()`` (the last 256, newest last).  A closed loop calls only
``place``, and nothing places after the window, so the window's
placements are the last ``rec["placements"]`` records.  A program
without the records, or with fewer of them, gives ``None``.
"""
from __future__ import annotations


def window_records(rec: dict):
    """The window's placement records, or ``None``."""
    try:
        from repro.core import spans
    except ImportError:
        return None
    n = rec.get("placements") or 0
    records = spans.recent()
    if n <= 0 or len(records) < n:
        return None
    window = records[-n:]
    if any(r.name != "place" for r in window):
        return None
    return window


def ms_per_place(rec: dict, name: str):
    """Mean milliseconds per placement spent in the span ``name``."""
    window = window_records(rec)
    if window is None:
        return None
    total = sum(r.spans.get(name, (0, 0.0))[1] for r in window)
    return 1e3 * total / len(window)
