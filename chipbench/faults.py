"""Faults planted under the timed path, to show that the check sees them.

Each is a context manager that patches one function of the program for
as long as it is open; ``chipbench/tests`` and ``chipbench/control.py``
run a cell inside one and expect ``correct`` to come out false.

``refine_unchanged``   the device refine returns its stack as given (a
                       step that returns its state unchanged);
``half_refined``       only the first half of each candidate stack is
                       refined, the rest comes back as given (half of the
                       batch left out);
``answer_altered``     the engine's plan places rank 0 on another free
                       node (an answer altered where it is produced).

A closed loop on one chip has no exchange between chips to leave out.
"""
from __future__ import annotations

import contextlib

import numpy as np


@contextlib.contextmanager
def _patched(obj, name: str, make):
    orig = getattr(obj, name)
    setattr(obj, name, make(orig))
    try:
        yield
    finally:
        setattr(obj, name, orig)


def refine_unchanged():
    from repro.core import mapping_jax

    def make(orig):
        def refine_many(G_w, D, placements, *a, **kw):
            return np.array(placements, copy=True)
        return refine_many
    return _patched(mapping_jax, "refine_many", make)


def half_refined():
    from repro.core import mapping_jax

    def make(orig):
        def refine_many(G_w, D, placements, *a, **kw):
            P = np.array(placements, copy=True)
            if P.ndim == 1:
                return P
            k = len(P) // 2
            if k:
                P[:k] = orig(G_w, D, P[:k], *a, **kw)
            return P
        return refine_many
    return _patched(mapping_jax, "refine_many", make)


def answer_altered():
    from repro.core.engine import PlacementEngine

    def make(orig):
        def _plan(self, request, policy, placement, *a, **kw):
            p = np.array(placement, copy=True)
            free = np.setdiff1d(request.available_ids, p)
            if free.size:
                p[0] = free[0]
            return orig(self, request, policy, p, *a, **kw)
        return _plan
    return _patched(PlacementEngine, "_plan", make)


CLOSED_LOOP = {"refine_unchanged": refine_unchanged,
               "half_refined": half_refined,
               "answer_altered": answer_altered}
