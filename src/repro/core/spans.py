"""Named spans over a placement's phases, and one record per engine call.

A :class:`span` times one phase of the placement path.  It always adds
its ``perf_counter`` duration and a count of one under its name in the
innermost open :class:`record`, and, once ``jax`` has been imported by
anyone, it is also a ``jax.profiler.TraceAnnotation`` named
``"repro." + name`` (with its keyword arguments as the event's
arguments), so under a profiler trace the spans share the device
trace's clock.  The numpy path never imports jax for it.

A record opens at each public engine call (``place``, ``replace``,
``place_many``).  The open records are a :mod:`contextvars` stack, so
nested calls and threads keep records of their own.  A record's own
span (its name) is a span of the record around it, if any.  A span
opened outside any record goes to the trace only.  Closed records are
kept in a bounded deque, :func:`recent`: the last 4096, newest last.

No switch: with no profiler collecting, a span skips the annotation and
costs a few microseconds.
"""
from __future__ import annotations

import collections
import contextvars
import dataclasses
import sys
import time
from typing import Optional

# bounded, yet enough for every placement of a one-minute closed loop at
# tens of placements a second (an 8x8x8 torus places ~7 a second)
RECENT_MAX = 4096

_OPEN: contextvars.ContextVar[tuple] = contextvars.ContextVar(
    "repro_span_records", default=())
_RECENT: collections.deque = collections.deque(maxlen=RECENT_MAX)
_annotation = None


@dataclasses.dataclass(frozen=True)
class Record:
    """One closed record: its name, its total seconds, and each span
    opened directly in it, ``name -> (count, seconds)``."""

    name: str
    total_s: float
    spans: dict


def recent() -> list[Record]:
    """The last :data:`RECENT_MAX` closed records, oldest first."""
    return list(_RECENT)


def _trace_annotation():
    """``jax.profiler.TraceAnnotation`` once jax is imported, else None."""
    global _annotation
    if _annotation is None:
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        if profiler is not None:
            _annotation = profiler.TraceAnnotation
    return _annotation


class span:
    """``with span(name, **args):`` times the block into the innermost
    open record and annotates it in the profiler trace; ``seconds``
    holds the block's duration once it has closed."""

    __slots__ = ("name", "args", "seconds", "_ann", "_into", "_t0")

    def __init__(self, name: str, **args):
        self.name = name
        self.args = args

    def __enter__(self) -> "span":
        annotation = _annotation or _trace_annotation()
        if annotation is not None and annotation.is_enabled():
            self._ann = annotation("repro." + self.name, **self.args)
            self._ann.__enter__()
        else:
            self._ann = None
        stack = _OPEN.get()
        self._into = stack[-1] if stack else None
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0
        if self._into is not None:
            self._into.add(self.name, self.seconds)
        if self._ann is not None:
            self._ann.__exit__(*exc)


class record:
    """``with record(name) as rec:`` opens a record for one engine call.
    After the block, ``rec.closed`` is the :class:`Record`, which
    :func:`recent` keeps too."""

    def __init__(self, name: str):
        self.name = name
        self.closed: Optional[Record] = None
        self._spans: dict[str, tuple[int, float]] = {}
        self._span = span(name)

    def add(self, name: str, seconds: float) -> None:
        count, total = self._spans.get(name, (0, 0.0))
        self._spans[name] = (count + 1, total + seconds)

    def __enter__(self) -> "record":
        self._span.__enter__()
        self._token = _OPEN.set(_OPEN.get() + (self,))
        return self

    def __exit__(self, *exc) -> None:
        _OPEN.reset(self._token)
        self._span.__exit__(*exc)
        self.closed = Record(self.name, self._span.seconds, self._spans)
        _RECENT.append(self.closed)
