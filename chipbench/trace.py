"""From a profiler trace to device busy time, kernel time and idle gaps.

The run records its own host spans (``chipbench.*``) with
``jax.profiler.TraceAnnotation``, so they share the trace's clock with
the device's operations.  The reduction is plain arithmetic on interval
lists and is checked on synthetic events in ``chipbench/tests``.
"""
from __future__ import annotations

import dataclasses
from pathlib import Path

SPAN_PREFIX = "chipbench."


@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted, non-overlapping (start, end) intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy, lo: float, hi: float) -> list[tuple[float, float]]:
    """The complement of ``busy`` (merged) within [lo, hi]."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def label(points: list[float], spans: list[Event]) -> list[str]:
    """For each time in ``points`` (sorted), the name of the shortest
    host span covering it, without the prefix, or "none"."""
    spans = sorted(spans, key=lambda ev: ev.start_ns)
    active: list[Event] = []
    out, k = [], 0
    for t in points:
        while k < len(spans) and spans[k].start_ns <= t:
            active.append(spans[k])
            k += 1
        active = [ev for ev in active if ev.end_ns > t]
        best = min(active, key=lambda ev: ev.dur_ns, default=None)
        out.append(best.name[len(SPAN_PREFIX):] if best is not None
                   else "none")
    return out


def reduce(modules: dict[int, list[Event]], host_spans: list[Event],
           window: tuple[float, float], top: int = 10) -> dict:
    """Busy and idle time of each device within ``window`` (ns), from
    the device's program executions (``modules``, which do not nest),
    the programs that took most time, and the idle time grouped by what
    the host was doing when it began.

    ``busy_s`` is averaged over the devices, ``window_s`` is the
    window's length."""
    lo, hi = window
    busy_total, idle_by = 0.0, {}
    per_name: dict[str, float] = {}
    for evs in modules.values():
        inside = [ev for ev in evs if ev.end_ns > lo and ev.start_ns < hi]
        busy = union(clip([(ev.start_ns, ev.end_ns) for ev in inside],
                          lo, hi))
        busy_total += sum(e - s for s, e in busy)
        for (s, e), ev in zip(clip([(ev.start_ns, ev.end_ns)
                                    for ev in inside], lo, hi), inside):
            per_name[ev.name] = per_name.get(ev.name, 0.0) + (e - s)
        idle = gaps(busy, lo, hi)
        for (s, e), what in zip(idle, label([s for s, _ in idle],
                                            host_spans)):
            idle_by[what] = idle_by.get(what, 0.0) + (e - s)
    n_dev = max(len(modules), 1)
    ops_sorted = sorted(per_name.items(), key=lambda kv: -kv[1])[:top]
    idle_sorted = sorted(idle_by.items(), key=lambda kv: -kv[1])[:top]
    return {
        "busy_s": busy_total / n_dev * 1e-9,
        "window_s": (hi - lo) * 1e-9,
        "device_ops": [[k, v / n_dev * 1e-9] for k, v in ops_sorted],
        "idle_gaps": [[k, v / n_dev * 1e-9] for k, v in idle_sorted],
    }


def _profile_file(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(trace_dir: Path, device_ids):
    """From the newest profile under ``trace_dir``: the program
    executions of the devices ``device_ids`` ("XLA Modules" lines), the
    seconds spent in each operation name ("XLA Ops" lines, summed over
    the devices), and the run's own host spans."""
    import jax
    prof = jax.profiler.ProfileData.from_file(str(_profile_file(trace_dir)))
    wanted = {f"/device:TPU:{i}" for i in device_ids}
    modules: dict[int, list[Event]] = {}
    op_time: dict[str, float] = {}
    spans: list[Event] = []
    for plane in prof.planes:
        if plane.name in wanted:
            evs = modules.setdefault(int(plane.name.rsplit(":", 1)[1]), [])
            for line in plane.lines:
                if line.name == "XLA Modules":
                    evs.extend(Event(ev.name, ev.start_ns, ev.duration_ns)
                               for ev in line.events)
                elif line.name == "XLA Ops":
                    for ev in line.events:
                        op_time[ev.name] = (op_time.get(ev.name, 0.0)
                                            + ev.duration_ns * 1e-9)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(Event(ev.name, ev.start_ns, ev.duration_ns)
                             for ev in line.events
                             if ev.name.startswith(SPAN_PREFIX))
    return modules, op_time, spans


def window_of(spans: list[Event]) -> tuple[float, float]:
    """The measured window, as the run's ``chipbench.window`` span."""
    for ev in spans:
        if ev.name == SPAN_PREFIX + "window":
            return ev.start_ns, ev.end_ns
    raise ValueError("the trace holds no chipbench.window span")
