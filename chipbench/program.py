"""What the harness touches of the system under test, in one place.

The drivers reach the program only through this module, the fabric
files' ``topology`` constructors and the public entry point they drive
(``PlacementEngine.place``).  One coupling goes deeper and is listed in
``PERF.md``: the wrapper around ``mapping_jax.refine_many`` below.
"""
from __future__ import annotations

import contextlib
import time

import numpy as np


def engine():
    """A placement engine on the jax backend (the platform picks its
    dtype; the devices follow ``REPRO_JAX_DEVICES``, which the harness
    sets from the cell's chip count)."""
    from repro.core.engine import PlacementEngine
    return PlacementEngine(backend="jax")


def comm_graph(graph):
    """Hand a generated graph to the program as its ``CommGraph``."""
    from repro.core.comm_graph import CommGraph
    return CommGraph(graph.n, graph.G_v.copy(), graph.G_m.copy())


def workload(graph, name: str):
    from repro.workloads.patterns import Workload
    return Workload(name, comm_graph(graph), 1e7, 1, "benchmark")


def state_with_outage(n_nodes: int, p_f: np.ndarray):
    from repro.core.state import ClusterState
    return ClusterState.healthy(n_nodes).with_outage(p_f)


def request(comm, topo, state, seed: int = 0):
    from repro.core.engine import PlacementRequest
    return PlacementRequest(comm=comm, topology=topo, state=state,
                            seed=seed)


def warm(comm, D, n_nodes: int, plan: dict, rng) -> None:
    """Compile, for one guest, the device programs whose shapes depend
    on the health state rather than on the guest: the refine for every
    candidate-stack height in ``plan["refine"]``, the scoring for every
    height in ``plan["score"]``, and the node-subset growth for every
    subset size in ``plan["select"]`` (a [from, to) range).  A run would
    otherwise meet some of them for the first time inside its window."""
    from repro.core import backend, mapping, mapping_jax
    G = comm.weights("volume")
    with backend.use("jax"):
        for b in plan.get("refine", []):
            mapping_jax.refine_many(G, D, _stack(rng, n_nodes, comm.n, b))
        for b in plan.get("score", []):
            mapping.hop_bytes_batch(G, D, _stack(rng, n_nodes, comm.n, b))
        if "select" in plan:
            for m in range(*plan["select"]):
                mapping.select_nodes(np.ones((m, m)), comm.n, seed=0)


def _stack(rng, n_nodes: int, n: int, b: int) -> np.ndarray:
    return np.stack([rng.permutation(n_nodes)[:n] for _ in range(b)])


class Spans:
    """Host spans: while a trace is on, each is a ``TraceAnnotation``, so
    it lands on the trace's clock.

    The drivers start and stop the trace between steps.  Starting the
    profiler and writing the trace out take long: that time is kept in
    ``stalled``, and the drivers leave it out of what they measure."""

    def __init__(self):
        self.stalled = 0.0
        self._window = None

    @property
    def tracing(self) -> bool:
        return self._window is not None

    @contextlib.contextmanager
    def __call__(self, name: str):
        if self._window is None:
            yield
            return
        import jax
        with jax.profiler.TraceAnnotation("chipbench." + name):
            yield

    def start_trace(self, trace_dir: str) -> None:
        import jax
        t0 = time.perf_counter()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0          # host spans only, no Python
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        self._window = jax.profiler.TraceAnnotation("chipbench.window")
        self._window.__enter__()
        self.stalled += time.perf_counter() - t0

    def stop_trace(self) -> None:
        if self._window is None:
            return
        import jax
        t0 = time.perf_counter()
        self._window.__exit__(None, None, None)
        self._window = None
        jax.profiler.stop_trace()
        self.stalled += time.perf_counter() - t0


class RefineRecorder:
    """Wraps ``mapping_jax.refine_many`` (as ``chip_smoke.py`` does):
    times and counts every device refine, notes the stack shapes of the
    traced ones, and keeps every refined row by the loop's ``tag`` (the
    job it served) and a seeded sample of whole dispatches, for the
    check.  ``refine_many`` returns a host array, so its wall time
    includes the device's work."""

    def __init__(self, spans: Spans, rng: np.random.Generator,
                 keep_width: int, reservoir: int = 32):
        from repro.core import mapping_jax
        self._mj = mapping_jax
        self._orig = mapping_jax.refine_many
        self.spans = spans
        self.rng = rng
        self.keep_width = keep_width
        self.reservoir = reservoir
        self.recording = False
        self.tag = None
        self.calls = 0
        self.seconds = 0.0
        self.traced_shapes: list[tuple[int, int]] = []
        self.kept: list[dict] = []
        self.produced: dict = {}
        self._seen_small = 0
        self._small: list[dict] = []

    def __enter__(self):
        self._mj.refine_many = self._call
        return self

    def __exit__(self, *exc):
        self._mj.refine_many = self._orig

    def _call(self, G_w, D, placements, *args, **kw):
        P = np.array(placements, copy=True)
        t0 = time.perf_counter()
        with self.spans("refine"):
            out = self._orig(G_w, D, placements, *args, **kw)
        dt = time.perf_counter() - t0
        if self.recording:
            self.calls += 1
            self.seconds += dt
            P2 = np.atleast_2d(P)
            out2 = np.atleast_2d(np.asarray(out))
            self.produced.setdefault(self.tag, []).append(out2)
            if self.spans.tracing:
                self.traced_shapes.append(P2.shape)
            self._keep({"G": G_w, "inp": P2, "out": out2, "tag": self.tag})
        return out

    def _keep(self, rec: dict) -> None:
        """Every dispatch as wide as a whole job, and a reservoir sample
        of the narrower sub-problem dispatches."""
        if rec["inp"].shape[1] >= self.keep_width:
            self.kept.append(rec)
            return
        self._seen_small += 1
        if len(self._small) < self.reservoir:
            self._small.append(rec)
        else:
            j = int(self.rng.integers(self._seen_small))
            if j < self.reservoir:
                self._small[j] = rec

    def sample(self) -> list[dict]:
        return self.kept + self._small


class CompileCounter:
    """Counts, by name, the programs JAX lowers (a new shape, or a new
    function) while ``active``: inside the window there should be none."""

    def __init__(self):
        import jax
        self.active = False
        self.names: dict[str, int] = {}

        def listener(name, _dur, **kw):
            if self.active and name.endswith("jaxpr_to_mlir_module_duration"):
                fun = str(kw.get("fun_name", "?"))
                self.names[fun] = self.names.get(fun, 0) + 1
        jax.monitoring.register_event_duration_secs_listener(listener)
