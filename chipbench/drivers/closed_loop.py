"""Closed loop of ``PlacementEngine.place``, one job at a time.

Each step is the call a resource manager's plugin makes at job launch:
one job of the mix's kinds in turn, each with a fault draw of its own
(the paper's protocol), so every placement sees a health state no
other placement of the run saw.  Draw ``j`` of kind ``k`` is made from
``[draw_seed, k, j]`` alone; a run takes its kind's draws in blocks of
``shuffle_block``, each block in an order drawn from ``--seed``.  So
every seed offers the same draws in another order, and two seeds that
complete the same number of blocks have placed the same jobs under the
same health states.  A draw never comes back within a run.

The window ends when the last placement started within ``seconds``
returns, so no placement is cut.  A traced run traces
``trace_placements`` whole placements, started at the boundary from
which they are expected to be the window's last; the time the profiler
takes to start and to write the trace is left out of the window's
clock.
"""
from __future__ import annotations

import time

import numpy as np

from chipbench import check as ck
from chipbench import generator, program, reference


class Driver:
    def __init__(self, fabric, spec: dict, mix: dict, seed: int, spans):
        self.fabric = fabric
        self.spec = spec
        self.mix = mix
        self.seed = seed
        self.spans = spans
        self.kinds = mix["jobs"]

    # ----------------------------------------------------------- set-up
    def setup(self) -> None:
        self.topo = self.fabric.topology(self.spec)
        self.n_nodes = self.topo.n_nodes
        self.engine = program.engine()
        self.graphs = [generator.GUESTS[k["guest"]](k["ranks"])
                       for k in self.kinds]
        self.comms = [program.comm_graph(g) for g in self.graphs]
        self._warm()

    def _warm(self) -> None:
        """One placement of each job kind on a health state with
        consecutive healthy windows and one on a state without, then
        every candidate-stack height the policy can form."""
        rng = np.random.default_rng(0)
        for kind, comm in zip(self.kinds, self.comms):
            for p_f in self._warm_states(kind):
                state = program.state_with_outage(self.n_nodes, p_f)
                self.engine.place(program.request(comm, self.topo, state),
                                  policy=self.mix["policy"], rng=rng)
            W = self.engine.weights(self.topo, p_f)
            program.warm(comm, W, self.n_nodes, self.mix["warm"], rng)

    def _warm_states(self, kind: dict) -> list[np.ndarray]:
        """Faults packed at the lowest ids (windows of healthy nodes
        follow), and faults spread evenly (no window of ``ranks`` healthy
        ids, when the spacing allows)."""
        n, f = self.n_nodes, kind["faulty"]
        packed = np.zeros(n)
        packed[:f] = kind["p_f"]
        spread = np.zeros(n)
        spread[np.linspace(0, n, f, endpoint=False).astype(int)] = kind["p_f"]
        return [packed, spread]

    # ----------------------------------------------------------- window
    def draw(self, k: int, turn: int) -> tuple[int, np.ndarray]:
        """(draw index, outage vector) of kind ``k``'s ``turn``-th job."""
        block = self.mix["shuffle_block"]
        b, r = divmod(turn, block)
        j = b * block + int(np.random.default_rng(
            [self.seed, k, b]).permutation(block)[r])
        kind = self.kinds[k]
        rng = np.random.default_rng([self.mix["draw_seed"], k, j])
        return j, generator.fault_draw(rng, self.n_nodes, kind["faulty"],
                                       kind["p_f"])

    def window(self, seconds: float, trace_dir: str | None = None) -> dict:
        rec = program.RefineRecorder(
            self.spans, np.random.default_rng([self.seed, 1]),
            keep_width=min(k["ranks"] for k in self.kinds))
        to_trace = self.mix["trace_placements"] if trace_dir else 0
        traced = 0
        seen: set[tuple[int, bytes]] = set()
        hits0 = dict(self.engine.stats)
        jobs = []
        with rec:
            rec.recording = True
            t_start = time.perf_counter()

            def clock() -> float:
                return time.perf_counter() - t_start - self.spans.stalled

            i = 0
            while clock() < seconds or traced < to_trace:
                k = i % len(self.kinds)
                j, p_f = self.draw(k, i // len(self.kinds))
                key = (k, np.flatnonzero(p_f).tobytes())
                if key in seen:
                    raise RuntimeError(f"health state {j} of kind {k} "
                                       f"came back within the window")
                seen.add(key)
                if (to_trace and not self.spans.tracing and traced == 0
                        and clock() + to_trace * _mean_s(jobs) >= seconds):
                    self.spans.start_trace(trace_dir)
                state = program.state_with_outage(self.n_nodes, p_f)
                req = program.request(self.comms[k], self.topo, state)
                rec.tag = i
                calls0, refine0 = rec.calls, rec.seconds
                t0 = clock()
                with self.spans("place"):
                    plan = self.engine.place(
                        req, policy=self.mix["policy"],
                        rng=np.random.default_rng([self.seed, 2, i]))
                t1 = clock()
                jobs.append({
                    "kind": k, "draw": j, "penal": p_f > 0,
                    "placement": np.array(plan.placement),
                    "claimed": plan.hop_bytes_fault_weighted,
                    "t0": t0, "t1": t1,
                    "refine_s": rec.seconds - refine0,
                    "refine_calls": rec.calls - calls0})
                if self.spans.tracing:
                    traced += 1
                    if traced == to_trace:
                        self.spans.stop_trace()
                i += 1
            window_s = clock()
            rec.recording = False
        hits = {key: self.engine.stats[key] - hits0[key] for key in hits0}
        self._sample = rec.sample()
        self._produced = rec.produced
        self._jobs = jobs
        return {
            "window_s": window_s,
            "place_ms": [1e3 * (j["t1"] - j["t0"]) for j in jobs],
            "host_ms": [1e3 * (j["t1"] - j["t0"] - j["refine_s"])
                        for j in jobs],
            "refine_ms": [1e3 * j["refine_s"] for j in jobs],
            "refine_calls": [j["refine_calls"] for j in jobs],
            "traced_refine_shapes": rec.traced_shapes,
            "notes": {"health_states": len(seen),
                      "traced_placements": traced},
            "cache": hits,
            "placements": len(jobs),
            "attempted": len(jobs),
            "failed": 0,
        }

    # ------------------------------------------------------------ check
    def answers(self) -> dict:
        """Host copies of what the window produced, for the check."""
        return {"jobs": self._jobs, "sample": self._sample,
                "produced": self._produced, "graphs": self.graphs,
                "n_nodes": self.n_nodes}

    def close(self) -> None:
        self.engine = None


def _mean_s(jobs: list[dict]) -> float:
    """Mean time of the placements so far (0 before the first)."""
    if not jobs:
        return 0.0
    return float(np.mean([j["t1"] - j["t0"] for j in jobs]))


def check(fabric, ans: dict, precision: str = "float32") -> dict:
    """The numbers of a closed-loop run (``chipbench/check.py``), and its
    quality sums: the reference hop-bytes of the window's placements and
    of Slurm's block placement of the same jobs."""
    allocatable = np.ones(ans["n_nodes"], dtype=bool)
    invalid, claim_err = 0, 0.0
    why = []
    ours = block = 0.0
    for i, job in enumerate(ans["jobs"]):
        G = ans["graphs"][job["kind"]].G_v
        p, penal = job["placement"], job["penal"]
        reason = reference.invalid_reason(p, G.shape[0], allocatable)
        if reason is not None:
            invalid += 1
            why.append(reason)
            continue
        if not ck.produced(ans, i, p):
            invalid += 1
            why.append("a placement no refine produced")
            continue
        M = reference.sym_weights(fabric, p, penal)
        ref = reference.hop_bytes(G, M)
        claimed = (job["claimed"] if precision == "float32"
                   else ck.bf16_hop_bytes(G, M))
        if claimed is None:
            invalid += 1
            why.append("a plan states no fault-weighted hop-bytes")
            continue
        claim_err = max(claim_err, abs(claimed - ref) / ref)
        ours += ref
        Mb = reference.sym_weights(
            fabric, reference.block_placement(G.shape[0], allocatable),
            penal)
        block += reference.hop_bytes(G, Mb)
    gap = 0.0
    for rec in ans["sample"]:
        g, bad = ck.sample_gap(fabric, rec,
                               ans["jobs"][rec["tag"]]["penal"])
        gap = max(gap, g)
        invalid += bad
        if bad:
            why.append("a refine returned other nodes than it was given")
    return {"numbers": {"invalid": invalid, "claim_err": claim_err,
                        "swap_gap": gap},
            "why": why[:5],
            "quality": {"hop_bytes": ours, "block_hop_bytes": block}}
