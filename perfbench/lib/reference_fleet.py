"""Plain reference for a shared pool: the serial DES of
`perfbench.lib.reference` with tenant-tagged arrivals.

Each arrival carries its app (tenant). The app's token bucket, if the
cell has one, admits or sheds it; an admitted arrival is dispatched by
the unchanged `reference.Des` logic with the app's own request size and
deadline; the allocator ticks on the pooled demand. It imports nothing
of the program: the buckets' knobs are computed here from each app's
weight and the configuration's admission entry.

Token bucket (per app, rate ``rate x weight`` tokens per second, depth
``max(burst x weight, 1)``, full at time 0): at each of the app's
arrivals the bucket refills for the time since its last arrival, up to
its depth; the arrival is admitted if a whole token is there, and takes
it. A shed request is counted as a request and as a missed deadline:
the user saw it fail.

Arithmetic runs in float64; ``precision="bfloat16"`` rounds every stored
time, load, energy and token level to bfloat16 instead (the control).
"""

from __future__ import annotations

import heapq

import numpy as np

from perfbench.lib.reference import Des, Fleet, Totals

__all__ = ["Fleet", "FleetDes", "Totals"]


class FleetDes(Des):
    """One shared fleet, one dispatch policy, one admission policy, the
    energy objective, a tenant-tagged stream of arrivals."""

    def __init__(self, fleet: Fleet, sizes, deadlines, weights,
                 admission: dict, dispatcher: str, n_max: int,
                 precision: str = "float64"):
        sizes = [float(s) for s in sizes]
        deadlines = [float(d) for d in deadlines]
        super().__init__(fleet, sizes[0], deadlines[0], dispatcher, n_max,
                         precision)
        self.sizes, self.deadlines = sizes, deadlines
        w = np.asarray(weights, np.float64)
        self.bucket = admission["name"] == "token_bucket"
        if self.bucket:
            self.rate = (admission["rate"] * w).tolist()
            self.depth = np.maximum(admission["burst"] * w, 1.0).tolist()
            self.tokens = list(self.depth)
            self.last = [0.0] * len(w)
        elif admission["name"] != "admit_all":
            raise ValueError(f"unknown admission {admission['name']!r}")
        self.shed = 0

    def _admit(self, tid: int) -> bool:
        if not self.bucket:
            return True
        tok = self.q(min(self.depth[tid], self.tokens[tid]
                         + (self.now - self.last[tid]) * self.rate[tid]))
        self.last[tid] = self.now
        if tok < 1.0:
            self.tokens[tid] = tok
            return False
        self.tokens[tid] = self.q(tok - 1.0)
        return True

    def _tagged_arrival(self, tid: int) -> None:
        if not self._admit(tid):
            self.shed += 1
            return
        self.size, self.deadline = self.sizes[tid], self.deadlines[tid]
        self._arrival()

    def run_tagged(self, times: np.ndarray, tids: np.ndarray,
                   horizon_s: float) -> Totals:
        """`Des.run`'s merge of the arrival stream with the event heap,
        arrivals first at equal times, each arrival with its app."""
        T = self.fleet.T_s
        for k in range(int(np.ceil(horizon_s / T))):
            self._push(self.q(k * T), "tick")
        times = [self.q(float(t)) for t in times]
        tids = np.asarray(tids).tolist()
        ai, n = 0, len(times)
        while self.events or ai < n:
            t_ev = self.events[0][0] if self.events else float("inf")
            t_ar = times[ai] if ai < n else float("inf")
            if t_ar <= t_ev:
                self.now = t_ar
                self._tagged_arrival(tids[ai])
                ai += 1
                continue
            t, _, kind, payload = heapq.heappop(self.events)
            self.now = t
            self._event(kind, payload, horizon_s)
        tot = self._finalize(horizon_s)
        tot.requests += self.shed
        tot.deadline_misses += self.shed
        return tot
