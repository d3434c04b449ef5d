"""Plain reference: a serial discrete-event simulator of the same semantics.

A copy of the paper's per-request simulator as the repository's serial
oracle defines it (worker lifecycle, deadline-aware dispatch of Alg. 3
under the three Table-9 dispatchers, the per-interval Spork allocator of
Algs. 1-2 with the conditional-histogram predictor), written without the
failure model, which no benchmark configuration turns on. It imports
nothing of the program: the fleet parameters, breakeven threshold and
objective coefficients are computed here from the configuration file.

Arithmetic runs in float64. ``precision="bfloat16"`` rounds every stored
time, load and energy to bfloat16 instead: the control that the
comparison in `perfbench.lib.compare` must reject.
"""

from __future__ import annotations

import heapq
from bisect import bisect_right, insort
from dataclasses import dataclass

import ml_dtypes
import numpy as np

_BF16 = ml_dtypes.bfloat16


def _q64(x: float) -> float:
    return x


def _q16(x: float) -> float:
    return float(_BF16(x))




# ------------------------------------------------------------ parameters
@dataclass(frozen=True)
class Fleet:
    """Worker parameters (paper Table 6) and the fleet's interval."""

    cpu: dict
    fpga: dict
    T_s: float
    cpu_idle_timeout_s: float
    max_fpgas: int

    @classmethod
    def from_config(cls, f: dict) -> "Fleet":
        T = f["interval_s"] if f.get("interval_s") is not None \
            else f["fpga"]["spin_up_s"]
        return cls(dict(f["cpu"]), dict(f["fpga"]), float(T),
                   float(f["cpu_idle_timeout_s"]), int(f["max_fpgas"]))

    @property
    def S(self) -> float:
        return self.fpga["speedup"] / self.cpu["speedup"]

    def spec(self, kind: str) -> dict:
        return self.fpga if kind == "fpga" else self.cpu

    def timeout(self, kind: str) -> float:
        return self.T_s if kind == "fpga" else self.cpu_idle_timeout_s


def energy_objective(fl: Fleet) -> tuple[float, tuple]:
    """Energy objective (energy weight 1): the breakeven threshold of
    Eq. 1, clamped to one interval, and the Alg.-2 coefficients
    (co_min, co_over, co_under, amort_unit)."""
    f, c, S, T = fl.fpga, fl.cpu, fl.S, fl.T_s
    den = c["busy_w"] - f["busy_w"] / S + f["idle_w"] / S
    tb = float("inf") if den <= 0 else T * f["idle_w"] / den
    coeffs = (f["busy_w"] * T, f["idle_w"] * T, S * c["busy_w"] * T,
              f["busy_w"] * f["spin_up_s"])
    return min(tb, T), coeffs


# ------------------------------------------------------------- predictor
class Predictor:
    """Alg. 2: next allocation from the histogram of needed FPGAs
    conditioned on the count two intervals back, minimizing the expected
    objective plus the lifetime-amortized spin-up cost."""

    def __init__(self, n_max: int, coeffs: tuple, T_s: float):
        self.n_max, self.coeffs, self.T_s = n_max, coeffs, T_s
        self.H = np.zeros((n_max, n_max))
        self.life_sum = np.zeros(n_max)
        self.life_cnt = np.zeros(n_max)

    def observe(self, n_lag2: int, n_needed: int) -> None:
        self.H[min(n_lag2, self.n_max - 1), min(n_needed, self.n_max - 1)] += 1

    def record_lifetime(self, level: int, life_s: float) -> None:
        level = min(level, self.n_max - 1)
        self.life_sum[level] += life_s
        self.life_cnt[level] += 1

    def predict(self, n_prev: int, n_curr: int) -> int:
        n_prev = min(n_prev, self.n_max - 1)
        hist = self.H[n_prev]
        total = hist.sum()
        if total <= 0:
            return n_prev
        n = self.n_max
        co_min, co_over, co_under, amort_unit = self.coeffs
        avg = np.where(self.life_cnt > 0,
                       self.life_sum / np.maximum(self.life_cnt, 1), self.T_s)
        per_level = amort_unit / np.maximum(np.ceil(avg / self.T_s), 1.0)
        gated = np.where(np.arange(n) >= n_curr, per_level, 0.0)
        amort = np.concatenate([[0.0], np.cumsum(gated)])[:n]
        p = hist / total
        bins = np.arange(n, dtype=np.float64)
        P, M = np.cumsum(p), np.cumsum(p * bins)
        Pm1 = np.concatenate([[0.0], P[:-1]])
        Mm1 = np.concatenate([[0.0], M[:-1]])
        tail = P[-1] - Pm1
        j = (co_min * (Mm1 + bins * tail) + co_over * (bins * Pm1 - Mm1)
             + co_under * ((M[-1] - Mm1) - bins * tail) + amort)
        seen = np.nonzero(hist > 0)[0]
        lo, hi = seen[0], seen[-1]
        return int(lo + np.argmin(j[lo:hi + 1]))


# ------------------------------------------------------------------- DES
@dataclass
class _Worker:
    wid: int
    kind: str
    alloc_t: float
    ready_at: float
    level: int
    avail: float = 0.0
    busy: float = 0.0
    dealloc_t: float = -1.0
    idle_mark: float = -1.0


@dataclass
class Totals:
    """What a run of the reference produces (the program's `RunTotals`
    fields that the comparison reads)."""

    requests: int = 0
    deadline_misses: int = 0
    fpga_spinups: int = 0
    cpu_spinups: int = 0
    energy_j: float = 0.0
    cost_usd: float = 0.0


class Des:
    """One fleet, one dispatch policy, the energy objective, a stream of
    arrivals."""

    def __init__(self, fleet: Fleet, size_s: float, deadline_s: float,
                 dispatcher: str, n_max: int, precision: str = "float64"):
        self.q = _q16 if precision == "bfloat16" else _q64
        self.fleet, self.size, self.deadline = fleet, size_s, deadline_s
        self.dispatcher = dispatcher
        self.tb, coeffs = energy_objective(fleet)
        self.n_max = n_max
        self.pred = Predictor(n_max, coeffs, fleet.T_s)
        self.workers: dict[int, _Worker] = {}
        self.order = {"fpga": [], "cpu": []}     # sorted (avail, wid)
        self.pending = {"fpga": [], "cpu": []}   # wids still spinning up
        self.ring: list[int] = []                # round-robin FPGA ring
        self.rr_pos = 0
        self.wid = 0
        self.events: list = []
        self.seq = 0
        self.now = 0.0
        self.F_acc = self.C_acc = 0.0
        self.n_lag = [0, 0]
        self.tot = Totals()

    def _push(self, t: float, kind: str, payload: int = 0) -> None:
        self.seq += 1
        heapq.heappush(self.events, (t, self.seq, kind, payload))

    # ---------- lifecycle
    def _spin_up(self, kind: str) -> _Worker:
        self.wid += 1
        w = _Worker(self.wid, kind, self.now,
                    self.q(self.now + self.fleet.spec(kind)["spin_up_s"]),
                    len(self.order[kind]) + len(self.pending[kind]))
        w.avail = w.ready_at
        self.workers[w.wid] = w
        self.pending[kind].append(w.wid)
        self._push(w.ready_at, "ready", w.wid)
        if kind == "fpga":
            self.tot.fpga_spinups += 1
        else:
            self.tot.cpu_spinups += 1
        return w

    def _on_ready(self, w: _Worker) -> None:
        if w.dealloc_t >= 0:
            return
        self.pending[w.kind].remove(w.wid)
        insort(self.order[w.kind], (w.avail, w.wid))
        if w.kind == "fpga":
            insort(self.ring, w.wid)
        if w.avail <= self.now:
            self._mark_idle(w)

    def _mark_idle(self, w: _Worker) -> None:
        w.idle_mark = self.now
        self._push(self.q(self.now + self.fleet.timeout(w.kind)),
                   "idle_check", w.wid)

    def _on_idle_check(self, w: _Worker) -> None:
        if w.dealloc_t >= 0:
            return
        if (w.avail <= w.idle_mark
                and self.now - w.idle_mark >= self.fleet.timeout(w.kind) - 1e-9):
            w.dealloc_t = self.now
            lst = self.order[w.kind]
            i = bisect_right(lst, (w.avail, w.wid)) - 1
            if i >= 0 and lst[i] == (w.avail, w.wid):
                del lst[i]
            if w.wid in self.pending[w.kind]:
                self.pending[w.kind].remove(w.wid)
            if w.wid in self.ring:
                self.ring.remove(w.wid)
            if w.kind == "fpga":
                self.pred.record_lifetime(w.level, self.now - w.alloc_t)

    def _on_complete(self, w: _Worker) -> None:
        if w.dealloc_t < 0 and w.avail <= self.now + 1e-12:
            self._mark_idle(w)

    # ---------- dispatch (Alg. 3)
    def _service(self, kind: str) -> float:
        return self.size / (self.fleet.S if kind == "fpga" else 1.0)

    def _try_type(self, kind: str) -> _Worker | None:
        """Busiest ready worker that still meets the deadline (or the
        least idle one), else the pending worker with most queued load."""
        slack = self.now + self.deadline - self._service(kind)
        lst = self.order[kind]
        i = bisect_right(lst, (slack, float("inf")))
        if i > 0:
            return self.workers[lst[i - 1][1]]
        best = None
        for wid in self.pending[kind]:
            w = self.workers[wid]
            if w.avail + self._service(kind) <= self.now + self.deadline:
                if best is None or w.avail > best.avail:
                    best = w
        return best

    def _find_worker(self) -> _Worker | None:
        d = self.dispatcher
        if d == "spork":
            return self._try_type("fpga") or self._try_type("cpu")
        if d == "index_packing":
            a, b = self._try_type("fpga"), self._try_type("cpu")
            if a and b:
                return a if a.avail >= b.avail else b
            return a or b
        if d == "round_robin":
            n = len(self.ring)
            for k in range(n):
                w = self.workers[self.ring[(self.rr_pos + k) % n]]
                slack = self.now + self.deadline - self._service(w.kind)
                if max(w.avail, self.now) <= slack:
                    self.rr_pos = (self.rr_pos + k + 1) % n
                    return w
            return self._try_type("cpu")
        raise ValueError(f"unknown dispatcher {d!r}")

    def _assign(self, w: _Worker) -> None:
        q = self.q
        service = self._service(w.kind)
        start = max(w.avail, self.now)
        in_order = w.dealloc_t < 0 and w.ready_at <= self.now
        if in_order:
            lst = self.order[w.kind]
            i = bisect_right(lst, (w.avail, w.wid)) - 1
            in_order = i >= 0 and lst[i] == (w.avail, w.wid)
            if in_order:
                del lst[i]
        w.avail = q(start + service)
        w.busy = q(w.busy + service)
        if in_order:
            insort(self.order[w.kind], (w.avail, w.wid))
        self._push(w.avail, "complete", w.wid)
        if w.kind == "fpga":
            self.F_acc = q(self.F_acc + service)
        else:
            self.C_acc = q(self.C_acc + service)
        if w.avail > self.now + self.deadline + 1e-9:
            self.tot.deadline_misses += 1

    def _arrival(self) -> None:
        self.tot.requests += 1
        w = self._find_worker()
        if w is None:
            w = self._spin_up("cpu")
        self._assign(w)

    # ---------- allocator (Algs. 1-2)
    def _on_tick(self) -> None:
        T = self.fleet.T_s
        lam = self.q(self.F_acc + self.C_acc / self.fleet.S)
        n = int(lam // T)
        if lam - n * T > self.tb:
            n += 1
        n_needed = min(n, self.n_max - 1)
        self.pred.observe(self.n_lag[1], n_needed)
        self.n_lag = [n_needed, self.n_lag[0]]
        n_curr = len(self.order["fpga"]) + len(self.pending["fpga"])
        target = self.pred.predict(n_needed, n_curr)
        for _ in range(max(0, target - n_curr)):
            if len(self.order["fpga"]) + len(self.pending["fpga"]) \
                    >= self.fleet.max_fpgas:
                break
            self._spin_up("fpga")
        self.F_acc = self.C_acc = 0.0

    def _event(self, kind: str, payload: int, horizon_s: float) -> None:
        if kind == "tick":
            if self.now < horizon_s:
                self._on_tick()
            return
        w = self.workers[payload]
        if kind == "ready":
            self._on_ready(w)
        elif kind == "complete":
            self._on_complete(w)
        else:
            self._on_idle_check(w)

    def run(self, times: np.ndarray, horizon_s: float) -> Totals:
        """Merge the arrival stream with the event heap, arrivals first
        at equal times."""
        T = self.fleet.T_s
        for k in range(int(np.ceil(horizon_s / T))):
            self._push(self.q(k * T), "tick")
        times = [self.q(float(t)) for t in times]
        ai, n = 0, len(times)
        while self.events or ai < n:
            t_ev = self.events[0][0] if self.events else float("inf")
            t_ar = times[ai] if ai < n else float("inf")
            if t_ar <= t_ev:
                self.now = t_ar
                self._arrival()
                ai += 1
                continue
            t, _, kind, payload = heapq.heappop(self.events)
            self.now = t
            self._event(kind, payload, horizon_s)
        return self._finalize(horizon_s)

    def _finalize(self, horizon_s: float) -> Totals:
        q, tot = self.q, self.tot
        for w in self.workers.values():
            spec = self.fleet.spec(w.kind)
            end = w.dealloc_t if w.dealloc_t >= 0 else max(horizon_s, w.avail)
            life = max(end - w.alloc_t, 0.0)
            idle = max(life - w.busy - spec["spin_up_s"], 0.0)
            spin_j = (spec["spin_up_s"] + spec["spin_down_s"]) * spec["busy_w"]
            tot.energy_j = q(tot.energy_j + (w.busy * spec["busy_w"]
                                             + idle * spec["idle_w"] + spin_j))
            tot.cost_usd = q(tot.cost_usd + (life + spec["spin_down_s"])
                             * (spec["cost_per_hr"] / 3600.0))
        return tot
