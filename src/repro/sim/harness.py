"""Resilient sweep execution: checkpoint/resume, retry + degradation,
invariant guards.

The paper's full-size grids (10 seeds x 2-hour horizons, Tables 8-9)
run for minutes to hours; PR 6 made the *simulated fleet* fault-tolerant,
this module makes the *sweep framework that runs it* fault-tolerant.
It wraps the plan/execute stack (`repro.sim.plan` -> `repro.sim.exec`)
with three orthogonal layers, all reachable through the ordinary entry
points (``sweep(..., checkpoint_dir=...)`` etc.):

1. **Checkpoint/resume** — every completed `ChunkDispatch` result is
   persisted to a `repro.checkpoint.ChunkStore` (atomic npz + manifest,
   `repro.checkpoint.store.save_named`), content-addressed by
   `chunk_fingerprint`: a sha256 over the chunk's static program
   arguments, every padded input array (bytes + dtype + shape — which
   bakes in the resolved scenario demand and FailureSpec knobs), the
   backend name, and the `CODE_SALT` code-version salt. A sweep killed
   (even SIGKILL) mid-run and restarted with the same ``checkpoint_dir``
   re-executes only the chunks that never finished and returns results
   bit-identical to an uninterrupted run
   (tests/test_harness.py::test_sigkill_mid_sweep_resume_bit_identical).
   Bump `CODE_SALT` whenever engine semantics change: stale chunk
   results must never be resumed across a semantics change.

2. **Retry + graceful degradation** — each dispatch gets bounded retry
   with exponential backoff and an optional per-chunk wall timeout
   (`RetryPolicy`). A chunk whose dispatches keep failing on a non-local
   backend (device loss, `shard_map` failure, OOM — anything the
   backend raises) is *degraded* to `LocalBackend` instead of killing
   the sweep; degraded chunk indices are recorded in the result's
   ``meta['degraded_chunks']``. Only when the local fallback also fails
   does the sweep raise `ChunkExecutionError`.

3. **Invariant guards** — `check_totals` / `check_sweep_result` run a
   validator pass over every `RunTotals` / batched accumulator
   (`INVARIANTS` lists the exact checks: NaN/Inf sentinels,
   non-negativity, request conservation with the PR-6 resilience
   counters reconciled, energy-component accounting, served-work
   conservation), raising structured `InvariantViolation` errors.
   `repro.sim.exec.execute` runs them by default on every sweep —
   including every `benchmarks/run.py` suite — unless the
   ``REPRO_SKIP_INVARIANTS`` env var opts out (perf runs).
   `check_drift` bounds serial-vs-batched engine drift for the
   equivalence suites.

Contract documentation: docs/architecture.md "Execution hardening";
operational workflow: benchmarks/README.md "Resuming long sweeps".
"""

from __future__ import annotations

import hashlib
import math
import os
import signal
import threading
import time
from dataclasses import dataclass
from typing import Any, Callable, Sequence

import numpy as np

from repro.checkpoint.manager import ChunkStore
from repro.core.metrics import RunTotals
from repro.sim.ratesim import Accum

#: Code-version salt folded into every chunk fingerprint. Bump when the
#: simulator engines change semantics: resuming a checkpoint written by
#: different engine code must miss, not silently mix results.
CODE_SALT = "repro-sweep-harness-v4"  # v4: the arrival-backend
                                      # selector left the event/fleet
                                      # chunk statics

ENV_SKIP_INVARIANTS = "REPRO_SKIP_INVARIANTS"

#: Test hook (tests/test_harness.py): after this many *live-executed and
#: persisted* chunks, the process SIGKILLs itself — a deterministic
#: stand-in for "the job died at minute 119" that still exercises the
#: real kill path (no atexit, no finally blocks).
ENV_KILL_AFTER = "REPRO_HARNESS_KILL_AFTER_CHUNKS"


class ChunkExecutionError(RuntimeError):
    """A chunk dispatch failed after exhausting retries (and, when
    degradation applies, the local fallback)."""


class ChunkTimeout(ChunkExecutionError):
    """A chunk dispatch exceeded its per-chunk wall timeout."""


class InvariantViolation(RuntimeError):
    """A structured physics/accounting violation in simulator output.

    ``invariant`` names the violated rule (a key of `INVARIANTS`),
    ``where`` locates it (cell index / suite), ``detail`` carries the
    offending values."""

    def __init__(self, invariant: str, detail: str, where: str = ""):
        self.invariant = invariant
        self.detail = detail
        self.where = where
        loc = f" [{where}]" if where else ""
        super().__init__(f"invariant {invariant!r} violated{loc}: {detail}")


#: The validator catalogue (docs/architecture.md "Execution hardening").
INVARIANTS = {
    "finite": "every float total is finite (NaN/Inf sentinel)",
    "non_negative": "energies, costs, work terms and counters are >= 0",
    "request_conservation": "deadline_misses <= requests and served work "
                            "<= offered work (within float32 drift)",
    "resilience_reconciled": "failure_misses <= deadline_misses, "
                             "recovered_requests <= crashes, "
                             "retries <= failed_spinups",
    "energy_components": "stored energy components (+ wasted spin-up) "
                         "never exceed total energy_j",
    "drift": "serial-vs-batched engine totals agree within rtol "
             "(check_drift; not run per-sweep)",
    "tenant_conservation": "per-tenant TenantTotals rows reconcile with "
                           "the fleet-level RunTotals (admitted/shed/"
                           "missed exactly; work/energy/cost to float)",
}

# Served work may exceed offered work only by float32 accumulation drift
# over ~1e4-second traces; counters are exact.
_WORK_RTOL = 2e-2
_COMPONENT_RTOL = 1e-5


def invariants_enabled() -> bool:
    """Invariant guards run by default; ``REPRO_SKIP_INVARIANTS=1`` (any
    non-empty value but ``0``) opts out for perf runs."""
    return os.environ.get(ENV_SKIP_INVARIANTS, "") in ("", "0")


def check_totals(t: RunTotals, where: str = "") -> None:
    """Validate one `RunTotals` against the invariant catalogue; raises
    `InvariantViolation` on the first violation."""
    for f in RunTotals.FLOAT_FIELDS:
        v = float(getattr(t, f))
        if not math.isfinite(v):
            raise InvariantViolation("finite", f"{f} = {v}", where)
        if v < 0.0:
            raise InvariantViolation("non_negative", f"{f} = {v}", where)
    for f in RunTotals.COUNT_FIELDS:
        v = getattr(t, f)
        if not math.isfinite(float(v)):
            raise InvariantViolation("finite", f"{f} = {v}", where)
        if v < 0:
            raise InvariantViolation("non_negative", f"{f} = {v}", where)
    if t.deadline_misses > t.requests:
        raise InvariantViolation(
            "request_conservation",
            f"deadline_misses ({t.deadline_misses}) > requests "
            f"({t.requests})", where)
    served = t.work_on_fpga_cpu_s + t.work_on_cpu_cpu_s
    if served > t.work_cpu_s * (1.0 + _WORK_RTOL) + 1.0:
        raise InvariantViolation(
            "request_conservation",
            f"served work ({served:.6g} cpu-s) exceeds offered work "
            f"({t.work_cpu_s:.6g} cpu-s) beyond float32 drift", where)
    if t.failure_misses > t.deadline_misses:
        raise InvariantViolation(
            "resilience_reconciled",
            f"failure_misses ({t.failure_misses}) > deadline_misses "
            f"({t.deadline_misses})", where)
    if t.recovered_requests > t.crashes:
        raise InvariantViolation(
            "resilience_reconciled",
            f"recovered_requests ({t.recovered_requests}) > crashes "
            f"({t.crashes})", where)
    if t.retries > t.failed_spinups:
        raise InvariantViolation(
            "resilience_reconciled",
            f"retries ({t.retries}) > failed_spinups "
            f"({t.failed_spinups})", where)
    components = (t.fpga_idle_j + t.fpga_busy_j + t.cpu_busy_j + t.spinup_j
                  + t.wasted_spinup_j)
    if components > t.energy_j * (1.0 + _COMPONENT_RTOL) + 1e-6:
        raise InvariantViolation(
            "energy_components",
            f"component sum ({components:.6g} J) exceeds energy_j "
            f"({t.energy_j:.6g} J)", where)


def check_accum(accum: Accum, work: np.ndarray | None,
                requests: np.ndarray | None, where: str = "") -> None:
    """Vectorized validator over a stacked rate-sweep `Accum` (leaves
    shaped ``(n_cells,)``) — the batched-accumulator counterpart of
    `check_totals`; locates the first offending cell."""
    leaves = {f: np.asarray(leaf, np.float64)
              for f, leaf in zip(Accum._fields, accum)}
    for f, leaf in leaves.items():
        bad = ~np.isfinite(leaf)
        if bad.any():
            i = int(np.argmax(bad))
            raise InvariantViolation("finite", f"{f}[{i}] = {leaf[i]}",
                                     where or f"cell {i}")
        neg = leaf < 0.0
        if neg.any():
            i = int(np.argmax(neg))
            raise InvariantViolation("non_negative", f"{f}[{i}] = {leaf[i]}",
                                     where or f"cell {i}")
    if requests is not None:
        over = leaves["missed_requests"] > np.asarray(requests, np.float64)
        if over.any():
            i = int(np.argmax(over))
            raise InvariantViolation(
                "request_conservation",
                f"missed_requests[{i}] ({leaves['missed_requests'][i]:.6g}) "
                f"> requests[{i}] ({np.asarray(requests)[i]})",
                where or f"cell {i}")
    if work is not None:
        served = leaves["work_f"] + leaves["work_c"]
        lim = np.asarray(work, np.float64) * (1.0 + _WORK_RTOL) + 1.0
        over = served > lim
        if over.any():
            i = int(np.argmax(over))
            raise InvariantViolation(
                "request_conservation",
                f"served work[{i}] ({served[i]:.6g} cpu-s) exceeds offered "
                f"work ({np.asarray(work)[i]:.6g} cpu-s) beyond float32 "
                "drift", where or f"cell {i}")


def check_fleet_result(result, where: str = "") -> None:
    """Validate a `FleetSweepResult`: the per-cell `RunTotals` pass plus
    the tenant conservation contract (`repro.core.metrics.TenantTotals`
    docstring) — per-tenant rows must reconcile with the fleet totals:
    exactly on admitted/shed/missed counters, to float rounding on
    work/energy/cost attribution."""
    for i, (t, rows) in enumerate(zip(result._totals, result._tenants)):
        loc = f"{where}cell {i}".strip()
        check_totals(t, where=loc)
        adm = sum(r.admitted for r in rows)
        shed = sum(r.shed for r in rows)
        offered = sum(r.requests for r in rows)
        missed = sum(r.deadline_misses for r in rows)
        exact = [
            ("sum(admitted)", adm, "requests", t.requests),
            ("sum(shed)", shed, "breakdown[shed_requests]",
             t.breakdown.get("shed_requests", 0)),
            ("sum(offered)", offered, "breakdown[offered_requests]",
             t.breakdown.get("offered_requests", 0)),
            ("sum(deadline_misses)", missed, "deadline_misses",
             t.deadline_misses),
        ]
        for na, a, nb, b in exact:
            if int(a) != int(b):
                raise InvariantViolation(
                    "tenant_conservation", f"{na} ({a}) != {nb} ({b})", loc)
        for r in rows:
            if r.admitted + r.shed != r.requests:
                raise InvariantViolation(
                    "tenant_conservation",
                    f"tenant {r.tenant}: admitted ({r.admitted}) + shed "
                    f"({r.shed}) != requests ({r.requests})", loc)
            if r.deadline_misses > r.admitted:
                raise InvariantViolation(
                    "tenant_conservation",
                    f"tenant {r.tenant}: deadline_misses "
                    f"({r.deadline_misses}) > admitted ({r.admitted})", loc)
        approx = [
            ("sum(work_on_fpga_cpu_s)",
             sum(r.work_on_fpga_cpu_s for r in rows), t.work_on_fpga_cpu_s),
            ("sum(work_on_cpu_cpu_s)",
             sum(r.work_on_cpu_cpu_s for r in rows), t.work_on_cpu_cpu_s),
            ("sum(energy_j)", sum(r.energy_j for r in rows), t.energy_j),
            ("sum(cost_usd)", sum(r.cost_usd for r in rows), t.cost_usd),
        ]
        for name, a, b in approx:
            if abs(a - b) > max(abs(b), 1.0) * 1e-6:
                raise InvariantViolation(
                    "tenant_conservation",
                    f"{name} ({a:.9g}) != fleet total ({b:.9g})", loc)


def check_sweep_result(result, where: str = "") -> None:
    """Validate a `SweepResult` (vectorized accumulator pass),
    `EventSweepResult` (per-cell `RunTotals` pass) or `FleetSweepResult`
    (totals pass + tenant conservation). No-op when
    ``REPRO_SKIP_INVARIANTS`` opts out — callers gate themselves;
    `repro.sim.exec.execute` is the default call site."""
    if getattr(result, "_tenants", None) is not None:  # FleetSweepResult
        check_fleet_result(result, where=where)
        return
    totals = getattr(result, "_totals", None)
    if totals is not None:            # EventSweepResult
        for i, t in enumerate(totals):
            check_totals(t, where=f"{where}cell {i}".strip())
        return
    check_accum(result.accum, result._work, result._requests, where=where)


_DRIFT_FIELDS = ("energy_j", "cost_usd", "work_on_fpga_cpu_s",
                 "work_on_cpu_cpu_s")
_DRIFT_EXACT = ("requests",)


def check_drift(serial: RunTotals, batched: RunTotals, rtol: float = 0.05,
                where: str = "") -> None:
    """Serial-vs-batched drift bound: the two engines must agree exactly
    on request counts and within ``rtol`` relative on energy/cost/work
    (the documented equivalence contract, docs/architecture.md §3).
    Raises `InvariantViolation('drift', ...)` beyond the bound."""
    for f in _DRIFT_EXACT:
        a, b = getattr(serial, f), getattr(batched, f)
        if a != b:
            raise InvariantViolation(
                "drift", f"{f}: serial {a} != batched {b}", where)
    for f in _DRIFT_FIELDS:
        a, b = float(getattr(serial, f)), float(getattr(batched, f))
        scale = max(abs(a), abs(b), 1e-9)
        if abs(a - b) / scale > rtol:
            raise InvariantViolation(
                "drift",
                f"{f}: serial {a:.6g} vs batched {b:.6g} "
                f"(rel {abs(a - b) / scale:.3g} > rtol {rtol})", where)


# --------------------------------------------------------------- fingerprints
def chunk_fingerprint(dispatch, backend_name: str,
                      salt: str = CODE_SALT) -> str:
    """Stable content fingerprint of one `ChunkDispatch` under one
    backend: sha256 over the code salt, backend name, chunk kind/shape,
    the static program arguments (repr — policies, interval/spin-up
    statics, `FailStatic`) and every padded input array (name, dtype,
    shape, raw bytes). Two chunks with the same fingerprint compute the
    same rows, so completed results are safe to resume across runs; any
    change to cells, resolved scenario demand, failure knobs, backend or
    engine code version changes the fingerprint and forces re-execution."""
    h = hashlib.sha256()
    for part in (salt, backend_name, dispatch.kind, repr(dispatch.static),
                 str(dispatch.chunk)):
        h.update(part.encode())
        h.update(b"\x00")
    for name in sorted(dispatch.arrays):
        a = np.ascontiguousarray(dispatch.arrays[name])
        h.update(name.encode())
        h.update(str(a.dtype).encode())
        h.update(repr(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()[:32]


def plan_fingerprint(plan, backend_name: str, salt: str = CODE_SALT) -> str:
    """Fingerprint of a whole `SweepPlan` (order-independent combination
    of its chunk fingerprints)."""
    h = hashlib.sha256()
    for fp in sorted(chunk_fingerprint(d, backend_name, salt)
                     for d in plan.dispatches):
        h.update(fp.encode())
    return h.hexdigest()[:32]


# ------------------------------------------------------- retry + degradation
@dataclass(frozen=True)
class RetryPolicy:
    """Bounded retry with exponential backoff + per-chunk wall timeout.

    ``max_retries`` counts *re*-attempts (0 = one attempt only);
    ``timeout_s`` bounds each attempt's wall time (None = unbounded);
    ``degrade`` lets a non-local backend fall back to `LocalBackend`
    after its retries are exhausted instead of failing the sweep."""

    max_retries: int = 2
    backoff_s: float = 0.25
    backoff_mult: float = 2.0
    timeout_s: float | None = None
    degrade: bool = True


DEFAULT_RETRY = RetryPolicy()


def _call_with_timeout(fn: Callable[[], Any], timeout_s: float | None,
                       label: str):
    """Run ``fn`` with a wall timeout. JAX dispatches cannot be
    cancelled, so the attempt runs in a daemon thread: on timeout the
    computation is abandoned (it finishes or dies in the background) and
    `ChunkTimeout` is raised — the retry/degradation ladder decides what
    happens next."""
    if timeout_s is None:
        return fn()
    box: dict[str, Any] = {}
    done = threading.Event()

    def worker():
        try:
            box["value"] = fn()
        except BaseException as e:   # noqa: BLE001 — re-raised in caller
            box["error"] = e
        finally:
            done.set()

    threading.Thread(target=worker, daemon=True).start()
    if not done.wait(timeout_s):
        raise ChunkTimeout(f"{label} exceeded wall timeout {timeout_s}s")
    if "error" in box:
        raise box["error"]
    return box["value"]


def _flatten_output(kind: str, out) -> list[np.ndarray]:
    """Flat, host-side leaf list of one dispatch's output pytree."""
    if kind == "rate":
        leaves = list(out)                       # Accum
    elif kind == "fleet":
        acc, fail, over, fa = out                # (... , FleetTenantAcc)
        leaves = list(acc) + list(fail) + [over] + list(fa)
    else:
        acc, fail, over = out                    # (Accum, FailAcc, overflow)
        leaves = list(acc) + list(fail) + [over]
    return [np.asarray(x) for x in leaves]


def _reassemble_output(kind: str, leaves: Sequence[np.ndarray]):
    """Inverse of `_flatten_output` (numpy leaves; the scatter loops in
    `repro.sim.exec` only ever np.asarray them)."""
    if kind == "rate":
        return Accum(*leaves)
    from repro.sim.events_batched import FailAcc
    n = len(Accum._fields)
    m = len(FailAcc._fields)
    if kind == "fleet":
        from repro.fleet.engine import FleetTenantAcc
        k = n + m + 1
        return (Accum(*leaves[:n]), FailAcc(*leaves[n:n + m]), leaves[n + m],
                FleetTenantAcc(*leaves[k:k + len(FleetTenantAcc._fields)]))
    return (Accum(*leaves[:n]), FailAcc(*leaves[n:n + m]), leaves[n + m])


class ResilientRunner:
    """Per-sweep execution driver: checkpoint lookup/persist, bounded
    retry, wall timeout and mesh->local degradation around every
    `ChunkDispatch`. One instance per `repro.sim.exec.execute` call; its
    `meta()` is attached to the `SweepResult`/`EventSweepResult`."""

    def __init__(self, backend, checkpoint_dir=None,
                 retry: RetryPolicy | None = None):
        self.backend = backend
        self.retry = retry or DEFAULT_RETRY
        self.store = (ChunkStore(checkpoint_dir)
                      if checkpoint_dir is not None else None)
        self.executed_chunks = 0     # ran live this call
        self.restored_chunks = 0     # served from the checkpoint store
        self.retried_dispatches = 0  # failed attempts that were retried
        self.degraded_chunks: list[int] = []   # chunk indices run on the
        self._chunk_i = -1                     # local fallback
        self._local = None
        kill_after = os.environ.get(ENV_KILL_AFTER, "")
        self._kill_after = int(kill_after) if kill_after else None

    def meta(self) -> dict:
        return {
            "executed_chunks": self.executed_chunks,
            "restored_chunks": self.restored_chunks,
            "retried_dispatches": self.retried_dispatches,
            "degraded_chunks": list(self.degraded_chunks),
            "checkpointed": self.store is not None,
        }

    # -- the one entry point the exec scatter loops call per dispatch --
    def run(self, dispatch):
        self._chunk_i += 1
        key = (chunk_fingerprint(dispatch, self.backend.name)
               if self.store is not None else None)
        if key is not None and self.store.has(key):
            self.restored_chunks += 1
            return _reassemble_output(dispatch.kind, self.store.load(key))
        out = self._run_live(dispatch)
        leaves = _flatten_output(dispatch.kind, out)
        if key is not None:
            self.store.save(key, leaves,
                            metadata={"kind": dispatch.kind,
                                      "backend": self.backend.name,
                                      "chunk": dispatch.chunk,
                                      "n_real": dispatch.n_real,
                                      "salt": CODE_SALT})
        self.executed_chunks += 1
        if (self._kill_after is not None
                and self.executed_chunks >= self._kill_after):
            # test hook: die the hard way, mid-sweep, after persisting
            os.kill(os.getpid(), signal.SIGKILL)
        return _reassemble_output(dispatch.kind, leaves)

    def _attempt(self, backend, dispatch):
        """One dispatch attempt, blocked to completion so the timeout
        covers compile + compute, not just program launch."""
        import jax
        return jax.block_until_ready(backend.run(dispatch))

    def _run_live(self, dispatch):
        r = self.retry
        label = f"chunk {self._chunk_i} ({dispatch.kind}, " \
                f"{dispatch.n_real} cells)"
        delay = r.backoff_s
        last: BaseException | None = None
        for attempt in range(r.max_retries + 1):
            try:
                return _call_with_timeout(
                    lambda: self._attempt(self.backend, dispatch),
                    r.timeout_s, label)
            except BaseException as e:  # noqa: BLE001 — ladder decides
                last = e
                if attempt < r.max_retries:
                    self.retried_dispatches += 1
                    if delay > 0:
                        time.sleep(delay)
                    delay *= r.backoff_mult
        # retries exhausted: degrade a non-local backend to LocalBackend
        # (device loss / shard_map failure must not kill the sweep)
        if r.degrade and self.backend.name != "local":
            if self._local is None:
                from repro.sim.exec import LocalBackend
                self._local = LocalBackend()
            try:
                out = _call_with_timeout(
                    lambda: self._attempt(self._local, dispatch),
                    r.timeout_s, label + " [degraded to local]")
            except BaseException as e:  # noqa: BLE001
                raise ChunkExecutionError(
                    f"{label} failed on backend {self.backend.name!r} "
                    f"after {r.max_retries + 1} attempts AND on the local "
                    f"fallback: {e}") from e
            self.degraded_chunks.append(self._chunk_i)
            return out
        raise ChunkExecutionError(
            f"{label} failed on backend {self.backend.name!r} after "
            f"{r.max_retries + 1} attempts: {last}") from last
