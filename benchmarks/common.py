"""Shared benchmark helpers: fast-mode defaults, timing, CSV + JSON emission.

Also enables JAX's persistent compilation cache
(`repro.compile_cache`: ``$JAX_COMPILATION_CACHE_DIR`` if set, else
results/.jax_cache) so repeated benchmark runs — and the separate
suites of one run — skip recompiling the sweep-engine programs.
"""

from __future__ import annotations

import json
import os
import tempfile
import time

from repro.compile_cache import enable_compilation_cache

FAST = os.environ.get("BENCH_FAST", "1") != "0"

_RESULTS_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            os.pardir, "results")
SWEEP_JSON = os.environ.get(
    "BENCH_SWEEP_JSON", os.path.join(_RESULTS_DIR, "BENCH_sweep.json"))

enable_compilation_cache()


def fast_params(fast: bool = FAST):
    """(n_traces, horizon_s, n_apps_per_bucket) for fast vs full runs."""
    return (3, 1800, 4) if fast else (10, 7200, None)


def backend_meta() -> dict:
    """{backend, n_devices, platform} for the sweep execution backend
    this run resolves to (``BENCH_SWEEP_BACKEND``), recorded with every
    suite entry so BENCH_sweep.json numbers are attributable to a
    backend and a device."""
    import jax

    from repro.sim.exec import get_backend
    b = get_backend()
    return {"backend": b.name, "n_devices": b.n_devices,
            "platform": jax.devices()[0].platform}


def emit(name: str, rows: list[dict], t0: float) -> None:
    """Scaffold contract: ``name,us_per_call,derived`` CSV lines, plus a
    machine-readable suite -> {wall seconds, rows} entry in
    results/BENCH_sweep.json so the perf trajectory is tracked across PRs."""
    wall_s = time.time() - t0
    us = wall_s * 1e6 / max(len(rows), 1)
    for row in rows:
        derived = ";".join(f"{k}={v}" for k, v in row.items())
        print(f"{name},{us:.0f},{derived}")
    record_sweep(name, wall_s, len(rows))


HISTORY_CAP = 50


def atomic_write_json(path: str, data) -> None:
    """Crash-safe JSON write: serialize to a temp file in the target
    directory, fsync, then `os.replace` over the destination. A run
    killed mid-write (the SIGKILL resilience tests do exactly this)
    leaves either the old file or the new one — never a truncated
    half-JSON that poisons every later benchmark run."""
    path = os.path.abspath(path)
    directory = os.path.dirname(path)
    os.makedirs(directory, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=directory,
                               prefix=os.path.basename(path) + ".tmp.")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(data, f, indent=2, sort_keys=True)
            f.write("\n")
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


def load_json_or_quarantine(path: str) -> dict:
    """Read a results JSON; on corruption, move the bad file aside to
    ``<path>.corrupt`` (evidence, not data loss) and start fresh."""
    try:
        with open(path) as f:
            return json.load(f)
    except OSError:
        return {}
    except ValueError:
        try:
            os.replace(path, path + ".corrupt")
            print(f"[bench] WARNING: corrupt {path}; "
                  f"quarantined to {path}.corrupt")
        except OSError:     # pragma: no cover — read-only results dir
            pass
        return {}


def _load_sweep() -> dict:
    return load_json_or_quarantine(SWEEP_JSON)


def _save_sweep(data: dict) -> None:
    try:
        atomic_write_json(SWEEP_JSON, data)
    except OSError:         # pragma: no cover — read-only results dir
        pass


def record_sweep(name: str, wall_s: float, n_rows: int) -> None:
    """Merge one suite's timing into BENCH_sweep.json (best effort).

    The top-level fields hold the latest run (what CI's perf guard
    reads); ``history`` appends one `{wall_s, rows, fast}` entry per run
    (capped at the trailing HISTORY_CAP) so the file records a perf
    trajectory across PRs instead of overwriting it."""
    data = _load_sweep()
    entry = {"wall_s": round(wall_s, 3), "rows": n_rows, "fast": FAST,
             **backend_meta()}
    prev = data.get(name) or {}
    history = list(prev.get("history", []))
    if not history and prev:        # migrate pre-history records
        history.append({k: prev[k] for k in ("wall_s", "rows", "fast")
                        if k in prev})
    history = (history + [entry])[-HISTORY_CAP:]
    data[name] = {**entry, "history": history}
    _save_sweep(data)


def record_kv(name: str, **fields) -> None:
    """Merge an arbitrary record (e.g. an engine-comparison entry) into
    BENCH_sweep.json under ``name`` (best effort, like `record_sweep`)."""
    data = _load_sweep()
    data[name] = fields
    _save_sweep(data)


def timed(fn):
    """Run ``fn`` and return (result, start time) for `emit`."""
    t0 = time.time()
    out = fn()
    return out, t0
