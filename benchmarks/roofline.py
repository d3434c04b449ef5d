"""Roofline analysis per (arch x shape) on the single-pod production mesh.

Methodology (EXPERIMENTS.md §Roofline):
  * XLA's HloCostAnalysis counts while-loop bodies ONCE (verified
    empirically), so scanned-layer costs cannot be read off the full
    config. Instead we lower depth-reduced variants with every model scan
    UNROLLED (models.flags.SCAN_UNROLL) at two depths La < Lb, fit
    x(L) = fixed + slope * L, and extrapolate to the full depth.
  * Collective bytes come from the post-SPMD HLO census of the same
    unrolled lowerings (per-device shapes), extrapolated identically.
  * memory-fit numbers come from the full-depth compile (scan form, the
    deployable artifact).

Terms per cell (peaks of the target chip, `launch.mesh.chip_peaks` of
``TARGET_DEVICE_KIND`` — TPU v5e):
    compute_s    = HLO_FLOPs_dev / 197e12
    memory_s     = HLO_bytes_dev / 819e9
    collective_s = collective_bytes_dev / 50e9   (per-link ICI)
plus MODEL_FLOPS = 6*N*D (train; 2*N*D inference, N = active params) and
the usefulness ratio MODEL_FLOPS / HLO_FLOPs.

Also includes the min-plus DP transition scaling study (``kind:
"minplus"`` rows): dense O(N^2) vs structured O(N log N) wall time per
step at N in {128, 512, 2048, 8192}, so the asymptotic win behind the
fig2 speedup is visible in results/roofline.json. Each row names the
platform and device kind it was timed on.

``--collect`` runs each dry-run lowering in a ``JAX_PLATFORMS=cpu``
child (placeholder CPU devices; it never contends for an accelerator),
and a child that fails fails the collection.

Usage:
    python -m benchmarks.roofline --collect   # runs the reduced lowerings
    python -m benchmarks.roofline --report    # prints the table
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from pathlib import Path

from repro.configs.registry import SHAPES, cells, get_config
from repro.launch.mesh import TARGET_DEVICE_KIND, chip_peaks

DRYRUN_DIR = Path("results/dryrun")
OUT = Path("results/roofline.json")
CHIPS = 256  # single-pod


def depth_points(arch: str) -> tuple[int, int]:
    cfg = get_config(arch, "full")
    if cfg.family == "hybrid":
        p = len(cfg.block_pattern)
        return p, 2 * p
    if cfg.family == "moe" and cfg.n_dense_layers:
        return 2, 4
    return 2, 4


def collect(only: list[str] | None = None) -> None:
    for arch, shape, _ in cells():
        if only and arch not in only:
            continue
        la, lb = depth_points(arch)
        for L in (la, lb):
            tag = DRYRUN_DIR / f"{arch}__{shape}__single__L{L}u.json"
            if tag.exists() and json.loads(tag.read_text()).get("ok"):
                continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape, "--mesh", "single",
                   "--layers", str(L), "--unroll",
                   "--out", str(DRYRUN_DIR)]
            print("collect:", " ".join(cmd[3:]))
            subprocess.run(cmd, env={**os.environ, "PYTHONPATH": "src",
                                     "JAX_PLATFORMS": "cpu"}, check=True)


def model_flops(arch: str, shape: str) -> float:
    cfg = get_config(arch, "full")
    spec = SHAPES[shape]
    n = cfg.param_count(active_only=True)
    if spec["kind"] == "train":
        tokens = spec["global_batch"] * spec["seq_len"]
        return 6.0 * n * tokens
    if spec["kind"] == "prefill":
        tokens = spec["global_batch"] * spec["seq_len"]
        return 2.0 * n * tokens
    return 2.0 * n * spec["global_batch"]      # decode: one token per seq


def _load(tag: str) -> dict | None:
    p = DRYRUN_DIR / f"{tag}.json"
    if not p.exists():
        return None
    rec = json.loads(p.read_text())
    return rec if rec.get("ok") else None


def extrapolate(arch: str, shape: str) -> dict | None:
    la, lb = depth_points(arch)
    a = _load(f"{arch}__{shape}__single__L{la}u")
    b = _load(f"{arch}__{shape}__single__L{lb}u")
    full = _load(f"{arch}__{shape}__single")
    if not (a and b):
        return None
    L = get_config(arch, "full").n_layers

    def fit(key, getter=lambda r, k: r.get(k, 0.0)):
        xa, xb = getter(a, key), getter(b, key)
        slope = (xb - xa) / (lb - la)
        return max(xa + slope * (L - la), xa)

    coll = lambda r, _: r["collectives"].get(
        "total_bytes_tpu", r["collectives"]["total_bytes"])
    rec = {
        "arch": arch, "shape": shape, "n_layers": L,
        "flops_dev": fit("hlo_flops"),
        "bytes_dev": fit("hlo_bytes"),
        "coll_bytes_dev": fit(None, coll),
        "mem_dev_bytes": (full or b).get("device_bytes_total", 0),
        "compile_ok_full": bool(full),
    }
    peaks = chip_peaks(TARGET_DEVICE_KIND)
    rec["compute_s"] = rec["flops_dev"] / peaks.flops_bf16
    rec["memory_s"] = rec["bytes_dev"] / peaks.hbm_bw
    rec["collective_s"] = rec["coll_bytes_dev"] / peaks.ici_bw_per_link
    terms = {"compute": rec["compute_s"], "memory": rec["memory_s"],
             "collective": rec["collective_s"]}
    rec["bottleneck"] = max(terms, key=terms.get)
    mf = model_flops(arch, shape)
    rec["model_flops_total"] = mf
    rec["model_flops_dev"] = mf / CHIPS
    rec["useful_ratio"] = (rec["model_flops_dev"] / rec["flops_dev"]
                           if rec["flops_dev"] > 0 else 0.0)
    # roofline fraction: useful work per second at the bottleneck
    step_s = max(terms.values())
    ideal_s = rec["model_flops_dev"] / peaks.flops_bf16
    rec["roofline_fraction"] = ideal_s / step_s if step_s > 0 else 0.0
    return rec


MINPLUS_NS = (128, 512, 2048, 8192)


def minplus_scaling(ns=MINPLUS_NS, reps: int = 3) -> list[dict]:
    """Dense vs structured min-plus transition wall time per step.

    One jitted step per (backend, N), timed post-compile (best of
    ``reps``), on a random monotone y_c instance — the same contraction
    the DP runs T times per solve, so the dense/structured ratio here is
    the per-interval speedup behind fig2. Every row names the platform
    and device kind it ran on."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.dp import minplus_step_jnp, minplus_step_structured

    backends = {"dense": jax.jit(minplus_step_jnp),
                "structured": jax.jit(
                    lambda F, p, c, co: minplus_step_structured(
                        F, p, c, co, check=False))}
    dev = jax.devices()[0]
    rows = []
    for n in ns:
        rng = np.random.default_rng(n)
        F = jnp.asarray(rng.normal(0, 100, n), jnp.float32)
        ycp = jnp.asarray(np.sort(rng.integers(0, n, n))[::-1], jnp.float32)
        ycc = jnp.asarray(np.sort(rng.integers(0, n, n))[::-1], jnp.float32)
        coeffs = (500.0, 5.0, 0.75, 0.75)
        row = {"kind": "minplus", "n": n, "platform": dev.platform,
               "device_kind": dev.device_kind}
        for name, fn in backends.items():
            out, arg = fn(F, ycp, ycc, coeffs)          # compile + warm
            out.block_until_ready()
            best = float("inf")
            for _ in range(reps):
                t0 = time.perf_counter()
                out, arg = fn(F, ycp, ycc, coeffs)
                out.block_until_ready()
                best = min(best, time.perf_counter() - t0)
            row[f"{name}_us"] = round(best * 1e6, 1)
        row["speedup"] = round(row["dense_us"] / max(row["structured_us"],
                                                     1e-9), 1)
        rows.append(row)
    return rows


def report(minplus_rows: list[dict] | None = None) -> list[dict]:
    """Summarize collected lowerings. ``--report`` stays read-mostly:
    unless fresh minplus scaling rows are passed in (the `run()` entry
    re-benchmarks them), previously recorded ones are carried over."""
    rows = []
    for arch, shape, _ in cells():
        rec = extrapolate(arch, shape)
        if rec is None:
            continue
        rows.append(rec)
    if minplus_rows is None:
        from benchmarks.common import load_json_or_quarantine
        prev = load_json_or_quarantine(str(OUT)) or []
        minplus_rows = [r for r in prev if r.get("kind") == "minplus"]
    rows.extend(minplus_rows)
    from benchmarks.common import atomic_write_json
    atomic_write_json(str(OUT), rows)
    return rows


def run() -> list[dict]:
    """Benchmark-runner entry: summarize whatever has been collected."""
    rows = report(minplus_scaling())
    minplus_rows = [r for r in rows if r.get("kind") == "minplus"]
    rows = [r for r in rows if r.get("kind") != "minplus"]
    return minplus_rows + [{
        "arch": r["arch"], "shape": r["shape"],
        "compute_ms": round(r["compute_s"] * 1e3, 3),
        "memory_ms": round(r["memory_s"] * 1e3, 3),
        "collective_ms": round(r["collective_s"] * 1e3, 3),
        "bottleneck": r["bottleneck"],
        "useful_ratio": round(r["useful_ratio"], 3),
        "roofline_frac": round(r["roofline_fraction"], 4),
        "mem_GiB": round(r["mem_dev_bytes"] / 2**30, 2),
    } for r in rows]


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--collect", action="store_true")
    ap.add_argument("--report", action="store_true")
    ap.add_argument("--arch", nargs="*", default=None)
    args = ap.parse_args()
    if args.collect:
        collect(args.arch)
    for row in run():
        print(row)
