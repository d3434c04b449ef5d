"""The path from the entry points to the device, checked without a chip.

  * `repro.kernels.backend.use_interpret` follows the platform only;
  * `repro.compile_cache` honours ``JAX_COMPILATION_CACHE_DIR`` and
    otherwise uses the checkout's fixed ``results/.jax_cache``;
  * peak rates come from a table keyed by device kind, and an unknown
    kind raises;
  * `benchmarks/run.py` exits non-zero when a suite raises;
  * every child that fabricates host devices runs with
    ``JAX_PLATFORMS=cpu``, and a child that fails fails its parent;
  * `chip_smoke.py` refuses a host without a TPU, and its phases pass
    on small grids (the mesh phase on four fabricated CPU devices);
  * the full-mode grids the smoke run uses have the published sizes.
"""

import json
import os
import subprocess
import sys
import textwrap
import types

import numpy as np
import pytest

import jax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _run_py(code: str, env_extra: dict | None = None, timeout: int = 600):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.pathsep.join([ROOT, os.path.join(ROOT, "src")])}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(env_extra or {})
    return subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          env=env, capture_output=True, text=True,
                          cwd=ROOT, timeout=timeout)


# ------------------------------------------------------- Pallas mode
@pytest.mark.parametrize("platform,interpret", [
    ("cpu", True), ("gpu", True), ("tpu", False)])
def test_use_interpret_follows_platform(monkeypatch, platform, interpret):
    from repro.kernels import backend
    monkeypatch.setattr(backend.jax, "default_backend", lambda: platform)
    assert backend.use_interpret() is interpret


# ---------------------------------------------------- compile cache
def test_compile_cache_env_dir_is_the_only_dir(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, compiled programs land there
    and the default directory is never created."""
    cache, default = tmp_path / "cache", tmp_path / "default"
    out = _run_py(f"""
        import jax, jax.numpy as jnp
        from repro import compile_cache
        compile_cache.DEFAULT_DIR = {str(default)!r}
        print("DIR=" + compile_cache.enable_compilation_cache())
        print("CFG=" + str(jax.config.jax_compilation_cache_dir))
        jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0)).block_until_ready()
    """, {"JAX_COMPILATION_CACHE_DIR": str(cache)})
    assert out.returncode == 0, out.stderr[-2000:]
    assert f"DIR={cache}" in out.stdout
    assert f"CFG={cache}" in out.stdout
    assert cache.is_dir() and any(cache.iterdir())
    assert not default.exists()


def test_compile_cache_default_is_fixed_checkout_dir():
    out = _run_py("""
        import jax
        from repro.compile_cache import enable_compilation_cache
        print("DIR=" + enable_compilation_cache())
        print("CFG=" + str(jax.config.jax_compilation_cache_dir))
    """)
    assert out.returncode == 0, out.stderr[-2000:]
    want = os.path.join(ROOT, "results", ".jax_cache")
    dirs = {line.split("=", 1)[1] for line in out.stdout.splitlines()
            if "=" in line}
    assert {os.path.realpath(d) for d in dirs} == {os.path.realpath(want)}


# ------------------------------------------------------- peak table
def test_chip_peaks_v5e_from_table():
    from repro.launch.mesh import TARGET_DEVICE_KIND, chip_peaks
    p = chip_peaks("TPU v5 lite")
    assert (p.flops_bf16, p.hbm_bw, p.ici_bw_per_link) == (197e12, 819e9,
                                                           50e9)
    assert chip_peaks(TARGET_DEVICE_KIND) is p


def test_chip_peaks_unknown_kind_raises():
    from repro.launch.mesh import chip_peaks
    with pytest.raises(KeyError, match="no published peaks"):
        chip_peaks("cpu")


def test_router_analytic_latency_uses_target_peaks():
    from repro.configs.registry import get_config
    from repro.launch.mesh import TARGET_DEVICE_KIND, chip_peaks
    from repro.serve.router import analytic_token_latency
    arch = "qwen3-0.6b"
    active = get_config(arch, "full").param_count(active_only=True)
    assert analytic_token_latency(arch) == pytest.approx(
        active * 2.0 / chip_peaks(TARGET_DEVICE_KIND).hbm_bw)


# ---------------------------------------------------- benchmark harness
_SUITE_MODULES = ("warmup", "fig2_pareto", "table8_production",
                  "table9_dispatch", "scenario_suite", "chaos_suite",
                  "fleet_suite", "fig4_spork_vs_mark", "fig5_sensitivity",
                  "fig6_worker_efficiency", "fig7_request_sizes",
                  "policy_tuning", "roofline")


@pytest.mark.parametrize("failing,rc", [(None, 0), ("fleet_suite", 1)])
def test_run_exit_code_reports_failed_suites(monkeypatch, tmp_path,
                                             capsys, failing, rc):
    import importlib

    from benchmarks import common, run
    monkeypatch.setattr(common, "SWEEP_JSON", str(tmp_path / "s.json"))
    for name in _SUITE_MODULES:
        mod = importlib.import_module(f"benchmarks.{name}")

        def fake(*a, _name=name, **k):
            if _name == failing:
                raise RuntimeError("suite blew up")
            return [{"ok": 1}]
        monkeypatch.setattr(mod, "run", fake)
    assert run.main() == rc
    out = capsys.readouterr().out
    assert (f"{failing},0,error=RuntimeError" in out) == (failing is not None)


def test_backend_meta_names_platform():
    from benchmarks.common import backend_meta
    meta = backend_meta()
    assert meta["platform"] == jax.devices()[0].platform
    assert meta["backend"] == "local" and meta["n_devices"] == 1


def _failed_proc(*_a, **_k):
    return types.SimpleNamespace(returncode=3, stdout="", stderr="boom")


def test_table9_mesh_probe_child_is_cpu_only_and_fails_loudly(monkeypatch):
    from benchmarks import table9_dispatch
    seen = {}

    def fake_run(cmd, env, **kw):
        seen["env"] = env
        return _failed_proc()
    monkeypatch.setattr(subprocess, "run", fake_run)
    with pytest.raises(RuntimeError, match="mesh probe failed"):
        table9_dispatch._probe_manycore_rows()
    assert seen["env"]["JAX_PLATFORMS"] == "cpu"
    assert "--xla_force_host_platform_device_count=8" in \
        seen["env"]["XLA_FLAGS"]


def test_mesh_manycore_child_is_cpu_only_and_fails_loudly(monkeypatch):
    from benchmarks import mesh_manycore
    seen = {}

    def fake_run(cmd, env, **kw):
        seen["env"] = env
        return _failed_proc()
    monkeypatch.setattr(mesh_manycore, "_measure", lambda *a, **k: [])
    monkeypatch.setattr(subprocess, "run", fake_run)
    with pytest.raises(RuntimeError, match="many-core probe failed"):
        mesh_manycore.run()
    assert seen["env"]["JAX_PLATFORMS"] == "cpu"


def test_roofline_collect_child_is_cpu_only_and_checked(monkeypatch,
                                                        tmp_path):
    from benchmarks import roofline
    calls = []

    def fake_run(cmd, env, check):
        calls.append((env, check))
        raise subprocess.CalledProcessError(3, cmd)
    monkeypatch.setattr(roofline, "DRYRUN_DIR", tmp_path)
    monkeypatch.setattr(subprocess, "run", fake_run)
    arch = next(iter(roofline.cells()))[0]
    with pytest.raises(subprocess.CalledProcessError):
        roofline.collect([arch])
    env, check = calls[0]
    assert env["JAX_PLATFORMS"] == "cpu" and check is True


def test_minplus_scaling_rows_name_the_device():
    from benchmarks.roofline import minplus_scaling
    (row,) = minplus_scaling(ns=(128,), reps=1)
    dev = jax.devices()[0]
    assert row["platform"] == dev.platform
    assert row["device_kind"] == dev.device_kind
    assert set(k for k in row if k.endswith("_us")) == {"dense_us",
                                                        "structured_us"}


def test_dp_has_no_kernel_transition():
    from repro.core.dp import TRANSITIONS, solve_dp
    from repro.core.workers import DEFAULT_FLEET
    assert TRANSITIONS == ("dense", "structured")
    with pytest.raises(ValueError, match="unknown transition"):
        solve_dp(np.full(8, 10.0), DEFAULT_FLEET, transition="kernel")


# ----------------------------------------------------- full-mode grids
def test_table9_full_grid_size():
    from benchmarks.table9_dispatch import CASES, _grid
    grid, horizon = _grid(fast=False)
    assert horizon == 3600
    assert [label for label, _ in grid] == [c[0] for c in CASES]
    assert all(len(apps) == 5 for _, apps in grid)


def test_fleet_full_grid_size():
    from benchmarks.fleet_suite import SCALES, grid
    from repro.policies import admission_policy_names
    g = grid(fast=False)
    assert (g.horizon_s, g.demand) == (180.0, 0.1)
    assert len(g.cells) == len(SCALES) * len(admission_policy_names())
    assert max(len(c.tenants) for c in g.cells) == 1024
    # ~102 workers of demand at 1024 tenants: wider than 32 + 64 slots
    assert (g.w_fpga, g.w_cpu) == (64, 128)


def test_table8_full_grid_size():
    from benchmarks.table8_production import SCHEDULERS, grid
    from repro.core.traces import TABLE7
    plain, tuned, order = grid(fast=False)
    n_apps = sum(n for src in ("azure", "alibaba")
                 for b, n in TABLE7[src].items() if b in ("short", "medium"))
    n_plain = sum(1 for _, _, kw in SCHEDULERS if not kw.get("tuned"))
    assert len(plain) == n_apps * n_plain and len(tuned) == n_apps
    assert {len(c.counts) for c in plain} == {7200}


# ------------------------------------------------------------ chip_smoke
def test_chip_smoke_refuses_a_host_without_tpu():
    out = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                         env={**os.environ, "JAX_PLATFORMS": "cpu"},
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    for line in out.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)


def _small_event_cells():
    from repro.core.workers import DEFAULT_FLEET
    from repro.sim.sweep import EventCell
    rng = np.random.default_rng(11)
    cells = []
    for label in ("azure-like(medium)", "other"):
        for disp in ("round_robin", "index_packing", "spork"):
            arr = np.sort(rng.uniform(0.0, 240.0, 300))
            cells.append(EventCell(disp, arr, 0.25, DEFAULT_FLEET,
                                   horizon_s=240.0, tag=(label, disp, 0)))
    return cells


def _small_fleet_cells():
    from repro.fleet import FleetCell
    from repro.policies import admission_policy_names
    from repro.workloads import tenant_population
    pop = tenant_population(16, horizon_s=60.0, mean_demand_workers=0.05,
                            seed=1)
    return [FleetCell(tenants=pop, admission=a, tag=(16, a))
            for a in admission_policy_names()]


def test_chip_smoke_des_phase_small(capsys):
    import chip_smoke
    chip_smoke.phase_des(chip_smoke.CompileClock(),
                         cells=_small_event_cells())
    out = capsys.readouterr().out
    assert "phase=des cells=6 arrivals=1800" in out
    assert out.count("des_oracle") == 3


def test_chip_smoke_fleet_phase_small(capsys):
    import chip_smoke
    chip_smoke.phase_fleet(chip_smoke.CompileClock(),
                           cells=_small_fleet_cells())
    out = capsys.readouterr().out
    assert "phase=fleet cells=3" in out and "fleet_oracle" in out


def test_chip_smoke_rate_phase_small(capsys):
    import chip_smoke
    from benchmarks.table8_production import grid
    plain = grid(fast=True, buckets=("short",), sources=("azure",))[0]
    chip_smoke.phase_rate(chip_smoke.CompileClock(), cells=plain)
    out = capsys.readouterr().out
    assert f"phase=rate cells={len(plain)}" in out
    assert out.count("rate_oracle") == len({c.policy for c in plain}) >= 3


def test_chip_smoke_check_meta_rejects_degraded_chunks():
    import chip_smoke
    res = types.SimpleNamespace(meta={
        "degraded_chunks": [0], "retried_dispatches": 0,
        "restored_chunks": 0, "checkpointed": False})
    with pytest.raises(chip_smoke.SmokeFailure, match="degraded"):
        chip_smoke.check_meta(res, "t")
    res.meta.update(degraded_chunks=[], retried_dispatches=2)
    with pytest.raises(chip_smoke.SmokeFailure, match="retried"):
        chip_smoke.check_meta(res, "t")


def test_chip_smoke_mesh_phase_on_four_cpu_devices():
    """The ``--chips 4`` phase, rehearsed on four fabricated CPU devices:
    mesh totals bit-identical to local, shards on four devices."""
    out = _run_py("""
        import sys
        sys.path.insert(0, "tests")
        import chip_smoke
        from test_device_path import _small_event_cells, _small_fleet_cells
        chip_smoke.phase_mesh(chip_smoke.CompileClock(), n_devices=4,
                              ev_cells=_small_event_cells() * 2,
                              fl_cells=_small_fleet_cells())
    """, {"XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert out.returncode == 0, out.stderr[-3000:]
    assert "shard_devices=[0, 1, 2, 3]" in out.stdout
    assert "bit_identical=True" in out.stdout
