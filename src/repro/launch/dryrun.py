"""Multi-pod dry-run: lower + compile every (arch x input-shape) cell on
the production meshes and record memory/cost/collective analyses.

`main` pins JAX to the CPU backend with 512 placeholder host devices
(``jax_platforms=cpu``, ``jax_num_cpu_devices=512``) before anything
touches a device, so jax.make_mesh can build the (2, 16, 16) production
mesh and the run never claims an accelerator. Nothing here allocates
real arrays — inputs are ShapeDtypeStructs (launch.specs) and
compilation is AOT.

Usage:
    python -m repro.launch.dryrun --arch qwen3-0.6b --shape train_4k \
        --mesh single
    python -m repro.launch.dryrun --all --mesh both --out results/dryrun

Per cell it records: compile success, memory_analysis (per-device bytes),
cost_analysis (flops / bytes accessed), and the collective-op byte census
parsed from the post-SPMD HLO (see roofline notes in EXPERIMENTS.md).
"""

import argparse
import json
import re
import time
import traceback
from pathlib import Path

import jax

from repro.configs.registry import SHAPES, cells, get_config
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import cell_lowerable

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def collective_census(hlo_text: str) -> dict:
    """Sum output bytes of every collective op in post-SPMD HLO.

    Shapes in partitioned HLO are per-device; ops inside while bodies are
    counted once (the roofline runner scales by trip count analytically).

    XLA-CPU legalizes bf16 dot operands to f32, so weight/activation
    gathers that would move bf16 on TPU show up as f32 here; the census
    tracks f32 bytes separately and reports `total_bytes_tpu` = bf16 +
    f32/2 as the TPU-dtype-corrected estimate (see EXPERIMENTS.md
    §Roofline methodology)."""
    out = {k: {"count": 0, "bytes": 0, "bytes_f32": 0} for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.strip()
        m = re.match(r"%?[\w.\-]+\s*=\s*(.*?)\s*(all-gather|all-reduce|"
                     r"reduce-scatter|all-to-all|collective-permute)", s)
        if not m:
            continue
        op = m.group(2)
        tensors = _SHAPE_RE.findall(m.group(1))
        nbytes = f32bytes = 0
        for dt, dims in tensors:
            if dt not in _DTYPE_BYTES:
                continue
            numel = 1
            for d in dims.split(","):
                if d:
                    numel *= int(d)
            nbytes += numel * _DTYPE_BYTES[dt]
            if dt == "f32":
                f32bytes += numel * 4
        out[op]["count"] += 1
        out[op]["bytes"] += nbytes
        out[op]["bytes_f32"] += f32bytes
    out["total_bytes"] = sum(v["bytes"] for k, v in out.items()
                             if isinstance(v, dict))
    f32_total = sum(v["bytes_f32"] for k, v in out.items()
                    if isinstance(v, dict))
    out["total_bytes_tpu"] = out["total_bytes"] - f32_total // 2
    return out


def run_cell(arch: str, shape: str, mesh_kind: str,
             n_layers_override=None, save_hlo: str | None = None) -> dict:
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    rec = {"arch": arch, "shape": shape, "mesh": mesh_kind,
           "devices": int(len(mesh.devices.reshape(-1))),
           "n_layers_override": n_layers_override}
    t0 = time.time()
    fn, args = cell_lowerable(arch, shape, mesh,
                              n_layers_override=n_layers_override)
    with mesh:
        lowered = jax.jit(fn).lower(*args)
        rec["lower_s"] = round(time.time() - t0, 1)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 1)
        mem = compiled.memory_analysis()
        if mem is not None:
            for f in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes"):
                rec[f] = int(getattr(mem, f, 0) or 0)
            rec["device_bytes_total"] = (rec.get("argument_size_in_bytes", 0)
                                         + rec.get("temp_size_in_bytes", 0))
        ca = compiled.cost_analysis()
        if ca:
            rec["hlo_flops"] = float(ca.get("flops", -1))
            rec["hlo_bytes"] = float(ca.get("bytes accessed", -1))
        hlo = compiled.as_text()
        rec["collectives"] = collective_census(hlo)
        if save_hlo:
            Path(save_hlo).write_text(hlo)
    rec["ok"] = True
    rec["total_s"] = round(time.time() - t0, 1)
    return rec


#: placeholder host devices: enough for the (2, 16, 16) multi-pod mesh
N_PLACEHOLDER_DEVICES = 512


def main() -> None:
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", N_PLACEHOLDER_DEVICES)
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--layers", type=int, default=None,
                    help="depth override for roofline lowerings")
    ap.add_argument("--unroll", action="store_true",
                    help="unroll model scans (exact cost analysis; use "
                         "with --layers)")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--save-hlo", default=None)
    args = ap.parse_args()

    if args.unroll:
        from repro.models import flags
        flags.SCAN_UNROLL = True

    outdir = Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    todo = (cells() if args.all else
            [(args.arch, args.shape, False)])

    failures = 0
    for arch, shape, _ in todo:
        for mk in meshes:
            tag = f"{arch}__{shape}__{mk}"
            if args.layers:
                tag += f"__L{args.layers}" + ("u" if args.unroll else "")
            path = outdir / f"{tag}.json"
            try:
                rec = run_cell(arch, shape, mk,
                               n_layers_override=args.layers,
                               save_hlo=args.save_hlo)
                print(f"[ok] {tag}: lower {rec['lower_s']}s "
                      f"compile {rec['compile_s']}s "
                      f"mem/dev {rec.get('device_bytes_total', 0) / 2**30:.2f} GiB "
                      f"coll {rec['collectives']['total_bytes'] / 2**20:.1f} MiB")
            except Exception as e:  # noqa: BLE001 — record and continue
                failures += 1
                rec = {"arch": arch, "shape": shape, "mesh": mk, "ok": False,
                       "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()}
                print(f"[FAIL] {tag}: {type(e).__name__}: {e}")
            path.write_text(json.dumps(rec, indent=2))
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
