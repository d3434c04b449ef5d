"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state; the dry-run fabricates its 512 placeholder
CPU devices through the ``jax_num_cpu_devices`` config before first use.

Target hardware: TPU v5e pods — 256 chips/pod as a (16, 16) (data, model)
mesh; the multi-pod configuration stacks a leading 'pod' axis (2 pods =
512 chips). The 'pod' axis defaults to outer data parallelism; the
pipeline module can claim it for pipeline stages instead.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh():
    """Degenerate 1-device mesh for smoke tests of mesh-aware code paths."""
    return jax.make_mesh((1, 1), ("data", "model"))


def make_cell_mesh(devices=None):
    """1-D ``('cells',)`` mesh for sharding sweep-cell batches
    (`repro.sim.exec.MeshBackend`): the leading cell axis of a sweep
    chunk is split across ``devices`` (default: all local devices).
    Fabricate CPU devices with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` or the
    ``jax_num_cpu_devices`` config."""
    import numpy as np
    from jax.sharding import Mesh
    devs = list(devices) if devices is not None else jax.devices()
    return Mesh(np.array(devs), ("cells",))


@dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peak rates."""

    flops_bf16: float            # FLOP/s
    hbm_bw: float                # bytes/s
    ici_bw_per_link: float       # bytes/s per inter-chip link


#: Peaks keyed by ``jax.Device.device_kind``. Source: Google Cloud TPU
#: documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM, 1,600
#: Gbit/s chip-to-chip interconnect over 4 links (50 GB/s per link).
CHIP_PEAKS = {
    "TPU v5 lite": ChipPeaks(flops_bf16=197e12, hbm_bw=819e9,
                             ici_bw_per_link=50e9),
}

#: ``device_kind`` of the production pods the dry-run meshes model.
TARGET_DEVICE_KIND = "TPU v5 lite"


def chip_peaks(device_kind: str) -> ChipPeaks:
    """Peaks of one chip of ``device_kind``; a kind missing from
    `CHIP_PEAKS` is an error, never a default."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} (known: {sorted(CHIP_PEAKS)})"
                       ) from None
