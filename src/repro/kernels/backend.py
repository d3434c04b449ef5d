"""Pallas execution mode, decided by the platform.

Every kernel in `repro.kernels` runs in one of two modes:

  * **compiled** — lowered by Mosaic for the TPU (``interpret=False``).
    A kernel that fails to lower raises; nothing falls back.
  * **interpret** — ``pallas_call(..., interpret=True)``: the kernel
    body is traced as ordinary JAX ops with refs emulated, so the same
    semantics run on the CPU backend the tests use.

`use_interpret` is the one place that decides: compiled on a TPU,
interpret everywhere else. There is no probe and no override.
"""

from __future__ import annotations

import jax


def use_interpret() -> bool:
    """True unless JAX's default backend is a TPU, where every kernel
    compiles through Mosaic."""
    return jax.default_backend() != "tpu"
