"""The PRNG bits of the stand-in streams, pinned.

JAX 0.9 turned ``jax_threefry_partitionable`` on by default, which
changed the bits of every `jax.random.split` and draw. The goldens
(tests/goldens, tests/test_traces.py) and every recorded stand-in result
predate that, so each stand-in draw (`repro.core.bmodel`,
`repro.workloads.generators`, `repro.workloads.scenarios.realize`) runs
under the old setting, whatever the caller's. The setting is read when a
draw is traced, so a jitted synthesis is pinned at its outermost call.
It lives in `repro.core` because `repro.workloads` imports
`repro.core.bmodel`.
"""

from __future__ import annotations

import functools

import jax


def pinned_stream(draw):
    """Run ``draw`` with ``jax_threefry_partitionable`` off."""
    @functools.wraps(draw)
    def pinned(*args, **kwargs):
        with jax.threefry_partitionable(False):
            return draw(*args, **kwargs)
    return pinned
