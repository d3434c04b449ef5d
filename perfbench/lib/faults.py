"""Faults planted in the program, to show that the check rejects them.

Each patches the program in this process only (tests pass pytest's
``monkeypatch.setattr`` so the patch is undone):

* ``stale_state``: the arrival step returns its state unchanged;
* ``half_batch``: every other slot of each arrival block is left out
  (turned into padding) before the engine runs;
* ``answer_altered``: the engine's cost output is 10% high for every
  cell, where the engine produces it.

A cell on one chip has no exchange between chips to leave out.
"""

from __future__ import annotations

import functools

FAULTS = ("stale_state", "half_batch", "answer_altered")


def plant(name: str, setattr_=setattr) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.sim import events_batched, exec as sim_exec

    if name == "stale_state":
        def stale(es, code, w_f, is_f, idxW, c, t):
            return c
        setattr_(events_batched, "_arrival_step", stale)
    elif name == "half_batch":
        orig = sim_exec._event_args

        def halved(d):
            times = np.array(d.arrays["times"])
            times[..., 1::2] = np.inf
            arrays = dict(d.arrays, times=times)
            return orig(type(d)(d.kind, d.static, arrays, d.cell_idx,
                                d.chunk))
        setattr_(sim_exec, "_event_args", halved)
    elif name == "answer_altered":
        orig = events_batched._simulate_cells

        @functools.wraps(orig)
        def altered(*a, **k):
            out = orig(*a, **k)
            acc = out[0]
            return (acc._replace(cost=acc.cost * jnp.float32(1.1)),
                    *out[1:])
        setattr_(events_batched, "_simulate_cells", altered)
    else:
        raise ValueError(f"unknown fault {name!r} (known: {FAULTS})")
    jax.clear_caches()
