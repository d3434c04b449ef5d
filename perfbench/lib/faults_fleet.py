"""Faults planted in the fleet path (`repro.fleet.engine`), to show that
the check rejects them. The fleet engine binds the DES step it calls by
name, so each fault of `perfbench.lib.faults` has a twin here that
patches what the fleet path runs; ``admission_skipped`` is its own:

* ``stale_state``: the arrival step returns its state unchanged;
* ``half_batch``: every other slot of each arrival block is left out
  (turned into padding) before the engine runs;
* ``answer_altered``: the engine's cost output is 10% high for every
  cell;
* ``admission_skipped``: every arrival is admitted, whatever the cell's
  admission policy.

Each patches the program in this process only (tests pass pytest's
``monkeypatch.setattr``, so the patch is undone).
"""

from __future__ import annotations

import functools

FAULTS = ("stale_state", "half_batch", "answer_altered",
          "admission_skipped")


def plant(name: str, setattr_=setattr) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.fleet import engine
    from repro.sim import exec as sim_exec

    if name == "stale_state":
        def stale(es, code, w_f, is_f, idxW, c, t):
            return c
        setattr_(engine, "_arrival_step", stale)
    elif name == "half_batch":
        orig = sim_exec._fleet_args

        def halved(d):
            times = np.array(d.arrays["times"])
            times[..., 1::2] = np.inf
            arrays = dict(d.arrays, times=times)
            return orig(type(d)(d.kind, d.static, arrays, d.cell_idx,
                                d.chunk))
        setattr_(sim_exec, "_fleet_args", halved)
    elif name == "answer_altered":
        orig = engine._simulate_fleet_cells

        @functools.wraps(orig)
        def altered(*a, **k):
            out = orig(*a, **k)
            acc = out[0]
            return (acc._replace(cost=acc.cost * jnp.float32(1.1)),
                    *out[1:])
        setattr_(engine, "_simulate_fleet_cells", altered)
    elif name == "admission_skipped":
        def admit_all(code, t, tok, last, cnt, rate, burst, quota, xp):
            return xp.asarray(True), tok, last, cnt
        setattr_(engine, "admission_decide", admit_all)
    else:
        raise ValueError(f"unknown fault {name!r} (known: {FAULTS})")
    jax.clear_caches()
