"""Exec and harness (`repro.sim.exec`, `repro.sim.harness`): host
milliseconds per grid inside the executor call during which no program
ran on the device (transfer, dispatch, fetch, invariant guards), from the
profiler trace."""


def read(rec):
    tr = rec.trace
    if not tr or tr["exec_spans"] == 0:
        return None
    return 1e3 * tr["exec_idle_s"] / tr["exec_spans"]
