"""Host planning (`repro.sim.plan`): host milliseconds per grid inside
the planner call, from the benchmark's own span around it."""


def read(rec):
    spans = [g["plan_s"] for g in rec.grids if g.get("plan_s") is not None]
    return 1e3 * sum(spans) / len(spans) if spans else None
