"""Host planning (`repro.sim.plan`): real arrivals over the arrival
slots the plan's dispatches scan (chunk x entries x block width), in
percent. A count from the plan's shapes."""


def slots(shapes) -> int:
    return sum(chunk * entries * block for chunk, entries, block in shapes)


def read(rec):
    grids = [g for g in rec.grids if g["shapes"]]
    total = sum(slots(g["shapes"]) for g in grids)
    if not total:
        return None
    return 100.0 * sum(g["arrivals"] for g in grids) / total
