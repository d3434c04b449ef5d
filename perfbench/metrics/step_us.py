"""Engine scan (the engine's jitted programs, such as
`events_batched._simulate_cells`): device time of the engine programs in
the traced window over the serial arrival steps their dispatches ran
(entries x block width per dispatch), in microseconds."""


def read(rec):
    tr = rec.trace
    steps = sum(entries * block for g in rec.grids
                for _, entries, block in g["shapes"])
    if not tr or not tr["engine_s"] or not steps:
        return None
    return 1e6 * tr["engine_s"] / steps
