"""End to end: simulated arrivals of every grid completed in the window
over the time from the window's start to the return of the last grid.
One ratio over the whole window, not a statistic of per-grid rates; a
grid that failed counts its time and none of its arrivals."""


def read(rec):
    if not rec.grids:
        return None
    arrivals = sum(g["arrivals"] for g in rec.grids if g["ok"])
    return arrivals / max(rec.grids[-1]["end"] - rec.t_open, 1e-9)
