"""End to end: seconds from process start to the window's open (JAX
start, compile cache, streams, the warm-up grid, the window's grids
realized)."""


def read(rec):
    return rec.setup_s
