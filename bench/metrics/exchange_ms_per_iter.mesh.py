"""Milliseconds of device time per BSP iteration in the mesh exchange:
the device self time of the ops under ``op.exchange`` (the row psum of
the column-chunk masks and the column all-gather), averaged over the
chips, over the iterations of the window's batches. Compute that runs
alongside a collective is not in it; the wait for the slowest block
is."""
from bench.scopes import per_unit_ms


def read(run):
    return per_unit_ms(run, leaf="op.exchange")
