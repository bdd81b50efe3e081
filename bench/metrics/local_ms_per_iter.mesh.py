"""Milliseconds of device time per BSP iteration spent in each chip's
own work on a mesh: the device self time of the ops under the ``op.*``
scopes other than ``op.exchange`` (the 2-D local expansion, its filter
and the label update), averaged over the chips, over the iterations of
the window's batches, a batch counting the most iterations any of its
lanes took."""
from bench.scopes import per_unit_ms


def read(run):
    ops = per_unit_ms(run, root="op")
    if ops is None:
        return None
    return ops - per_unit_ms(run, leaf="op.exchange")
