"""Milliseconds of device time per BSP iteration spent in the enactor:
the device self time of the ops under the ``enactor.*`` scopes
(``enactor.loop``, ``enactor.select_lanes``, ``enactor.tier``,
``enactor.direction``) in the traced window, over the iterations of the
window's batches, a batch counting the most iterations any of its lanes
took (the count ``bsp_iter_ms`` divides by)."""
from bench.scopes import per_unit_ms


def read(run):
    return per_unit_ms(run, root="enactor")
