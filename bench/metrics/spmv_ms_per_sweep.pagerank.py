"""Milliseconds of device time per PageRank sweep in the pull SpMV: the
device self time of the ops under the ``op.spmv`` scope in the traced
window, over the sweeps of the window's runs."""
from bench.scopes import per_unit_ms


def read(run):
    return per_unit_ms(run, leaf="op.spmv", count="sweeps")
