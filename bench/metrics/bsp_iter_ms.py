"""Milliseconds per BSP iteration of the enactor: the fenced wall time
of the window's batches over their iterations, a batch counting the
most iterations any of its lanes took (``BFSResult.iterations``)."""


def read(run):
    timed = [it for it in run.items if it.get("iterations")]
    if not timed:
        return None
    busy = sum(it["t1"] - it["t0"] for it in timed)
    return 1e3 * busy / sum(it["iterations"] for it in timed)
