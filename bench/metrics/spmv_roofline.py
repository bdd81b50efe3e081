"""Share (%) of the bandwidth roofline that the PageRank program reached
in the traced window: the least time its pull SpMV sweeps need at the
chip's peak HBM bandwidth (``bench/costs.py``), over the device's busy
time in the profiler trace."""
from bench.costs import pull_spmv_sweep_bytes


def read(run):
    tr = run.trace_summary
    if tr is None or not tr["busy_s"] or not run.peaks:
        return None
    sweeps = sum(it.get("sweeps", 0) for it in run.items)
    if not sweeps:
        return None
    g = run.graph
    least = (sweeps * pull_spmv_sweep_bytes(g.n, g.stored_edges)
             / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / tr["busy_s"]
