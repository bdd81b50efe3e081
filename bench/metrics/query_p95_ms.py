"""The 95th percentile of every query's latency in the window, from the
moment its chunk was handed to the engine to the end of the flush that
answered it. A query not answered ``ok`` counts as slower than any limit."""
from bench.stats import quantile


def read(run):
    if not run.queries:
        return None
    return quantile([q["lat_ms"] for q in run.queries], 0.95)
