"""What the benchmark knows of each query kind: how the program answers
a batch of sources, which field of its result is the answer, the
reference answer of one source it is compared with, the control put in
the program's place to show that the comparison fails, and how an
answer is compared."""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable

import numpy as np

from . import reference as R


@dataclass(frozen=True)
class Kind:
    name: str
    run: Callable          # (graph, srcs, params) -> program result
    answer: Callable       # result -> (B, n) device array
    iterations: Callable   # result -> (B,) BSP iterations, or None
    reference: Callable    # (csr, src, params) -> (n,) numpy answer
    control: Callable      # (csr, src, params) -> (n,) answer that fails
    exact: bool = True     # False: distances, compared by relative gap


def _bfs_run(g, srcs, p):
    from repro.core.primitives import bfs_batch
    return bfs_batch(g, srcs, budget=p.get("budget"))


def _sssp_run(g, srcs, p):
    from repro.core.primitives import sssp_batch
    return sssp_batch(g, srcs, budget=p.get("budget"))


def _reach_run(g, srcs, p):
    from repro.core.primitives import reach_batch
    return reach_batch(g, srcs, int(p.get("hops", 3)))


def _bfs_short(c, s, p):
    """The reference's depths with the farthest level left out: the
    least that a traversal stopped one level early gets wrong."""
    out = R.bfs(c, s)
    out[out == out.max()] = -1
    return out


def _sssp_bf16(c, s, p):
    """The reference in the nearest precision below the configuration's
    float32: every weight and every sum rounded to bfloat16."""
    import ml_dtypes
    return R.sssp(c, s, rounding=ml_dtypes.bfloat16)


KINDS = {
    "bfs": Kind("bfs", _bfs_run, lambda r: r.labels,
                lambda r: r.iterations, lambda c, s, p: R.bfs(c, s),
                _bfs_short),
    "sssp": Kind("sssp", _sssp_run, lambda r: r.dist,
                 lambda r: r.iterations, lambda c, s, p: R.sssp(c, s),
                 _sssp_bf16, exact=False),
    "reach": Kind("reach", _reach_run, lambda r: r.reached,
                  lambda r: None,
                  lambda c, s, p: R.reach(c, s, int(p.get("hops", 3))),
                  lambda c, s, p: R.reach(c, s,
                                          int(p.get("hops", 3)) - 1)),
}


def mismatches(answer: np.ndarray, ref: np.ndarray) -> int:
    """Entries where the answer differs from the reference: every entry
    of an exact answer (depths, reach flags), and of a distance only
    whether it is reached (finite) or not."""
    a = np.asarray(answer)
    r = np.asarray(ref)
    if a.dtype.kind == "f":
        return int(np.count_nonzero(np.isfinite(a) != np.isfinite(r)))
    r = r.astype(bool) if a.dtype == bool else r.astype(a.dtype)
    return int(np.count_nonzero(a != r))


def rel_gap(answer: np.ndarray, ref: np.ndarray) -> float:
    """The widest relative gap ``|answer - ref| / ref`` over the entries
    that both reach; where the reference reads 0 (the source), any other
    answer is an infinite gap."""
    a = np.asarray(answer, np.float64)
    r = np.asarray(ref, np.float64)
    both = np.isfinite(a) & np.isfinite(r)
    a, r = a[both], r[both]
    diff = np.abs(a - r)
    with np.errstate(divide="ignore", invalid="ignore"):
        gap = np.where(r > 0, diff / np.where(r > 0, r, 1.0),
                       np.where(diff > 0, np.inf, 0.0))
    return float(gap.max()) if gap.size else 0.0
