"""Bytes that an algorithm has to move, counted from its sizes alone,
whatever implements it. These are lower bounds for a roofline share."""
from __future__ import annotations

INT32 = 4
FLOAT32 = 4


def pull_spmv_sweep_bytes(n: int, m: int) -> int:
    """One PageRank pull sweep over a CSC graph of ``n`` vertices and
    ``m`` stored edges: each edge's int32 row index read once, the
    ``n + 1`` int32 offsets, and per vertex its float32 inverse degree,
    its rank read and its new rank written. The contributions a sweep
    gathers per edge are not counted: a cache may serve them."""
    return INT32 * m + INT32 * (n + 1) + FLOAT32 * 3 * n
