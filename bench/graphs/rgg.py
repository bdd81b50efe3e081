"""Random geometric graph (DIMACS10 ``rgg_n_2_k``): ``n`` points drawn
uniformly in the unit square, an edge between every two points at
Euclidean distance at most ``radius_factor * sqrt(ln n / n)``.

Vectorized over a grid of cells no smaller than the radius: each point
is compared with the points of its own cell (higher ids only) and of
four of its eight neighbour cells, so every pair is examined once."""
from __future__ import annotations

import math

import numpy as np


def radius(cfg: dict) -> float:
    n = int(cfg["n"])
    return float(cfg["radius_factor"]) * math.sqrt(math.log(n) / n)


def edges(cfg: dict, rng: np.random.Generator):
    n = int(cfg["n"])
    r = radius(cfg)
    pts = rng.random((n, 2))
    side = max(int(1.0 / r), 1)             # cells per side, each >= r wide
    cx = np.minimum((pts[:, 0] * side).astype(np.int64), side - 1)
    cy = np.minimum((pts[:, 1] * side).astype(np.int64), side - 1)
    order = np.argsort(cx * side + cy, kind="stable")
    starts = np.searchsorted((cx * side + cy)[order],
                             np.arange(side * side + 1))
    src, dst = [], []
    for dx, dy in ((0, 0), (0, 1), (1, -1), (1, 0), (1, 1)):
        nx, ny = cx + dx, cy + dy
        i = np.nonzero((nx < side) & (ny >= 0) & (ny < side))[0]
        cell = nx[i] * side + ny[i]
        lo, cnt = starts[cell], starts[cell + 1] - starts[cell]
        before = np.cumsum(cnt) - cnt
        pos = np.repeat(lo - before, cnt) + np.arange(int(cnt.sum()))
        a, b = np.repeat(i, cnt), order[pos]
        if dx == 0 and dy == 0:
            a, b = a[b > a], b[b > a]
        close = ((pts[a] - pts[b]) ** 2).sum(axis=1) <= r * r
        src.append(a[close])
        dst.append(b[close])
    return np.concatenate(src), np.concatenate(dst), n
