"""Closed loop of whole-graph PageRank runs, back to back.

Traffic parameters: ``damping``, ``sweeps`` (a fixed number: the
tolerance is 0, as LDBC Graphalytics runs PageRank), ``precision`` (as
the configuration states it; the control runs the program's own
``bf16`` path) and ``check_runs``, the number of the window's runs,
drawn from the seed, whose ranks are compared with the reference.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

import numpy as np

from bench import reference as R
from bench.graphdata import rng_for
from bench.harness import Check


# the control is the program's own bf16 sweep: a run of its own
CONTROL_IS_A_PROGRAM_PATH = True


@dataclass
class State:
    g: object
    params: dict
    ranks: list = field(default_factory=list)
    host: dict = field(default_factory=dict)


def prepare(run, g, traffic: dict) -> State:
    params = {"damping": float(traffic["damping"]),
              "max_iter": int(traffic["sweeps"]), "tol": 0.0,
              "precision": "bf16" if run.control else traffic["precision"]}
    return State(g=g, params=params)


def _pagerank(st: State):
    from repro.core.primitives import pagerank
    return pagerank(st.g, **st.params)


def cycle(st: State) -> int:
    """Every run does the same work: a cycle is one run."""
    return 1


def warm(run, st: State) -> None:
    import jax
    jax.block_until_ready(_pagerank(st))


def step(run, st: State, i: int) -> None:
    import jax
    t0 = time.monotonic()
    r = _pagerank(st)
    jax.block_until_ready(r)
    t1 = time.monotonic()
    st.ranks.append(r.rank)
    run.items.append({"t0": t0, "t1": t1,
                      "sweeps": st.params["max_iter"]})
    run.spans.append(("pagerank", t0, t1))


def collect(run, st: State) -> None:
    k = min(len(st.ranks), int(run.spec.traffic["check_runs"]))
    pick = rng_for(run.seed, 2).permutation(len(st.ranks))[:k]
    st.host = {int(j): np.asarray(st.ranks[j]) for j in pick}
    st.ranks.clear()
    st.g = None


def check(run, st: State) -> list:
    """The widest gap between a rank and the reference's, in units of
    the mean rank ``1/n``."""
    ref = R.pagerank(run.graph.csr(), st.params["damping"],
                     st.params["max_iter"])
    n = run.graph.n
    errs = [float(np.max(np.abs(r.astype(np.float64) - ref)) * n)
            for r in st.host.values()]
    run.attempted = len(run.items)
    limit = run.spec.traffic["limits"]["rank_err"]
    run.failed = sum(e > limit for e in errs)
    return [Check("rank_err", max(errs), limit)]
