"""Closed loop of batched traversals: one program call per batch of
``batch`` sources, the next batch sent when the last one is done.

Traffic parameters: ``primitive`` (a kind of ``bench/kinds.py``:
``bfs``, ``sssp`` or ``reach``), ``batch``, ``hops`` (reach only) and
``check_batches``, the number of the window's batches, drawn from the
seed, whose every lane is compared with the reference (a distance by
its relative gap, ``<kind>_rel_err``, as well).

Sources are the configuration's ``search_keys`` (Graph500's 64 keys of
the dataset, deepest first), relabelled by the seed and dealt
round-robin into batches, so that every batch holds the same spread of
depths. A cycle is one pass over the keys; the window runs whole cycles,
each in a batch and lane order drawn from the seed: every run does the
same work in another order.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

import numpy as np

from bench.graphdata import rng_for
from bench.harness import Check
from bench.kinds import KINDS, mismatches, rel_gap


@dataclass
class State:
    kind: object
    g: object
    batch: int
    params: dict
    stream: np.ndarray
    warm_srcs: np.ndarray
    answers: list = field(default_factory=list)
    host: dict = field(default_factory=dict)


CYCLES = 64     # more than any window holds


def prepare(run, g, traffic: dict) -> State:
    kind = KINDS[traffic["primitive"]]
    b = int(traffic["batch"])
    keys = run.graph.relabel(run.spec.config["search_keys"])
    if len(keys) % b:
        raise ValueError(f"{len(keys)} search keys do not fill batches "
                         f"of {b}")
    dealt = keys.reshape(-1, len(keys) // b).T      # batch j: keys[j::nb]
    rng = rng_for(run.seed, 1)
    stream = np.concatenate([
        rng.permuted(dealt[rng.permutation(len(dealt))], axis=1)
        for _ in range(CYCLES)]).astype(np.int32)
    return State(kind=kind, g=g, batch=b,
                 params={"hops": int(traffic.get("hops", 3))},
                 stream=stream.reshape(-1),
                 warm_srcs=run.graph.warm_sources(b))


def cycle(st: State) -> int:
    """Units (batches) in one pass over the search keys."""
    return len(st.stream) // st.batch // CYCLES


def warm(run, st: State) -> None:
    import jax
    jax.block_until_ready(st.kind.run(st.g, st.warm_srcs, st.params))


def step(run, st: State, i: int) -> None:
    import jax
    srcs = st.stream.take(np.arange(i * st.batch, (i + 1) * st.batch),
                          mode="wrap")
    t0 = time.monotonic()
    r = st.kind.run(st.g, srcs, st.params)
    jax.block_until_ready(r)
    iters = st.kind.iterations(r)
    iters = None if iters is None else int(np.max(np.asarray(iters)))
    t1 = time.monotonic()
    st.answers.append(st.kind.answer(r))
    run.items.append({"t0": t0, "t1": t1, "sources": srcs,
                      "iterations": iters})
    run.spans.append((f"{st.kind.name}_batch", t0, t1))


def collect(run, st: State) -> None:
    """Copy the sampled batches' answers to the host and free the rest."""
    k = min(len(st.answers), int(run.spec.traffic["check_batches"]))
    pick = rng_for(run.seed, 2).permutation(len(st.answers))[:k]
    st.host = {int(j): np.asarray(st.answers[j]) for j in pick}
    st.answers.clear()
    st.g = None


def check(run, st: State) -> list:
    """Compare every lane of the sampled batches; the control puts the
    kind's control (``bench/kinds.py``) in the program's place."""
    csr = run.graph.csr()
    lim = run.spec.traffic["limits"]
    gap_name = f"{st.kind.name}_rel_err"
    bad_entries = bad_lanes = 0
    gap = 0.0
    ref = {}
    for j, ans in sorted(st.host.items()):
        for lane, s in enumerate(run.items[j]["sources"]):
            s = int(s)
            if s not in ref:
                ref[s] = st.kind.reference(csr, s, st.params)
            got = (st.kind.control(csr, s, st.params) if run.control
                   else ans[lane])
            miss = mismatches(got, ref[s])
            bad_entries += miss
            if not st.kind.exact:
                g = rel_gap(got, ref[s])
                gap = max(gap, g)
                miss += g > lim[gap_name]
            bad_lanes += miss > 0
    run.attempted = len(run.items) * st.batch
    run.failed = bad_lanes
    name = f"{st.kind.name}_mismatches"
    checks = [Check(name, bad_entries, lim[name])]
    if not st.kind.exact:
        checks.append(Check(gap_name, gap, lim[gap_name]))
    return checks
