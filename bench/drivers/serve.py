"""Batch-synchronous chunks of queries through the serving engine
(``serve_mixed``).

The benchmark hands the engine a chunk of ``chunk`` queries at once and
hands over the next chunk when every query of this one is answered: a
batch-synchronous mix, not independent clients (an open-loop arrival
schedule needs one in ``serve_mixed``). A query's latency runs from the
moment its chunk was handed over to the end of the flush that answered
it, so it counts the wait behind earlier flushes in the chunk.

Traffic parameters: ``chunk``, ``batch`` (the engine's slots per
flush), ``hops`` (reach), ``ldbc`` (the LDBC SNB Interactive complex
reads the mix stands for: each read's engine kind and its published
frequency, one read per that many updates, so that a kind's share of a
chunk is the sum of its reads' ``1 / freq`` over the total; every chunk
holds the same counts, rounded by largest remainder), ``cycle`` and
``check_per_kind``, the number of answered queries of each kind, drawn
from the seed, compared with the reference. Sources are uniform over
the dataset's vertices of degree >= 1: LDBC curates its parameters
rather than drawing them from a law, so no public skew applies.

The chunks are ``cycle`` chunks of the dataset, each with its kinds in
an order and its sources drawn with the dataset's ``graph_seed`` and
relabelled by the run's seed, so that every chunk's flushes hold the
same queries in every run. The window sends whole cycles, each in a
chunk order drawn from the seed: every run does the same work in
another order.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

import numpy as np

from bench.graphdata import rng_for
from bench.harness import Check
from bench.kinds import KINDS, mismatches, rel_gap


def ldbc_shares(ldbc: dict) -> dict:
    """Each kind's share of the mix: its reads' ``1 / freq`` over the
    sum of all reads' (LDBC's frequency is one read per ``freq``
    updates)."""
    rate = {}
    for read in ldbc.values():
        rate[read["kind"]] = rate.get(read["kind"], 0.0) + 1.0 / float(
            read["freq"])
    total = sum(rate.values())
    return {k: v / total for k, v in rate.items()}


def chunk_counts(mix: dict, size: int) -> dict:
    """Queries of each kind in a chunk: the shares times ``size``,
    rounded by largest remainder so that they add up."""
    want = {k: float(v) * size for k, v in mix.items()}
    counts = {k: int(np.floor(v)) for k, v in want.items()}
    rest = size - sum(counts.values())
    for k in sorted(want, key=lambda k: counts[k] - want[k])[:rest]:
        counts[k] += 1
    return counts


CYCLES = 64     # more than any window holds


@dataclass
class State:
    g: object
    traffic: dict
    backend: str
    hops: int
    kinds: list
    chunks: list                                  # the dataset's chunks
    order: np.ndarray                             # chunk of each unit
    answers: list = field(default_factory=list)   # (kind, src, field, lane)


def dataset_chunks(cfg: dict, sources: np.ndarray, traffic: dict) -> list:
    """The ``cycle`` chunks of the dataset, as (kind, source) pairs in
    the dataset's own vertex ids (``sources``: its vertices of degree
    >= 1, ascending)."""
    counts = chunk_counts(ldbc_shares(traffic["ldbc"]),
                          int(traffic["chunk"]))
    kinds = [k for k in sorted(counts) for _ in range(counts[k])]
    gs = cfg["graph_seed"]
    out = []
    for c in range(int(traffic["cycle"])):
        rng = rng_for(gs, 100 + c)
        ks = rng.permutation(kinds)
        srcs = rng.choice(sources, size=len(ks))
        out.append([(str(k), int(s)) for k, s in zip(ks, srcs)])
    return out


def prepare(run, g, traffic: dict) -> State:
    from repro.core import backend as B
    gd = run.graph
    own = gd.sources() if gd.perm is None else np.sort(
        np.argsort(gd.perm)[gd.sources()])
    chunks = [[(k, int(gd.relabel([s])[0])) for k, s in c]
              for c in dataset_chunks(gd.cfg, own, traffic)]
    rng = rng_for(run.seed, 1)
    order = np.concatenate([rng.permutation(len(chunks))
                            for _ in range(CYCLES)])
    return State(g=g, traffic=traffic, backend=B.resolve(None),
                 hops=int(traffic["hops"]),
                 kinds=sorted({r["kind"] for r in traffic["ldbc"].values()}),
                 chunks=chunks, order=order)


def cycle(st: State) -> int:
    """Units (chunks) in one pass over the dataset's chunks."""
    return len(st.chunks)


def chunk(st: State, i: int) -> list:
    """The chunk that unit ``i`` of the window sends."""
    return st.chunks[st.order[i % len(st.order)]]


def _serve(run, st: State, queries: list) -> tuple:
    """One chunk through ``serve_mixed``, with a runner that times each
    flush's program call and keeps its answer, and a watchdog that
    stamps each flush's end."""
    from repro import ft
    from repro.launch import graph_serve

    calls, flushes = [], []

    def runner(kind, srcs, backend, hops):
        import jax
        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation(f"bench.runner.{kind}"):
            field_, ovf, conv = graph_serve._run_kind(st.g, kind, srcs,
                                                      backend, hops)
            # the fence; the engine makes the host copy itself
            jax.block_until_ready(field_)
        calls.append((kind, np.asarray(srcs), field_, t0,
                      time.monotonic()))
        return field_, ovf, conv

    class Stamp(ft.StepWatchdog):
        def start(self, step):
            super().start(step)
            self._began = time.monotonic()

        def stop(self):
            dt = super().stop()
            flushes.append((self._began, time.monotonic()))
            return dt

    stats = graph_serve.serve_mixed(
        st.g, queries, int(st.traffic["batch"]), st.backend, hops=st.hops,
        runner=runner, watchdog=Stamp())
    return stats, calls, flushes


def warm(run, st: State) -> None:
    """One query of each kind, from a vertex of degree 0 where the graph
    has one: every kind's program runs once, with next to no work."""
    src = int(run.graph.warm_sources(1)[0])
    _serve(run, st, [(k, src) for k in st.kinds])


def step(run, st: State, i: int) -> None:
    queries = chunk(st, i)
    t_hand = time.monotonic()
    stats, calls, flushes = _serve(run, st, queries)
    t1 = time.monotonic()
    # each flush's answer is the last program call made inside it (a
    # retried flush calls the program more than once)
    answered = {}
    for f0, f1 in flushes:
        inside = [c for c in calls if f0 <= c[3] and c[4] <= f1]
        if inside:
            answered.setdefault(inside[-1][0], []).append((inside[-1], f1))
    # a kind's flushes take its queries in arrival order, batch by batch
    batch = int(st.traffic["batch"])
    order = {}
    for qid, (kind, _) in enumerate(queries):
        order.setdefault(kind, []).append(qid)
    done = {}
    for kind, fl in answered.items():
        for j, (call, f_end) in enumerate(fl):
            for lane, qid in enumerate(order[kind][j * batch:
                                                   (j + 1) * batch]):
                if int(call[1][lane]) == queries[qid][1]:
                    done[qid] = (call, lane, f_end)
    for qid, rec in enumerate(stats["queries"]):
        ok = rec["status"] == "ok" and qid in done
        lat = (done[qid][2] - t_hand) * 1e3 if ok else float("inf")
        run.queries.append({"kind": rec["kind"], "source": rec["source"],
                            "ok": ok, "lat_ms": lat})
        if ok:
            call, lane, _ = done[qid]
            st.answers.append((rec["kind"], rec["source"], call[2], lane))
    for c in calls:
        run.spans.append(("runner", c[3], c[4]))
    run.items.append({"t0": t_hand, "t1": t1, "queries": len(queries)})


def collect(run, st: State) -> None:
    """Keep the host copies of the answers (the engine made them) and
    free the device."""
    st.answers = [(k, src, np.asarray(f), lane)
                  for k, src, f, lane in st.answers]
    st.g = None


def check(run, st: State) -> list:
    """Compare a sample of the answered queries of each kind (a distance
    by its relative gap as well), and count the queries that were not
    answered ``ok``. The control puts each kind's control
    (``bench/kinds.py``) in the program's place."""
    csr = run.graph.csr()
    rng = rng_for(run.seed, 2)
    k = int(st.traffic["check_per_kind"])
    lim = st.traffic["limits"]
    params = {"hops": st.hops}
    bad_entries = bad_queries = 0
    gap = 0.0
    by_kind = {}
    for a in st.answers:
        by_kind.setdefault(a[0], []).append(a)
    for kind in sorted(by_kind):
        got = by_kind[kind]
        kd = KINDS[kind]
        for j in rng.permutation(len(got))[:k]:
            _, src, field_, lane = got[j]
            ref = kd.reference(csr, int(src), params)
            ans = (kd.control(csr, int(src), params) if run.control
                   else field_[lane])
            miss = mismatches(ans, ref)
            bad_entries += miss
            if not kd.exact:
                g = rel_gap(ans, ref)
                gap = max(gap, g)
                miss += g > lim["sssp_rel_err"]
            bad_queries += miss > 0
    not_ok = sum(not q["ok"] for q in run.queries)
    run.attempted = len(run.queries)
    run.failed = not_ok + bad_queries
    return [Check("answer_mismatches", bad_entries,
                  lim["answer_mismatches"]),
            Check("sssp_rel_err", gap, lim["sssp_rel_err"]),
            Check("queries_not_ok", not_ok, lim["queries_not_ok"])]
