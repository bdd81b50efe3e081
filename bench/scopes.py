"""Device self time per program scope, and the program's own spans, in
the traced window of one run.

Every device op of the program carries an ``op_name`` under one of
three roots (DESIGN.md §10): ``enactor.*`` (the BSP loop, lane
freezing, tier and direction choice), ``op.*`` (operators and their
apply) and ``primitive.*`` (set-up and the result). An op counts under
the innermost such component of its name (its *leaf*), under the
innermost ``tier_<cap>`` component where it has one, and as ``mixed``
where it ran in BFS's mixed-direction step.

The op events come from the chip's "XLA Ops" line, as ``bench/trace.py``
reads them, and each op's name from the trace file itself: XProf's
``tf_op`` stat on the op's event metadata, or, for an op that has none
(a copy, a fusion whose root the compiler made), the compiled module
that the trace holds (``/host:metadata``, "Hlo Proto"), where such an op
counts under the nearest op upstream of it that has a name, else under
its nearest named user. The
program's host spans (``serve.mixed``, ``serve.flush``, ...) come from
the host plane with their args.

    python3 -m bench.scopes bench/.out/trace/<cell>

prints a trace's breakdown by scope, by step (direction, rung, scope)
in the window and in each unit, the serving flushes by kind, and the
idle gaps by program span.
"""
from __future__ import annotations

import glob
import json
import os
import re
import sys
from typing import Optional

from bench.trace import OPS_LINE, _is_chip, self_times, union

ROOTS = ("enactor.", "op.", "primitive.")
TIER = re.compile(r"tier_(\d+)$")
MIXED = "mixed"
PROGRAM_SPAN = re.compile(r"^(serve|graph|bench)\.")
NONE = "(no scope)"


# -- the trace file's own metadata (protobuf wire format) -------------------

def _varint(b: bytes, i: int) -> tuple:
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return out, i


def _fields(b: bytes, i: int = 0, end: Optional[int] = None):
    """``(field, value)`` of one message: an int, or ``(start, end)`` of
    a length-delimited value."""
    end = len(b) if end is None else end
    while i < end:
        key, i = _varint(b, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(b, i)
        elif wire == 2:
            n, i = _varint(b, i)
            value, i = (i, i + n), i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            value, i = int.from_bytes(b[i:i + n], "little"), i + n
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield field, value


def _text(b: bytes, span: tuple) -> str:
    return b[span[0]:span[1]].decode("utf-8", "replace")


def _stats(b: bytes, msg: tuple, stat_names: dict) -> dict:
    """The XStats of one XEventMetadata: name -> str, int or bytes."""
    out = {}
    for f, v in _fields(b, *msg):
        if f != 5:
            continue
        name, value = None, None
        for sf, sv in _fields(b, *v):
            if sf == 1:
                name = stat_names.get(sv)
            elif sf in (3, 4):
                value = sv
            elif sf == 5:
                value = _text(b, sv)
            elif sf == 6:
                value = b[sv[0]:sv[1]]
            elif sf == 7:                        # an interned string
                value = stat_names.get(sv)
        if name is not None:
            out[name] = value
    return out


def xplane_metadata(b: bytes) -> tuple:
    """From a serialized XSpace: ``{event name: stats}`` of the chips'
    event metadata, and ``{program id: HloModuleProto bytes}``."""
    ops, modules = {}, {}
    for f, plane in _fields(b):
        if f != 1:
            continue
        name, entries, stat_names = None, [], {}
        for pf, pv in _fields(b, *plane):
            if pf == 2:
                name = _text(b, pv)
            elif pf == 4:
                entries.append(pv)
            elif pf == 5:
                for mf, mv in _fields(b, *pv):
                    if mf == 2:
                        sid, sname = None, None
                        for sf, sv in _fields(b, *mv):
                            if sf == 1:
                                sid = sv
                            elif sf == 2:
                                sname = _text(b, sv)
                        stat_names[sid] = sname
        if name is None or not (_is_chip(name) or name == "/host:metadata"):
            continue
        for entry in entries:
            meta = next((v for ef, v in _fields(b, *entry) if ef == 2), None)
            if meta is None:
                continue
            ev_name, ev_id = None, None
            for ef, ev in _fields(b, *meta):
                if ef == 1:
                    ev_id = ev
                elif ef == 2:
                    ev_name = _text(b, ev)
            st = _stats(b, meta, stat_names)
            if name == "/host:metadata":
                proto = st.get("Hlo Proto")
                if isinstance(proto, bytes):
                    # HloProto: its field 1 is the HloModuleProto
                    mod = next((v for hf, v in _fields(proto) if hf == 1),
                               None)
                    if mod is not None:
                        modules[ev_id] = proto[mod[0]:mod[1]]
            elif ev_name is not None:
                ops[ev_name] = st
    return ops, modules


# -- op names from a compiled module's HLO text -----------------------------

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*.*?\s([\w\-]+)\((.*)$")
_COMP = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s.*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def hlo_op_names(text: str):
    """A function from an instruction's name to its op_name in one
    module's HLO text. An instruction with no op_name of its own (a copy,
    a fusion whose root the compiler made) takes that of the nearest
    instruction upstream of it (in its fused computation first, where it
    is a fusion), else of its nearest user."""
    instrs, roots, comp = {}, {}, None
    for line in text.splitlines():
        m = _COMP.match(line)
        if m:
            comp = m.group(1)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name, rest = m.group(1), m.group(3)
        on = _OP_NAME.search(rest)
        calls = re.search(r"calls=%([\w.\-]+)", rest)
        args = rest.split(")", 1)[0]
        instrs[name] = (on.group(1) if on else None,
                        re.findall(r"%([\w.\-]+)", args),
                        calls.group(1) if calls else None)
        if line.lstrip().startswith("ROOT"):
            roots[comp] = name

    users = {}
    for name, (_, operands, _) in instrs.items():
        for o in operands:
            users.setdefault(o, []).append(name)

    def walk(name, step):
        queue, seen = [name], {name}
        while queue:                     # breadth first
            here = queue.pop(0)
            if here in instrs and instrs[here][0]:
                return instrs[here][0]
            for nxt in step(here):
                if nxt not in seen:
                    seen.add(nxt)
                    queue.append(nxt)
        return None

    def upstream(name):
        _, operands, calls = instrs.get(name, (None, [], None))
        return ([roots[calls]] if calls in roots else []) + operands

    def resolve(name):
        # a copy of loop state or a broadcast constant has nothing named
        # upstream: it counts under its nearest named user
        return (walk(name, upstream)
                or walk(name, lambda n: users.get(n, [])))

    return resolve


def _module_text(proto: bytes) -> Optional[str]:
    try:
        from jax._src.lib import xla_client
        return xla_client._xla.HloModule.from_serialized_hlo_module_proto(
            proto).to_string()
    except Exception:        # no parser in this jaxlib: names stay unknown
        return None


def op_name_map(b: bytes) -> dict:
    """``{op event name: op_name}`` for every op of the chips' traces."""
    ops, modules = xplane_metadata(b)
    texts = {}
    out = {}
    for ev_name, st in ops.items():
        name = st.get("tf_op") or None
        if name is None and st.get("program_id") in modules:
            pid = st["program_id"]
            if pid not in texts:
                text = _module_text(modules[pid])
                texts[pid] = hlo_op_names(text) if text else (lambda _: None)
            name = texts[pid](ev_name.split(" = ", 1)[0].lstrip("%"))
        out[ev_name] = name
    return out


# -- attribution ------------------------------------------------------------

def scope_of(op_name: Optional[str]) -> tuple:
    """``(leaf, tier, mixed)`` of an op_name: its innermost component
    under a root (``NONE`` where there is none), its innermost
    ``tier_<cap>`` (or None), and whether ``mixed`` encloses it."""
    parts = (op_name or "").split("/")
    leaf = next((p for p in reversed(parts) if p.startswith(ROOTS)), NONE)
    tier = next((p for p in reversed(parts) if TIER.match(p)), None)
    return leaf, tier, MIXED in parts


def root_of(leaf: str) -> str:
    return leaf.split(".", 1)[0] if leaf != NONE else NONE


def attribute(device: dict, names: dict, window: tuple) -> dict:
    """Self time (ns, averaged over the chips) in ``window`` by leaf, by
    root, and by step: ``"<mixed or -> <tier or -> <leaf>"``. ``device``
    maps each chip to its op events ``(event name, start, end)``;
    ``names`` maps an event name to its op_name."""
    w0, w1 = window
    out = {"leaf": {}, "root": {}, "step": {}, "busy": 0}
    n_chips = max(len(device), 1)
    for events in device.values():
        merged = union((max(a, w0), min(b, w1)) for _, a, b in events
                       if b > w0 and a < w1)
        out["busy"] += sum(b - a for a, b in merged) / n_chips
        for ev, t in self_times(events, w0, w1).items():
            leaf, tier, mixed = scope_of(names.get(ev))
            t = t / n_chips
            step = f"{'mixed' if mixed else '-'} {tier or '-'} {leaf}"
            for key, k in (("leaf", leaf), ("root", root_of(leaf)),
                           ("step", step)):
                out[key][k] = out[key].get(k, 0) + t
    return out


def program_gaps(device: dict, host: list, window: tuple,
                 top: int = 10) -> list:
    """The ``top`` longest idle gaps of the chips in ``window``, each
    named by the innermost program span (``serve.*``, ``graph.*``,
    ``bench.*``) open in its middle."""
    w0, w1 = window
    spans = [(n, a, b) for n, a, b, _ in host if PROGRAM_SPAN.match(n)]
    gaps = []
    for events in device.values():
        merged = union((max(a, w0), min(b, w1)) for _, a, b in events
                       if b > w0 and a < w1)
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])

    def owner(t):
        open_ = [(b - a, n) for n, a, b in spans if a <= t <= b]
        return min(open_)[1] if open_ else "(no program span)"

    return [[owner((a + b) / 2), (b - a) * 1e-9] for a, b in gaps[:top]]


def flush_wait_share(host: list, window: tuple) -> Optional[float]:
    """Σ over queries of (start of the ``serve.flush`` that carried it −
    start of its ``serve.mixed``) over Σ of (end of that flush − start of
    its ``serve.mixed``), in %: a flush carries its ``lanes`` queries and
    names its ``serve.mixed`` by ``mixed``."""
    w0, w1 = window
    mixed = {st.get("id"): a for n, a, b, st in host if n == "serve.mixed"}
    wait = total = 0
    for n, a, b, st in host:
        if n != "serve.flush" or not (w0 <= a and b <= w1):
            continue
        m0 = mixed.get(st.get("mixed"))
        if m0 is None:
            continue
        lanes = int(st.get("lanes", 0))
        wait += lanes * (a - m0)
        total += lanes * (b - m0)
    return 100.0 * wait / total if total else None


# -- one run ----------------------------------------------------------------

class Scopes:
    """What one trace says per scope: ``self_ns`` (``attribute`` over
    the window), the host spans ``(name, start, end, args)``, the
    window, the idle gaps, and the op events with their names."""

    def __init__(self, self_ns: dict, host: list, window: tuple,
                 gaps: list, device: Optional[dict] = None,
                 names: Optional[dict] = None):
        self.self_ns = self_ns
        self.host = host
        self.window = window
        self.gaps = gaps
        self.device = device or {}
        self.names = names or {}

    def ms(self, root: Optional[str] = None,
           leaf: Optional[str] = None) -> float:
        """Self time (ms) under a root or a leaf."""
        table = self.self_ns["leaf" if leaf else "root"]
        return table.get(leaf or root, 0) * 1e-6

    @property
    def scoped(self) -> bool:
        """True where any op carries a scope (the program names them)."""
        return any(k != NONE and v > 0
                   for k, v in self.self_ns["root"].items())

    def summary(self, top: int = 12) -> dict:
        """The window's breakdown; per unit (each ``bench.*`` span that
        is not the window), its steps; per serving kind, its flushes."""
        busy = self.self_ns["busy"]
        units = [(a, b) for n, a, b, _ in self.host
                 if n.startswith("bench.") and not n.startswith(
                     "bench.runner")]
        return {"window_ms": (self.window[1] - self.window[0]) * 1e-6,
                "busy_ms": busy * 1e-6,
                "scoped_share": (100.0 * (1 - self.self_ns["root"].get(
                    NONE, 0) / busy) if busy else None),
                "self_ms": {k: _top_ms(t, None) for k, t in
                            self.self_ns.items() if k != "busy"},
                "units": [{"ms": (b - a) * 1e-6, "steps": _top_ms(
                    attribute(self.device, self.names, (a, b))["step"],
                    top)} for a, b in units],
                "flushes": flush_kinds(self.host, self.window),
                "flush_wait_share": flush_wait_share(self.host,
                                                     self.window),
                "idle_gaps": self.gaps}


def _top_ms(table: dict, top: Optional[int]) -> dict:
    rows = sorted(table.items(), key=lambda kv: -kv[1])
    return {k: v * 1e-6 for k, v in rows[:top]}


def flush_kinds(host: list, window: tuple) -> dict:
    """Per serving kind: its flushes in ``window``, their queries, their
    service time (flush spans, ms) and their queries' wait (ms summed
    over queries, from the start of their ``serve.mixed``)."""
    w0, w1 = window
    mixed = {st.get("id"): a for n, a, b, st in host if n == "serve.mixed"}
    out = {}
    for n, a, b, st in host:
        if n != "serve.flush" or not (w0 <= a and b <= w1):
            continue
        k = out.setdefault(st.get("kind"), {"flushes": 0, "queries": 0,
                                            "service_ms": 0.0,
                                            "wait_ms": 0.0})
        lanes = int(st.get("lanes", 0))
        k["flushes"] += 1
        k["queries"] += lanes
        k["service_ms"] += (b - a) * 1e-6
        if st.get("mixed") in mixed:
            k["wait_ms"] += lanes * (a - mixed[st["mixed"]]) * 1e-6
    return out


def read_trace(path: str, window_name: str) -> Optional[Scopes]:
    """``None`` where the file holds no chip or no window span."""
    import jax

    with open(path, "rb") as f:
        raw = f.read()
    data = jax.profiler.ProfileData.from_serialized_xspace(raw)
    device, host, window = {}, [], None
    for plane in data.planes:
        if _is_chip(plane.name):
            device[plane.name] = [
                (e.name, e.start_ns, e.start_ns + e.duration_ns)
                for line in plane.lines if line.name == OPS_LINE
                for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    a, b = e.start_ns, e.start_ns + e.duration_ns
                    if e.name == window_name and window is None:
                        window = (a, b)
                    elif PROGRAM_SPAN.match(e.name):
                        host.append((e.name, a, b, dict(e.stats)))
    if not device or window is None:
        return None
    names = op_name_map(raw)
    return Scopes(attribute(device, names, window), host, window,
                  program_gaps(device, host, window), device, names)


def trace_file(trace_dir: str) -> Optional[str]:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def scopes(run) -> Optional[Scopes]:
    """The run's ``Scopes``, read once and kept on the run; ``None`` for
    an untraced run or a trace without the window."""
    if "_bench_scopes" not in run.__dict__:
        from bench.harness import OUT, TRACE_WINDOW
        found = None
        if run.trace:
            path = trace_file(os.path.join(OUT, "trace", run.spec.name))
            found = path and read_trace(path, TRACE_WINDOW)
        run.__dict__["_bench_scopes"] = found or None
    return run.__dict__["_bench_scopes"]


def per_unit_ms(run, root: Optional[str] = None, leaf: Optional[str] = None,
                count: str = "iterations") -> Optional[float]:
    """Self time (ms) under ``root`` or ``leaf`` in the window over the
    sum of ``count`` over the window's units; ``None`` where the trace or
    the scopes are missing."""
    sc = scopes(run)
    n = sum(it.get(count) or 0 for it in run.items)
    if sc is None or not sc.scoped or not n:
        return None
    return sc.ms(root, leaf) / n


if __name__ == "__main__":
    from bench.harness import TRACE_WINDOW
    sc = read_trace(trace_file(sys.argv[1]), TRACE_WINDOW)
    print(json.dumps(None if sc is None else sc.summary(), indent=1))
