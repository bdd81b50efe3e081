"""Reduction of one profiler trace (``.xplane.pb``) to device busy time,
the idle share, the device operations that took most time and the
longest idle gaps, each gap named by the innermost host span open in
its middle."""
from __future__ import annotations

from typing import Optional


TOP = 10
OPS_LINE = "XLA Ops"


def _is_chip(plane_name: str) -> bool:
    head, _, idx = plane_name.rpartition(":")
    return head == "/device:TPU" and idx.isdigit()


def union(intervals) -> list:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def op_name(hlo: str) -> str:
    """``%fusion.12 = f32[8]{0} fusion(...)`` -> ``fusion.12``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def self_times(events, w0: int, w1: int) -> dict:
    """Time of each op name inside ``[w0, w1]`` less the time of the ops
    nested in it (a ``while`` holds its body's ops on the same line)."""
    out = {}
    stack = []       # (end, name) of the enclosing events
    for name, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        a, b = max(a, w0), min(b, w1)
        if b <= a:
            continue
        while stack and stack[-1][0] <= a:
            stack.pop()
        if stack:
            parent = stack[-1][1]
            out[parent] = out.get(parent, 0) - (b - a)
        out[name] = out.get(name, 0) + (b - a)
        stack.append((b, name))
    return out


def reduce_events(device: dict, host: list, window: tuple,
                  top: int = TOP) -> dict:
    """``device`` maps each chip to its op events ``(name, start, end)``,
    ``host`` holds host spans ``(name, start, end)``, ``window`` is the
    traced window ``(start, end)``; times in nanoseconds."""
    w0, w1 = window
    busy, per_op, gaps = [], {}, []
    for events in device.values():
        merged = union((max(a, w0), min(b, w1)) for _, a, b in events
                       if b > w0 and a < w1)
        busy.append(sum(b - a for a, b in merged))
        for name, t in self_times(events, w0, w1).items():
            per_op[name] = per_op.get(name, 0) + t
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])

    def owner(t):
        open_ = [(b - a, name) for name, a, b in host if a <= t <= b]
        return min(open_)[1] if open_ else "(no host span)"

    n_chips = max(len(device), 1)
    return {
        "busy_s": sum(busy) / n_chips * 1e-9,
        "window_s": (w1 - w0) * 1e-9,
        "device_ops": [[k, v / n_chips * 1e-9] for k, v in
                       sorted(per_op.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[owner((a + b) / 2), (b - a) * 1e-9]
                      for a, b in gaps[:top]],
    }


def reduce_trace(path: str, window_name: str) -> Optional[dict]:
    """Read the chips' op events and the host spans of one trace file;
    ``None`` where it holds no chip or no window span."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    device, host, window = {}, [], None
    for plane in data.planes:
        if _is_chip(plane.name):
            evs = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs += [(op_name(e.name), e.start_ns,
                             e.start_ns + e.duration_ns)
                            for e in line.events]
            device[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    span = (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    if e.name == window_name and window is None:
                        window = span[1:]
                    else:
                        host.append(span)
    if not device or window is None:
        return None
    return reduce_events(device, host, window)


def idle_share(run) -> Optional[float]:
    """The idle share (%) of a run's traced window; ``None`` untraced."""
    tr = run.trace_summary
    if tr is None or not tr["window_s"] or not tr["busy_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
