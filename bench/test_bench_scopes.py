"""The reduction of a trace to device self time per program scope, and
of the program's serving spans to ``flush_wait_share``: on hand-made
events, on hand-made HLO, and end to end on a serialized trace built
here byte by byte."""
import types

import jax
import jax.numpy as jnp
import pytest

from bench import scopes

MS = 1_000_000      # ns


def test_self_time_by_leaf_root_and_step():
    # a while holds its body's ops on the same line: its own time is what
    # they leave; a fusion counts under the innermost root of its name
    device = {"/device:TPU:0": [
        ("while", 0, 100 * MS),
        ("adv", 10 * MS, 50 * MS),
        ("apply", 50 * MS, 60 * MS),
        ("pull", 60 * MS, 90 * MS),
        ("init", 100 * MS, 105 * MS),
        ("anon", 105 * MS, 106 * MS),
    ]}
    names = {
        "while": "jit(f)/enactor.loop/while",
        "adv": "jit(f)/enactor.loop/while/body/enactor.tier/"
               "cond/branch_2_fun/tier_2048/op.advance_filter/vmap()/gather",
        "apply": "jit(f)/enactor.loop/while/body/enactor.tier/tier_2048/"
                 "op.apply/scatter",
        "pull": "jit(f)/enactor.loop/while/body/enactor.direction/mixed/"
                "op.pull/reduce",
        "init": "jit(f)/primitive.init/broadcast_in_dim",
    }
    out = scopes.attribute(device, names, (0, 200 * MS))
    assert out["leaf"] == {"enactor.loop": 20 * MS,
                           "op.advance_filter": 40 * MS,
                           "op.apply": 10 * MS, "op.pull": 30 * MS,
                           "primitive.init": 5 * MS, scopes.NONE: 1 * MS}
    assert out["root"] == {"enactor": 20 * MS, "op": 80 * MS,
                           "primitive": 5 * MS, scopes.NONE: 1 * MS}
    assert out["step"] == {"- - enactor.loop": 20 * MS,
                           "- tier_2048 op.advance_filter": 40 * MS,
                           "- tier_2048 op.apply": 10 * MS,
                           "mixed - op.pull": 30 * MS,
                           "- - primitive.init": 5 * MS,
                           "- - (no scope)": 1 * MS}
    assert out["busy"] == 106 * MS
    # the window clips
    part = scopes.attribute(device, names, (0, 55 * MS))
    assert part["leaf"]["op.apply"] == 5 * MS
    assert part["busy"] == 55 * MS


def test_scope_of_takes_the_innermost_root_and_tier():
    assert scopes.scope_of(None) == (scopes.NONE, None, False)
    assert scopes.scope_of("jit(g)/reduce_sum") == (scopes.NONE, None,
                                                     False)
    assert scopes.scope_of(
        "jit(f)/enactor.tier/tier_512/op.advance_filter/tier_x/gather:"
    ) == ("op.advance_filter", "tier_512", False)
    assert scopes.scope_of("a/enactor.direction/mixed/op.apply/b") == (
        "op.apply", None, True)


HLO = """HloModule jit_f, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%fused_computation (param_0: f32[8]) -> s32[8] {
  %param_0 = f32[8]{0} parameter(0)
  %sine.1 = f32[8]{0} sine(%param_0), metadata={op_name="jit(f)/op.spmv/sin"}
  ROOT %bitcast.1 = s32[8]{0} bitcast-convert(%sine.1)
}

ENTRY %main (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %copy.1 = f32[8]{0} copy(%x)
  %fusion.7 = s32[8]{0} fusion(%copy.1), kind=kLoop, calls=%fused_computation
  %add.2 = f32[8]{0} add(%copy.1, %copy.1), metadata={op_name="jit(f)/op.apply/add"}
  ROOT %tuple.3 = (s32[8]{0}, f32[8]{0}) tuple(%fusion.7, %add.2)
}
"""


def test_unnamed_fusion_counts_under_its_root_and_a_copy_under_its_user():
    resolve = scopes.hlo_op_names(HLO)
    assert resolve("fusion.7") == "jit(f)/op.spmv/sin"
    assert resolve("add.2") == "jit(f)/op.apply/add"
    assert resolve("copy.1") == "jit(f)/op.apply/add"   # its named user
    assert resolve("x") == "jit(f)/op.apply/add"
    assert resolve("nothing") is None


def test_per_unit_divides_by_iterations_and_sweeps():
    sc = scopes.Scopes({"leaf": {"op.spmv": 400 * MS, "op.apply": 30 * MS},
                        "root": {"op": 430 * MS, "enactor": 70 * MS},
                        "step": {}, "busy": 500 * MS},
                       [], (0, 600 * MS), [])
    run = types.SimpleNamespace(items=[{"iterations": 6}, {"iterations": 4},
                                       {"sweeps": 20}, {"sweeps": 20}])
    run.__dict__["_bench_scopes"] = sc
    assert scopes.per_unit_ms(run, root="op") == pytest.approx(43.0)
    assert scopes.per_unit_ms(run, root="enactor") == pytest.approx(7.0)
    assert scopes.per_unit_ms(run, leaf="op.spmv",
                              count="sweeps") == pytest.approx(10.0)
    # a program without scopes (every op "(no scope)") reads nothing
    bare = scopes.Scopes({"leaf": {scopes.NONE: 1}, "root": {scopes.NONE: 1},
                          "step": {}, "busy": 1}, [], (0, 1), [])
    run.__dict__["_bench_scopes"] = bare
    assert scopes.per_unit_ms(run, root="op") is None
    run.__dict__["_bench_scopes"] = None
    assert scopes.per_unit_ms(run, root="op") is None


def test_flush_wait_share_from_spans():
    host = [
        ("serve.mixed", 0, 100, {"id": 7, "queries": 6}),
        # 4 queries wait 10 and finish at 30; 2 wait 30 and finish at 90
        ("serve.flush", 10, 30, {"kind": "bfs", "lanes": 4, "mixed": 7}),
        ("serve.flush", 30, 90, {"kind": "sssp", "lanes": 2, "mixed": 7}),
        ("serve.mixed", 200, 300, {"id": 8}),
        ("serve.flush", 250, 300, {"kind": "bfs", "lanes": 1, "mixed": 8}),
        ("serve.dispatch", 12, 28, {"kind": "bfs"}),
    ]
    want = 100.0 * (4 * 10 + 2 * 30) / (4 * 30 + 2 * 90)
    assert scopes.flush_wait_share(host, (0, 100)) == pytest.approx(want)
    both = 100.0 * (4 * 10 + 2 * 30 + 50) / (4 * 30 + 2 * 90 + 100)
    assert scopes.flush_wait_share(host, (0, 300)) == pytest.approx(both)
    kinds = scopes.flush_kinds(host, (0, 300))
    assert kinds["bfs"] == {"flushes": 2, "queries": 5,
                            "service_ms": pytest.approx(70e-6),
                            "wait_ms": pytest.approx(90e-6)}
    assert kinds["sssp"]["wait_ms"] == pytest.approx(60e-6)
    assert scopes.flush_wait_share(host[:1], (0, 100)) is None


# -- a serialized trace, built here ----------------------------------------

def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _msg(*fields):
    """Protobuf bytes of ``(field, value)`` pairs: an int is a varint,
    bytes or str is length-delimited."""
    out = b""
    for f, v in fields:
        if isinstance(v, int):
            out += _varint(f << 3) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(f << 3 | 2) + _varint(len(v)) + v
    return out


def _plane(pid, name, lines, events, stats):
    """An XPlane: ``events`` maps metadata id -> (name, [(stat id,
    field, value)]); ``stats`` maps stat id -> name."""
    fields = [(1, pid), (2, name)]
    fields += [(3, line) for line in lines]
    for mid, (ev_name, ev_stats) in events.items():
        meta = _msg((1, mid), (2, ev_name),
                    *[(5, _msg((1, sid), (f, v))) for sid, f, v in ev_stats])
        fields.append((4, _msg((1, mid), (2, meta))))
    for sid, sname in stats.items():
        fields.append((5, _msg((1, sid), (2, _msg((1, sid), (2, sname))))))
    return _msg(*fields)


def _line(name, events):
    """An XLine at timestamp 0: ``events`` are (metadata id, start ns,
    duration ns, [(stat id, field, value)])."""
    return _msg((2, name), (3, 0), *[
        (4, _msg((1, mid), (2, a * 1000), (3, d * 1000),
                 *[(4, _msg((1, sid), (f, v))) for sid, f, v in st]))
        for mid, a, d, st in events])


def test_read_trace_end_to_end(tmp_path):
    @jax.jit
    def f(x):
        with jax.named_scope("op.spmv"):
            return jnp.sin(x) * 2.0

    mod = f.lower(jnp.ones(8)).compile().runtime_executable().hlo_modules()[0]
    instr = next(line.split("=")[0].strip().lstrip("ROOT").strip()
                 .lstrip("%") for line in mod.to_string().splitlines()
                 if "op.spmv" in line and " = " in line)
    hlo_proto = _msg((1, mod.as_serialized_hlo_module_proto()))
    # stats: 1 tf_op, 2 program_id, 3 Hlo Proto, 4 kind, 5 lanes, 6 mixed,
    # 7 id
    stats = {1: "tf_op", 2: "program_id", 3: "Hlo Proto", 4: "kind",
             5: "lanes", 6: "mixed", 7: "id"}
    chip = _plane(1, "/device:TPU:0",
                  [_line("XLA Ops", [(1, 100, 300, []), (2, 600, 200, []),
                                     (3, 900, 50, [])])],
                  {1: ("%fusion.1 = f32[8] fusion(%x)",
                       [(1, 5, "jit(g)/enactor.loop/while/body/tier_512/"
                               "op.advance_filter/gather:")]),
                   2: (f"%{instr} = f32[8]{{0}} thing(%x)",
                       [(2, 4, 77)]),             # named by the module
                   3: ("%copy.9 = f32[8] copy(%y)", [])},
                  stats)
    host = _plane(2, "/host:CPU",
                  [_line("python", [
                      (1, 0, 1000, []),
                      (2, 50, 1000, [(7, 4, 3)]),
                      (3, 60, 900, [(4, 5, "bfs"), (5, 4, 8), (6, 4, 3)]),
                      (4, 70, 10, []),
                      (5, 50, 400, [])])],
                  {1: ("bench.window", []), 2: ("serve.mixed", []),
                   3: ("serve.flush", []), 4: ("$frame.py:1 x", []),
                   5: ("bench.serve", [])},
                  stats)
    meta = _plane(3, "/host:metadata", [],
                  {77: ("jit_f(77)", [(3, 6, hlo_proto)])}, stats)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_msg((1, chip), (1, host), (1, meta)))

    sc = scopes.read_trace(str(path), "bench.window")
    assert sc.self_ns["leaf"] == {"op.advance_filter": 300,
                                  "op.spmv": 200, scopes.NONE: 50}
    assert sc.self_ns["step"]["- tier_512 op.advance_filter"] == 300
    assert sc.scoped and sc.ms(root="op") == pytest.approx(500e-6)
    names = {n for n, *_ in sc.host}
    assert names == {"serve.mixed", "serve.flush", "bench.serve"}
    share = scopes.flush_wait_share(sc.host, sc.window)
    assert share == pytest.approx(100.0 * 10 / 910)
    # the longest idle gap (200 ns, under the flush) named by the
    # innermost program span open in its middle
    assert sc.gaps[0] == ["serve.flush", pytest.approx(200e-9)]
    assert sc.gaps[1][0] == "bench.serve"
    summary = sc.summary()
    assert summary["scoped_share"] == pytest.approx(100 * 500 / 550)
    assert summary["flush_wait_share"] == pytest.approx(share)
    assert summary["flushes"]["bfs"]["queries"] == 8
    assert summary["units"] == [{"ms": pytest.approx(400e-6), "steps": {
        "- tier_512 op.advance_filter": pytest.approx(300e-6)}}]
