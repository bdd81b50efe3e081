"""reprolint — the jit-discipline linter (stdlib ``ast``, no deps).

The engine's correctness rests on conventions nothing else enforces:
jit-clean BSP loops (a single ``.item()`` in a hot path turns an async
dispatch pipeline into a per-iteration host round trip), int32-pinned
integer accumulators (under ``jax_enable_x64`` an unpinned ``jnp.sum``
promotes to int64 and poisons carried state — the exact drift class
PR 6 fixed by hand), fenced timing (an unfenced ``time.monotonic`` pair
measures enqueue latency, not execution), and diagnostics routed through
``repro.obs.log`` (a bare ``print`` in library code cannot be silenced
in a serving loop). Every rule below encodes one of those conventions.

Rules
  RL001 host-sync-in-traced   ``.item()``/``.tolist()``, ``int()``/
                              ``bool()``/``float()`` over array
                              expressions, or ``np.asarray``/``np.array``
                              of device values inside a traced region
                              (a jitted function, a ``lax`` control-flow
                              body, a Pallas kernel, or anything nested
                              in one).
  RL002 tracer-branch         Python ``if``/``while`` over an array
                              expression, or ``for`` over an array
                              iterable, inside a traced region — a
                              retrace storm or a ConcretizationError
                              waiting for the first untested config.
  RL003 unpinned-int-accum    ``jnp.sum``/``cumsum``/``prod``/
                              ``count_nonzero`` without ``dtype=`` over
                              a bool/int-flavored operand and without an
                              immediate ``.astype`` re-pin (x64 drift).
  RL004 unfenced-timing       a wall-clock measurement (two timing calls
                              or a timing subtraction) with no
                              ``block_until_ready`` / ``span`` /
                              ``timed`` fence inside the measured region.
  RL005 bare-diagnostic       ``print(...)`` or ``warnings.warn(...)``
                              in library code (under ``src/repro``) —
                              route through ``repro.obs.log``.
  RL006 swallowed-exception   a bare ``except:`` that never re-raises, or
                              an ``except Exception/BaseException`` whose
                              body is only ``pass``/``...``/``continue``.
                              Blanket swallowing hides the exact faults
                              the robustness layer exists to surface;
                              legitimate boundaries (the retry/degrade
                              ladder) declare themselves with a
                              ``# reprolint: disable=RL006 -- why``.

Suppression syntax (same line or the line above)::

    total = jnp.sum(counts)     # reprolint: disable=RL003 -- host-only
    # reprolint: disable=RL004,RL005
    # reprolint: skip-file          (first 10 lines: skip whole file)

A bare ``# reprolint: disable`` suppresses every rule on that line.
Suppressions are deliberate, reviewable markers — each one should carry
a trailing reason, the way the shipped tree's do.

CLI::

    python -m repro.analysis.lint [paths ...] [--select RL00x,...]
        [--json] [--statistics] [--lib-root PREFIX]

Exit status 1 when findings remain, 0 on a clean tree. Rule detection
is intentionally syntactic and calibrated to this codebase: it cannot
prove an expression is a tracer, only that it is array-flavored in a
region that traces — which is exactly the review question a human would
ask, automated.
"""
from __future__ import annotations

import argparse
import ast
import json
import re
import sys
from dataclasses import asdict, dataclass
from pathlib import Path
from typing import Iterable, Optional

RULES = {
    "RL001": "host sync inside a traced region",
    "RL002": "Python control flow over an array value in a traced region",
    "RL003": "integer/bool accumulation without a pinned dtype",
    "RL004": "wall-clock timing without a fence in the measured region",
    "RL005": "bare print()/warnings.warn() in library code",
    "RL006": "exception swallowed outside a declared retry boundary",
}

_SUPPRESS_RE = re.compile(
    r"#\s*reprolint:\s*disable(?:=\s*([A-Za-z0-9_,\s]+?))?\s*(?:--|$)")
_SKIP_FILE_RE = re.compile(r"#\s*reprolint:\s*skip-file")

# --- syntactic vocabulary -------------------------------------------------

_TIMING_FNS = {"time.monotonic", "time.monotonic_ns", "time.time",
               "time.perf_counter", "time.perf_counter_ns"}
_FENCE_ATTR = "block_until_ready"
_FENCE_CALLS = {"timed", "span"}
# calls whose function-valued arguments are traced by JAX
_TRACING_WRAPPERS = {"jit", "vmap", "pmap", "while_loop", "fori_loop",
                     "scan", "cond", "switch", "map", "shard_map",
                     "pallas_call", "checkpoint", "remat", "grad",
                     "value_and_grad"}
_ACCUM_FNS = {"jnp.sum", "jnp.cumsum", "jnp.prod", "jnp.count_nonzero",
              "jax.numpy.sum", "jax.numpy.cumsum", "jax.numpy.prod",
              "jax.numpy.count_nonzero"}
_ARRAY_METHODS = {"any", "all", "sum", "min", "max", "mean", "astype",
                  "argmax", "argmin", "item", "nonzero", "ravel", "dot"}
# jnp calls that return static Python values — never tracers
_STATIC_JNP = {"jnp.issubdtype", "jnp.dtype", "jnp.result_type",
               "jnp.iinfo", "jnp.finfo", "jnp.shape", "jnp.ndim",
               "jnp.size", "jnp.promote_types"}
_INT_DTYPES = {"int8", "int16", "int32", "int64",
               "uint8", "uint16", "uint32", "uint64"}
_BOOL_DTYPES = {"bool", "bool_"}
_BOOL_CALLS = {"jnp.logical_and", "jnp.logical_or", "jnp.logical_not",
               "jnp.logical_xor", "jnp.isin", "jnp.isnan", "jnp.isfinite",
               "jnp.isinf", "jnp.isclose", "jnp.equal", "jnp.not_equal",
               "jnp.greater", "jnp.less", "jnp.greater_equal",
               "jnp.less_equal"}
_NP_CAST = {"np.asarray", "np.array", "numpy.asarray", "numpy.array"}


@dataclass
class Finding:
    path: str
    line: int
    col: int
    rule: str
    message: str

    def render(self) -> str:
        return f"{self.path}:{self.line}:{self.col}: {self.rule} {self.message}"


def _dotted(node) -> Optional[str]:
    """'jax.lax.fori_loop' for an Attribute/Name chain, else None."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _is_arrayish(expr: ast.AST) -> bool:
    """Heuristic: does this expression produce / consume a jnp array?"""
    for sub in ast.walk(expr):
        if isinstance(sub, ast.Call):
            d = _dotted(sub.func)
            if d is not None:
                if d in _STATIC_JNP:
                    continue
                root = d.split(".", 1)[0]
                if root in ("jnp", "lax") or d.startswith(("jax.numpy.",
                                                          "jax.lax.")):
                    return True
            if (isinstance(sub.func, ast.Attribute)
                    and sub.func.attr in _ARRAY_METHODS):
                return True
    return False


def _astype_flavor(call: ast.Call) -> Optional[str]:
    """'int' / 'bool' when ``call`` is ``x.astype(<that dtype>)``."""
    if not (isinstance(call.func, ast.Attribute)
            and call.func.attr == "astype" and call.args):
        return None
    arg = call.args[0]
    name = _dotted(arg)
    leaf = name.rsplit(".", 1)[-1] if name else None
    if leaf in _INT_DTYPES:
        return "int"
    if leaf in _BOOL_DTYPES:
        return "bool"
    return None


def _flavor(expr: ast.AST, env: dict) -> Optional[str]:
    """'int' | 'bool' | None — the syntactic integer-ness of ``expr``.
    ``env`` maps local names to flavors (single-pass assignment scan)."""
    if isinstance(expr, ast.Compare):
        return "bool"
    if isinstance(expr, ast.BoolOp):
        return "bool"
    if isinstance(expr, ast.UnaryOp) and isinstance(expr.op,
                                                    (ast.Invert, ast.Not)):
        return "bool"
    if isinstance(expr, ast.BinOp):
        if isinstance(expr.op, (ast.BitAnd, ast.BitOr, ast.BitXor)):
            return "bool"
        if isinstance(expr.op, (ast.Add, ast.Sub, ast.Mult)):
            return (_flavor(expr.left, env) or _flavor(expr.right, env))
    if isinstance(expr, ast.Call):
        f = _astype_flavor(expr)
        if f is not None:
            return f
        d = _dotted(expr.func)
        if d in _BOOL_CALLS:
            return "bool"
    if isinstance(expr, ast.Name):
        return env.get(expr.id)
    return None


def _scope_nodes(body: Iterable[ast.stmt]):
    """All nodes in a function/module body WITHOUT descending into nested
    function definitions (they are their own scopes)."""
    stack = list(body)
    while stack:
        node = stack.pop()
        yield node
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            stack.append(child)


class _FileLinter:
    def __init__(self, path: str, source: str, *, lib: bool,
                 select: Optional[set] = None):
        self.path = path
        self.source = source
        self.lib = lib
        self.select = select or set(RULES)
        self.findings: list[Finding] = []
        self.lines = source.splitlines()
        self.suppressions = self._scan_suppressions()
        self.tree = ast.parse(source, filename=path)
        for node in ast.walk(self.tree):
            for child in ast.iter_child_nodes(node):
                child._rl_parent = node
        self.traced = self._collect_traced()

    # -- suppression handling ---------------------------------------------

    def _scan_suppressions(self) -> dict:
        out: dict[int, set] = {}
        for i, line in enumerate(self.lines, start=1):
            m = _SUPPRESS_RE.search(line)
            if m:
                ids = m.group(1)
                out[i] = ({s.strip().upper() for s in ids.split(",")
                           if s.strip()} if ids else {"*"})
        return out

    def _suppressed(self, line: int, rule: str) -> bool:
        for ln in (line, line - 1):
            ids = self.suppressions.get(ln)
            if ids and ("*" in ids or rule in ids):
                return True
        return False

    def _flag(self, node: ast.AST, rule: str, message: str) -> None:
        if rule not in self.select:
            return
        line = getattr(node, "lineno", 1)
        if self._suppressed(line, rule):
            return
        self.findings.append(Finding(self.path, line,
                                     getattr(node, "col_offset", 0),
                                     rule, message))

    # -- traced-region discovery ------------------------------------------

    def _collect_traced(self) -> set:
        """Function/Lambda nodes that JAX traces: jit-decorated, or passed
        (directly or via functools.partial) to a lax control-flow /
        pallas_call / transform wrapper."""
        defs_by_name: dict[str, list] = {}
        traced: set[int] = set()
        for node in ast.walk(self.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                defs_by_name.setdefault(node.name, []).append(node)
                for dec in node.decorator_list:
                    if self._is_jit_decorator(dec):
                        traced.add(id(node))

        def mark(arg):
            if isinstance(arg, ast.Lambda):
                traced.add(id(arg))
            elif isinstance(arg, ast.Name):
                for d in defs_by_name.get(arg.id, ()):
                    traced.add(id(d))
            elif isinstance(arg, ast.Call):
                d = _dotted(arg.func)
                if d and d.rsplit(".", 1)[-1] == "partial" and arg.args:
                    mark(arg.args[0])

        for node in ast.walk(self.tree):
            if not isinstance(node, ast.Call):
                continue
            d = _dotted(node.func)
            if d and d.rsplit(".", 1)[-1] in _TRACING_WRAPPERS:
                for arg in node.args:
                    mark(arg)
        return traced

    @staticmethod
    def _is_jit_decorator(dec: ast.AST) -> bool:
        d = _dotted(dec)
        if d in ("jit", "jax.jit", "pjit", "jax.pjit"):
            return True
        if isinstance(dec, ast.Call):
            d = _dotted(dec.func)
            if d in ("jit", "jax.jit", "pjit", "jax.pjit"):
                return True
            if d and d.rsplit(".", 1)[-1] == "partial" and dec.args:
                inner = _dotted(dec.args[0])
                return inner in ("jit", "jax.jit", "pjit", "jax.pjit")
        return False

    # -- main traversal ----------------------------------------------------

    def run(self) -> list[Finding]:
        if any(_SKIP_FILE_RE.search(ln) for ln in self.lines[:10]):
            return []
        self._visit_block(self.tree.body, traced=False)
        self._check_timing_scope(self.tree.body)
        self.findings.sort(key=lambda f: (f.line, f.col, f.rule))
        return self.findings

    def _visit_block(self, body, *, traced: bool) -> None:
        env: dict[str, Optional[str]] = {}
        stack = list(body)
        # breadth-ish walk that tracks traced-ness across nested defs and
        # builds the flavor environment from assignments in source order
        nodes = []
        while stack:
            node = stack.pop(0)
            # defs/lambdas — wherever they appear — get their own region,
            # with traced-ness propagated (a def nested in a jitted body
            # is traced too)
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                sub_traced = traced or id(node) in self.traced
                self._visit_block(node.body, traced=sub_traced)
                self._check_timing_scope(node.body)
                continue
            if isinstance(node, ast.Lambda):
                sub_traced = traced or id(node) in self.traced
                self._visit_expr_region([node.body], traced=sub_traced,
                                        env={})
                continue
            nodes.append(node)
            stack.extend(ast.iter_child_nodes(node))
        nodes.sort(key=lambda n: (getattr(n, "lineno", 0),
                                  getattr(n, "col_offset", 0)))
        for node in nodes:
            if (isinstance(node, ast.Assign) and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)):
                env[node.targets[0].id] = _flavor(node.value, env)
            self._check_node(node, traced=traced, env=env)

    def _visit_expr_region(self, exprs, *, traced: bool, env: dict) -> None:
        for e in exprs:
            for node in ast.walk(e):
                self._check_node(node, traced=traced, env=env)

    def _check_node(self, node, *, traced: bool, env: dict) -> None:
        if isinstance(node, ast.Call):
            self._check_call(node, traced=traced, env=env)
        elif isinstance(node, ast.ExceptHandler):
            self._check_except(node)
        elif isinstance(node, (ast.If, ast.While)) and traced:
            if _is_arrayish(node.test):
                kw = "if" if isinstance(node, ast.If) else "while"
                self._flag(node, "RL002",
                           f"Python `{kw}` over an array expression in a "
                           f"traced region — use jnp.where / lax.cond")
        elif isinstance(node, ast.For) and traced:
            if _is_arrayish(node.iter):
                self._flag(node, "RL002",
                           "Python `for` over an array iterable in a "
                           "traced region — use lax.fori_loop / scan")

    def _check_call(self, node: ast.Call, *, traced: bool,
                    env: dict) -> None:
        d = _dotted(node.func)

        # RL001 — host syncs in traced regions
        if traced:
            if (isinstance(node.func, ast.Attribute)
                    and node.func.attr in ("item", "tolist")
                    and not node.args):
                self._flag(node, "RL001",
                           f"`.{node.func.attr}()` forces a host sync "
                           f"inside a traced region")
            elif (isinstance(node.func, ast.Name)
                  and node.func.id in ("int", "bool", "float")
                  and len(node.args) == 1
                  and _is_arrayish(node.args[0])):
                self._flag(node, "RL001",
                           f"`{node.func.id}(...)` over an array "
                           f"expression concretizes a tracer (host sync)")
            elif d in _NP_CAST and node.args and not isinstance(
                    node.args[0], (ast.List, ast.Tuple, ast.Constant)):
                self._flag(node, "RL001",
                           f"`{d}` of a device value inside a traced "
                           f"region forces a transfer — use jnp")

        # RL003 — unpinned integer accumulation
        if d in _ACCUM_FNS and node.args:
            has_dtype = any(kw.arg == "dtype" for kw in node.keywords)
            parent = getattr(node, "_rl_parent", None)
            repinned = (isinstance(parent, ast.Attribute)
                        and parent.attr == "astype")
            if (not has_dtype and not repinned
                    and _flavor(node.args[0], env) in ("int", "bool")):
                self._flag(node, "RL003",
                           f"`{d}` over an integer/bool operand without "
                           f"dtype= promotes to int64 under "
                           f"jax_enable_x64 — pin dtype=jnp.int32")

        # RL005 — bare diagnostics in library code
        if self.lib:
            if isinstance(node.func, ast.Name) and node.func.id == "print":
                self._flag(node, "RL005",
                           "bare print() in library code — route through "
                           "repro.obs.log.get_logger(...)")
            elif d in ("warnings.warn",):
                self._flag(node, "RL005",
                           "warnings.warn() in library code — route "
                           "through repro.obs.log (deprecated()/logger)")

    # -- RL006: swallowed exceptions --------------------------------------

    @staticmethod
    def _broad_types(handler: ast.ExceptHandler):
        """Names among Exception/BaseException the handler catches."""
        nodes = (handler.type.elts if isinstance(handler.type, ast.Tuple)
                 else [handler.type])
        out = []
        for t in nodes:
            d = _dotted(t)
            leaf = d.rsplit(".", 1)[-1] if d else None
            if leaf in ("Exception", "BaseException"):
                out.append(leaf)
        return out

    def _check_except(self, handler: ast.ExceptHandler) -> None:
        body_raises = any(isinstance(n, ast.Raise)
                          for stmt in handler.body
                          for n in ast.walk(stmt))
        if handler.type is None:
            # a bare except: catches KeyboardInterrupt/SystemExit too —
            # only a re-raising cleanup handler gets a pass
            if not body_raises:
                self._flag(handler, "RL006",
                           "bare `except:` swallows every exception "
                           "(including KeyboardInterrupt) — catch a "
                           "concrete type, re-raise, or declare the "
                           "boundary with a disable comment")
            return
        broad = self._broad_types(handler)
        if not broad or body_raises:
            return
        trivial = all(
            isinstance(stmt, (ast.Pass, ast.Continue))
            or (isinstance(stmt, ast.Expr)
                and isinstance(stmt.value, ast.Constant)
                and stmt.value.value is Ellipsis)
            for stmt in handler.body)
        if trivial:
            self._flag(handler, "RL006",
                       f"`except {broad[0]}` with an empty body discards "
                       f"the failure — handle it, narrow the type, or "
                       f"declare the retry boundary with a disable "
                       f"comment")

    # -- RL004: per-scope timing analysis ---------------------------------

    def _check_timing_scope(self, body) -> None:
        timing_calls = []
        timing_subs = []
        fence_lines = []
        for node in _scope_nodes(body):
            if isinstance(node, ast.Call):
                d = _dotted(node.func)
                if d in _TIMING_FNS:
                    timing_calls.append(node)
                elif d and d.rsplit(".", 1)[-1] in _FENCE_CALLS:
                    fence_lines.append(node.lineno)
            if (isinstance(node, ast.Attribute)
                    and node.attr == _FENCE_ATTR):
                fence_lines.append(node.lineno)
            if isinstance(node, ast.Name) and node.id == _FENCE_ATTR:
                fence_lines.append(node.lineno)
            if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Sub):
                if any(isinstance(s, ast.Call)
                       and _dotted(s.func) in _TIMING_FNS
                       for s in ast.walk(node)):
                    timing_subs.append(node)
        measuring = len(timing_calls) >= 2 or timing_subs
        if not (measuring and timing_calls):
            return
        region = [n.lineno for n in timing_calls]
        region += [n.lineno for n in timing_subs]
        lo, hi = min(region), max(region)
        if any(lo <= ln <= hi for ln in fence_lines):
            return
        first = min(timing_calls, key=lambda n: n.lineno)
        self._flag(first, "RL004",
                   "timing region has no block_until_ready / span / "
                   "timed fence — async dispatch makes this measure "
                   "enqueue, not execution")


# --- public API ------------------------------------------------------------


def lint_source(source: str, path: str = "<string>", *,
                lib: Optional[bool] = None,
                select: Optional[set] = None,
                lib_root: str = "src/repro") -> list[Finding]:
    """Lint a source string. ``lib`` controls RL005 (library-only rule);
    when None it is inferred from ``path`` containing ``lib_root``."""
    if lib is None:
        lib = lib_root in Path(path).as_posix()
    try:
        return _FileLinter(path, source, lib=lib, select=select).run()
    except SyntaxError as e:
        return [Finding(path, e.lineno or 1, e.offset or 0, "RL000",
                        f"syntax error: {e.msg}")]


def lint_file(path, *, select: Optional[set] = None,
              lib_root: str = "src/repro") -> list[Finding]:
    p = Path(path)
    return lint_source(p.read_text(), str(p), select=select,
                       lib_root=lib_root)


def iter_py_files(paths) -> Iterable[Path]:
    for raw in paths:
        p = Path(raw)
        if p.is_dir():
            yield from sorted(q for q in p.rglob("*.py")
                              if "__pycache__" not in q.parts)
        elif p.suffix == ".py":
            yield p


def lint_paths(paths, *, select: Optional[set] = None,
               lib_root: str = "src/repro") -> list[Finding]:
    findings: list[Finding] = []
    for f in iter_py_files(paths):
        findings.extend(lint_file(f, select=select, lib_root=lib_root))
    return findings


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis.lint",
        description="reprolint — jit-discipline linter for the repro tree")
    ap.add_argument("paths", nargs="*", default=["src/repro"],
                    help="files/directories to lint (default: src/repro)")
    ap.add_argument("--select", default=None,
                    help="comma-separated rule ids (default: all)")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="emit findings as a JSON array")
    ap.add_argument("--statistics", action="store_true",
                    help="print a per-rule count summary")
    ap.add_argument("--lib-root", default="src/repro",
                    help="path fragment marking library code for RL005")
    args = ap.parse_args(argv)

    select = ({s.strip().upper() for s in args.select.split(",")}
              if args.select else None)
    findings = lint_paths(args.paths, select=select,
                          lib_root=args.lib_root)
    if args.as_json:
        print(json.dumps([asdict(f) for f in findings], indent=1))  # reprolint: disable=RL005 -- CLI output channel
    else:
        for f in findings:
            print(f.render())  # reprolint: disable=RL005 -- CLI output channel
    if args.statistics:
        counts: dict[str, int] = {}
        for f in findings:
            counts[f.rule] = counts.get(f.rule, 0) + 1
        for rule in sorted(counts):
            print(f"{rule}: {counts[rule]:4d}  {RULES.get(rule, '')}")  # reprolint: disable=RL005 -- CLI output channel
        nfiles = len(list(iter_py_files(args.paths)))
        print(f"{len(findings)} finding(s) across {nfiles} file(s)")  # reprolint: disable=RL005 -- CLI output channel
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
