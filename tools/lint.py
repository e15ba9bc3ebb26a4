#!/usr/bin/env python
"""AST-based jit-hygiene linter for the NeuRRAM reproduction.

Layer 2 of the static-analysis subsystem (layer 1 is the chip-IR verifier,
`src/repro/core/verify.py`). Each rule encodes a bug class this repo has
actually shipped or reviewed out:

  R001 unpinned-out-shardings   an engine-path `jax.jit` with a mesh in
                                lexical scope must pin `out_shardings`
                                (the PR-7 bug: fresh GSPMDSharding objects
                                per step caused a C++ pjit call-cache miss
                                on EVERY decode step, found only via a
                                runtime trace counter).
  R002 donated-arg-reuse        a buffer passed in a `donate_argnums`
                                position is dead after the call; reading
                                the old name again is use-after-donate.
  R003 host-op-in-traced        no `np.` calls or Python `if` on a traced
                                parameter inside a function handed to
                                `jax.jit` / `shard_map` / `pallas_call`
                                or a layer scan (`jax.lax.scan`,
                                `scan_layers`) (host ops silently
                                constant-fold at trace time; tracer `if`
                                raises only on the branch actually
                                taken).
  R004 static-argnames-real     `static_argnames` must name real
                                parameters and `static_argnums` must be in
                                range — jax only validates lazily at call
                                time, so a typo'd name silently makes the
                                argument traced (and the jit cache miss on
                                every distinct value never happens).
  R005 parity-eager-vs-jit      bitwise-parity assertions in tests/ must
                                compare jit-vs-jit: eager-vs-jit
                                comparisons conflate compiler numerics
                                with the contract under test (the repo's
                                bitwise gates — packed-vs-loop,
                                pool-vs-static — are all jit-vs-jit).
  R006 bare-serve-clock         serving-path modules (launch/*, *serving*,
                                *scheduler*) must take timestamps from
                                repro.obs.clock (now / timed_call /
                                stopwatch), not bare time.time() /
                                time.perf_counter() — two clocks on the
                                serve path make latency histograms, trace
                                spans and "continuous beats static" rows
                                mutually unfalsifiable. time.sleep is
                                fine (pacing, not measurement); the obs
                                package and benchmarks/_timing are the
                                clock's own home and exempt.

Pure AST analysis: nothing is imported or executed, so linting cannot be
affected by (or affect) device state. Suppress a finding with a trailing
`# lint: disable=R00X` comment on the offending line.

Usage:
  python tools/lint.py [paths...]     lint .py files/trees (default: src tests)
  python tools/lint.py --self-test    run the linter against the fixture
                                      snippets in tools/lint_fixtures/ (each
                                      declares its expected findings in a
                                      `# lint-expect:` header) AND drive the
                                      chip-IR verifier over in-process corrupt
                                      artifacts reproducing the historical
                                      layouts (PR-2 non-consecutive fused run,
                                      duplicated schedule index)

Run by `tools/ci.sh lint`, and first in the fast tier: violations fail
deterministically — no timing involved.
"""
from __future__ import annotations

import argparse
import ast
import dataclasses
import re
import sys
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Set, Tuple

REPO = Path(__file__).resolve().parent.parent

JIT_NAMES = {"jax.jit", "jit", "pjit", "jax.pjit", "api.jit"}
TRACE_WRAPPERS = JIT_NAMES | {
    "shard_map", "jax.shard_map", "jax.experimental.shard_map.shard_map",
    "pl.pallas_call", "pallas_call", "jax.checkpoint", "jax.remat",
    "jax.vmap", "vmap", "jax.lax.scan",
    # models/transformer.scan_layers: the layer scan every model family
    # shares hands its body to jax.lax.scan inside a lambda
    "scan_layers", "T.scan_layers"}
PARTIAL_NAMES = {"functools.partial", "partial"}
# attributes of a traced value that are static python data at trace time
STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "sharding", "at"}
# comparison helpers whose args a parity test feeds (R005)
PARITY_FNS = re.compile(
    r"(^|\.)(assert_)?(array_equal|allclose|array_almost_equal|"
    r"trees_all_close|trees_all_equal|equal)$")
DISABLE_RE = re.compile(r"#\s*(?:lint:\s*disable|noqa:)\s*=?\s*"
                        r"(R\d{3}(?:\s*,\s*R\d{3})*)")
# time-module functions that READ a clock (R006); time.sleep paces and is
# allowed on the serving path
CLOCK_FNS = {"time", "time_ns", "perf_counter", "perf_counter_ns",
             "monotonic", "monotonic_ns", "process_time",
             "process_time_ns"}


@dataclasses.dataclass(frozen=True)
class Violation:
    path: str
    line: int
    rule: str
    message: str

    def __str__(self):
        return f"{self.path}:{self.line}: {self.rule} {self.message}"


def _qualname(node: ast.AST) -> Optional[str]:
    """Dotted name of a Name/Attribute chain, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _call_name(call: ast.Call) -> Optional[str]:
    return _qualname(call.func)


def _is_jit_call(node: ast.AST) -> bool:
    return (isinstance(node, ast.Call)
            and _call_name(node) in JIT_NAMES)


def _const_str_tuple(node: ast.AST) -> Optional[Tuple[str, ...]]:
    """('a', 'b') / ['a'] / 'a' literals -> tuple of strings, else None."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for el in node.elts:
            if not (isinstance(el, ast.Constant)
                    and isinstance(el.value, str)):
                return None
            out.append(el.value)
        return tuple(out)
    return None


def _const_int_tuple(node: ast.AST) -> Optional[Tuple[int, ...]]:
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for el in node.elts:
            if not (isinstance(el, ast.Constant)
                    and isinstance(el.value, int)):
                return None
            out.append(el.value)
        return tuple(out)
    return None


def _fn_param_names(fn: ast.FunctionDef) -> List[str]:
    a = fn.args
    return [p.arg for p in a.posonlyargs + a.args + a.kwonlyargs]


def _fn_positional_count(fn: ast.FunctionDef) -> Optional[int]:
    a = fn.args
    if a.vararg is not None:
        return None                      # *args: any argnum is reachable
    return len(a.posonlyargs) + len(a.args)


class ModuleLinter:
    def __init__(self, path: Path, source: str, *, is_test: bool):
        self.path = path
        self.rel = str(path)
        self.is_test = is_test
        self.tree = ast.parse(source, filename=str(path))
        self.violations: List[Violation] = []
        self.disabled: Dict[int, Set[str]] = {}
        for i, line in enumerate(source.splitlines(), 1):
            m = DISABLE_RE.search(line)
            if m:
                self.disabled[i] = {r.strip()
                                    for r in m.group(1).split(",")}
        # parent pointers + enclosing-function chain
        self.parent: Dict[ast.AST, ast.AST] = {}
        for node in ast.walk(self.tree):
            for child in ast.iter_child_nodes(node):
                self.parent[child] = node
        # resolvable function defs: module level, plus nested defs keyed by
        # (enclosing fn, name) for locally-defined traced functions
        self.module_defs: Dict[str, ast.FunctionDef] = {}
        self.local_defs: Dict[Tuple[ast.AST, str], ast.FunctionDef] = {}
        for node in ast.walk(self.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                scope = self.enclosing_fn(node)
                if scope is None:
                    self.module_defs.setdefault(node.name, node)
                else:
                    self.local_defs.setdefault((scope, node.name), node)

    # ---------------------------------------------------------- plumbing

    def report(self, rule: str, node: ast.AST, message: str) -> None:
        line = getattr(node, "lineno", 0)
        if rule in self.disabled.get(line, ()):
            return
        self.violations.append(Violation(self.rel, line, rule, message))

    def enclosing_fn(self, node: ast.AST) -> Optional[ast.FunctionDef]:
        cur = self.parent.get(node)
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return cur
            cur = self.parent.get(cur)
        return None

    def enclosing_stmt(self, node: ast.AST) -> ast.stmt:
        cur = node
        while not isinstance(cur, ast.stmt):
            cur = self.parent[cur]
        return cur

    def resolve_fn(self, node: ast.AST, at: ast.AST
                   ) -> Optional[ast.FunctionDef]:
        """Resolve a Name to a function def visible from `at`'s scope."""
        if not isinstance(node, ast.Name):
            return None
        scope = self.enclosing_fn(at)
        while scope is not None:
            fn = self.local_defs.get((scope, node.id))
            if fn is not None:
                return fn
            scope = self.enclosing_fn(scope)
        return self.module_defs.get(node.id)

    def run(self) -> List[Violation]:
        if not self.is_test:
            # engine-path rule: test harnesses jit under a mesh to count
            # traces / check parity, where a one-shot unpinned jit is fine
            self.rule_out_shardings()
        self.rule_donated_reuse()
        self.rule_traced_host_ops()
        self.rule_static_argnames()
        if self.is_test:
            self.rule_parity_jit_vs_jit()
        if not self.is_test and self._serving_path_module():
            self.rule_serve_clock()
        return self.violations

    # ----------------------------------------------- R001: out_shardings

    def _binds_mesh(self, fn: ast.FunctionDef) -> bool:
        if any(p == "mesh" or p.endswith("_mesh")
               for p in _fn_param_names(fn)):
            return True
        for node in ast.walk(fn):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and node is not fn:
                continue
            if isinstance(node, ast.Name) and node.id == "mesh" \
                    and isinstance(node.ctx, ast.Store) \
                    and self.enclosing_fn(node) is fn:
                return True
        return False

    def _mesh_in_scope(self, node: ast.AST) -> bool:
        fn = self.enclosing_fn(node)
        while fn is not None:
            if self._binds_mesh(fn):
                return True
            fn = self.enclosing_fn(fn)
        # module-level mesh binding
        for stmt in self.tree.body:
            for t in ast.walk(stmt):
                if isinstance(t, ast.Name) and t.id == "mesh" \
                        and isinstance(t.ctx, ast.Store) \
                        and self.enclosing_fn(t) is None:
                    return True
        return False

    @staticmethod
    def _pins_out_shardings(call: ast.Call) -> bool:
        for kw in call.keywords:
            if kw.arg == "out_shardings":
                return True
            if kw.arg is None:
                # **expr — pinned if the expression mentions the key (the
                # conditional-dict idiom: **({"out_shardings": ns} if ns
                # is not None else {})); a bare **kwargs variable is
                # opaque, so give it the benefit of the doubt
                if isinstance(kw.value, ast.Name):
                    return True
                for sub in ast.walk(kw.value):
                    if isinstance(sub, ast.Constant) \
                            and sub.value == "out_shardings":
                        return True
        return False

    def rule_out_shardings(self) -> None:
        for node in ast.walk(self.tree):
            if not _is_jit_call(node):
                continue
            # decorators never see a local mesh; only call-site jits with a
            # mesh lexically in scope are the engine-path pattern
            if not self._mesh_in_scope(node):
                continue
            if self._pins_out_shardings(node):
                continue
            self.report(
                "R001", node,
                "jax.jit with a mesh in scope must pin out_shardings "
                "(unpinned shardings rebuilt per call defeat the C++ pjit "
                "call cache — one retrace-check per serving step)")

    # ------------------------------------------------ R002: donate reuse

    def _donated_positions(self, call: ast.Call) -> Tuple[int, ...]:
        for kw in call.keywords:
            if kw.arg == "donate_argnums":
                nums = _const_int_tuple(kw.value)
                if nums:
                    return nums
        return ()

    def rule_donated_reuse(self) -> None:
        for scope in ast.walk(self.tree):
            if not isinstance(scope, (ast.FunctionDef,
                                      ast.AsyncFunctionDef)):
                continue
            # name -> donated positions, for jits bound in THIS scope
            donating: Dict[str, Tuple[int, ...]] = {}
            for node in ast.walk(scope):
                if isinstance(node, ast.Assign) and _is_jit_call(node.value):
                    nums = self._donated_positions(node.value)
                    if not nums:
                        continue
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            donating[t.id] = nums
            if not donating:
                continue
            for node in ast.walk(scope):
                if not isinstance(node, ast.Call):
                    continue
                name = _call_name(node)
                args = node.args
                if name in donating:
                    positions = donating[name]
                elif name in ("timed_call", "_timing.timed_call") \
                        and args and isinstance(args[0], ast.Name) \
                        and args[0].id in donating:
                    # timed_call(fn, *args) shifts positions by one
                    positions = tuple(p + 1
                                      for p in donating[args[0].id])
                else:
                    continue
                stmt = self.enclosing_stmt(node)
                rebound = {t.id for t in ast.walk(stmt)
                           if isinstance(t, ast.Name)
                           and isinstance(t.ctx, ast.Store)}
                for p in positions:
                    if p >= len(args) or not isinstance(args[p], ast.Name):
                        continue
                    donated = args[p].id
                    if donated in rebound:
                        continue        # pool = decode(params, pool) idiom
                    self._check_use_after(scope, stmt, node, donated)

    def _check_use_after(self, scope, stmt, call, name: str) -> None:
        end = (stmt.end_lineno, getattr(stmt, "end_col_offset", 0))
        events = []
        for n in ast.walk(scope):
            if isinstance(n, ast.Name) and n.id == name:
                pos = (n.lineno, n.col_offset)
                if pos > end:
                    events.append((pos, isinstance(n.ctx, ast.Store)))
        events.sort()
        if events and not events[0][1]:
            self.report(
                "R002", call,
                f"'{name}' was donated to the jit at line {call.lineno} "
                f"and read again at line {events[0][0][0]} without being "
                "rebound — its buffer is dead after the call "
                "(use-after-donate)")

    # -------------------------------------- R003: host ops in traced fns

    def _traced_fns(self) -> List[Tuple[ast.FunctionDef, Set[str]]]:
        """(fn def, static param names) for every function this module
        hands to jit / shard_map / pallas_call, by decorator or call."""
        out: Dict[ast.FunctionDef, Set[str]] = {}

        def statics(call: Optional[ast.Call], fn: ast.FunctionDef
                    ) -> Set[str]:
            s: Set[str] = set()
            if call is None:
                return s
            params = _fn_param_names(fn)
            for kw in call.keywords:
                if kw.arg == "static_argnames":
                    s |= set(_const_str_tuple(kw.value) or ())
                if kw.arg == "static_argnums":
                    for i in _const_int_tuple(kw.value) or ():
                        if 0 <= i < len(params):
                            s.add(params[i])
            return s

        for node in ast.walk(self.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    if _is_jit_call(dec) or (
                            _qualname(dec) in JIT_NAMES):
                        out.setdefault(node, set()).update(
                            statics(dec if isinstance(dec, ast.Call)
                                    else None, node))
                    elif isinstance(dec, ast.Call) \
                            and _call_name(dec) in PARTIAL_NAMES \
                            and dec.args \
                            and _qualname(dec.args[0]) in JIT_NAMES:
                        out.setdefault(node, set()).update(
                            statics(dec, node))
            if isinstance(node, ast.Call) \
                    and _call_name(node) in TRACE_WRAPPERS and node.args:
                fn = self.resolve_fn(node.args[0], node)
                if fn is not None:
                    out.setdefault(fn, set()).update(statics(node, fn))
        return [(fn, s) for fn, s in out.items()]

    def _tracer_test_hit(self, test: ast.AST, traced: Set[str]
                         ) -> Optional[str]:
        """Name of a traced param the `if` test branches on, or None.

        Host-decidable uses are exempt: isinstance()/len() calls,
        `is (not) None`, and static attributes (.shape/.ndim/.dtype...).
        """
        parent: Dict[ast.AST, ast.AST] = {}
        for n in ast.walk(test):
            for c in ast.iter_child_nodes(n):
                parent[c] = n
        for n in ast.walk(test):
            if not (isinstance(n, ast.Name) and n.id in traced
                    and isinstance(n.ctx, ast.Load)):
                continue
            ok = False
            cur, prev = parent.get(n), n
            while True:
                if isinstance(cur, ast.Attribute) \
                        and cur.attr in STATIC_ATTRS:
                    ok = True
                    break
                if isinstance(cur, ast.Call) \
                        and _call_name(cur) in ("isinstance", "len",
                                                "hasattr", "getattr",
                                                "type") \
                        and prev in cur.args:
                    ok = True
                    break
                if isinstance(cur, ast.Compare) and all(
                        isinstance(op, (ast.Is, ast.IsNot))
                        for op in cur.ops):
                    ok = True
                    break
                if cur is None or not isinstance(cur, ast.expr):
                    break
                prev, cur = cur, parent.get(cur)
            if not ok:
                return n.id
        return None

    def rule_traced_host_ops(self) -> None:
        for fn, static in self._traced_fns():
            traced = {p for p in _fn_param_names(fn) if p not in static}
            for node in ast.walk(fn):
                if isinstance(node, ast.Attribute) \
                        and isinstance(node.value, ast.Name) \
                        and node.value.id in ("np", "numpy"):
                    self.report(
                        "R003", node,
                        f"numpy op `{_qualname(node)}` inside traced "
                        f"function '{fn.name}' — host numpy silently "
                        "constant-folds at trace time; use jnp")
                if isinstance(node, (ast.If, ast.IfExp, ast.While)):
                    hit = self._tracer_test_hit(node.test, traced)
                    if hit is not None:
                        kind = {"If": "if", "IfExp": "conditional",
                                "While": "while"}[type(node).__name__]
                        self.report(
                            "R003", node,
                            f"Python `{kind}` on traced parameter "
                            f"'{hit}' inside '{fn.name}' — trace-time "
                            "branching bakes in one path (use jnp.where/"
                            "lax.cond, or mark the param static)")

    # --------------------------------------------- R006: bare serve clock

    def _serving_path_module(self) -> bool:
        """Serving-path modules own no clocks of their own: anything under
        launch/, or named *serving* / *scheduler*. The obs package (the
        clock's home) and benchmarks/_timing (its re-export) are exempt."""
        parts = self.path.parts
        stem = self.path.stem
        if "obs" in parts or stem in ("_timing", "clock"):
            return False
        return ("launch" in parts or "serving" in stem
                or "scheduler" in stem)

    def rule_serve_clock(self) -> None:
        from_time: Set[str] = set()
        for node in ast.walk(self.tree):
            if isinstance(node, ast.ImportFrom) and node.module == "time":
                for alias in node.names:
                    if alias.name in CLOCK_FNS:
                        from_time.add(alias.asname or alias.name)
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.Call):
                continue
            name = _call_name(node)
            if name is None:
                continue
            bare = (name in from_time
                    or (name.startswith("time.")
                        and name.split(".", 1)[1] in CLOCK_FNS))
            if bare:
                self.report(
                    "R006", node,
                    f"bare clock `{name}()` on a serving-path module — "
                    "take timestamps from repro.obs.clock (now / "
                    "timed_call / stopwatch) so metrics histograms, trace "
                    "spans and bench rows all measure with ONE clock")

    # ----------------------------------------- R004: static names/nums

    def _check_statics(self, call: ast.Call, fn: ast.FunctionDef) -> None:
        params = _fn_param_names(fn)
        npos = _fn_positional_count(fn)
        has_kwargs = fn.args.kwarg is not None
        for kw in call.keywords:
            if kw.arg == "static_argnames":
                for name in _const_str_tuple(kw.value) or ():
                    if name not in params and not has_kwargs:
                        self.report(
                            "R004", call,
                            f"static_argnames names '{name}' but "
                            f"'{fn.name}' has no such parameter "
                            f"(params: {', '.join(params)}) — jax only "
                            "errors lazily, so the typo silently leaves "
                            "the real argument traced")
            if kw.arg == "static_argnums":
                for i in _const_int_tuple(kw.value) or ():
                    if npos is not None and not -npos <= i < npos:
                        self.report(
                            "R004", call,
                            f"static_argnums {i} out of range for "
                            f"'{fn.name}' ({npos} positional params)")

    def rule_static_argnames(self) -> None:
        for node in ast.walk(self.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    if isinstance(dec, ast.Call) and (
                            _call_name(dec) in JIT_NAMES
                            or (_call_name(dec) in PARTIAL_NAMES
                                and dec.args
                                and _qualname(dec.args[0]) in JIT_NAMES)):
                        self._check_statics(dec, node)
            elif _is_jit_call(node) and node.args:
                fn = self.resolve_fn(node.args[0], node)
                if fn is not None:
                    self._check_statics(node, fn)

    # ---------------------------------------- R005: parity jit-vs-jit

    def rule_parity_jit_vs_jit(self) -> None:
        for scope in ast.walk(self.tree):
            if not isinstance(scope, (ast.FunctionDef,
                                      ast.AsyncFunctionDef)):
                continue
            # jitted-name -> eager fn name, within this test function
            jitted: Dict[str, str] = {}
            for node in ast.walk(scope):
                if isinstance(node, ast.Assign) \
                        and _is_jit_call(node.value) \
                        and node.value.args \
                        and isinstance(node.value.args[0], ast.Name):
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            jitted[t.id] = node.value.args[0].id
            if not jitted:
                continue
            eager_of = {v: k for k, v in jitted.items()}

            def origin(node: ast.AST,
                       var_origin: Dict[str, Tuple[str, str]]
                       ) -> Optional[Tuple[str, str]]:
                if isinstance(node, ast.Call):
                    name = _call_name(node)
                    if name in jitted:
                        return ("jit", jitted[name])
                    if name in eager_of:
                        return ("eager", name)
                if isinstance(node, ast.Name):
                    return var_origin.get(node.id)
                return None

            var_origin: Dict[str, Tuple[str, str]] = {}
            for stmt in ast.walk(scope):
                if isinstance(stmt, ast.Assign) \
                        and isinstance(stmt.value, ast.Call):
                    o = origin(stmt.value, {})
                    if o:
                        for t in stmt.targets:
                            if isinstance(t, ast.Name):
                                var_origin[t.id] = o
            for node in ast.walk(scope):
                if not (isinstance(node, ast.Call)
                        and len(node.args) >= 2):
                    continue
                name = _call_name(node)
                if name is None or not PARITY_FNS.search(name):
                    continue
                origins = [origin(a, var_origin) for a in node.args[:2]]
                kinds = {o for o in origins if o}
                fns = {o[1] for o in origins if o}
                if len(fns) == 1 and {k for k, _ in kinds} == {"jit",
                                                              "eager"}:
                    f = next(iter(fns))
                    self.report(
                        "R005", node,
                        f"parity assertion compares eager '{f}' against "
                        f"jit('{f}') — bitwise gates must be jit-vs-jit "
                        "(eager numerics differ from compiled numerics "
                        "without either being wrong)")


# ------------------------------------------------------------------ driver

def iter_py_files(paths: Sequence[str]) -> List[Path]:
    files: List[Path] = []
    for p in paths:
        path = Path(p)
        if path.is_dir():
            files.extend(sorted(path.rglob("*.py")))
        elif path.suffix == ".py":
            files.append(path)
    return files


def lint_paths(paths: Sequence[str]) -> List[Violation]:
    violations: List[Violation] = []
    for f in iter_py_files(paths):
        src = f.read_text()
        is_test = "tests" in f.parts or f.name.startswith("test_")
        try:
            linter = ModuleLinter(f, src, is_test=is_test)
        except SyntaxError as e:
            violations.append(Violation(str(f), e.lineno or 0, "R000",
                                        f"syntax error: {e.msg}"))
            continue
        violations.extend(linter.run())
    return violations


# ---------------------------------------------------------------- self-test

def _fixture_expected(src: str) -> Set[str]:
    exp: Set[str] = set()
    for line in src.splitlines():
        line = line.strip()
        if line.startswith("# lint-expect:"):
            spec = line.split(":", 1)[1].strip()
            if spec != "none":
                exp.update(r.strip() for r in spec.split(","))
        elif line and not line.startswith("#"):
            break
    return exp


def self_test() -> int:
    failures = 0
    fixture_dir = REPO / "tools" / "lint_fixtures"
    for f in sorted(fixture_dir.glob("*.py")):
        src = f.read_text()
        expected = _fixture_expected(src)
        is_test = "test" in f.stem
        got = {v.rule for v in ModuleLinter(f, src, is_test=is_test).run()}
        if got != expected:
            print(f"SELF-TEST FAIL {f.name}: expected {sorted(expected)} "
                  f"got {sorted(got)}")
            failures += 1
        else:
            print(f"self-test ok   {f.name}: {sorted(expected) or 'clean'}")

    # chip-IR verifier drive: the two historical packed-layout bugs must be
    # caught by name on hand-built corrupt artifacts (no chip compile, no
    # device work — plain arrays through the pure verifier passes)
    sys.path.insert(0, str(REPO / "src"))
    import numpy as np

    from repro.core.mapping import PackedPlan, Tile, TileSchedule
    from repro.core.verify import (ChipVerifyError, check_packed,
                                   check_schedule)

    def packed(**over):
        base = dict(
            layer="w", bk=2, bn=2, n_rows=6, n_cols=2,
            row_block=(0, 1, 2), col_block=(0, 0, 0), seq_slot=(0, 0, 0),
            n_passes=1, transpose=False, tile_slot=(0, 1, 2),
            out_slot=(0, 0, 0), out_col=(0,),
            gd_tiles=np.zeros((3, 2, 2), np.float32),
            inv_norm_tiles=np.zeros((3, 1, 2), np.float32),
            v_decr_tiles=np.zeros((3,), np.float32),
            denorm_tiles=np.zeros((3, 1, 2), np.float32))
        base.update(over)
        return PackedPlan(**base)

    check_packed(packed())        # the valid layout must pass

    def expect(label, invariant, fn):
        nonlocal failures
        try:
            fn()
        except ChipVerifyError as e:
            if e.invariant == invariant:
                print(f"self-test ok   verifier/{label}: caught "
                      f"[{e.stage}/{e.invariant}]")
                return
            print(f"SELF-TEST FAIL verifier/{label}: wrong invariant "
                  f"{e.invariant} (wanted {invariant})")
        else:
            print(f"SELF-TEST FAIL verifier/{label}: not caught")
        failures += 1

    # PR-2 bug class: output block 0 revisited NON-consecutively (slots
    # 0 and 2 with block 1 between) — every index is in bounds, only the
    # Pallas TPU VMEM-liveness precondition is violated: the revisit would
    # silently re-initialize the accumulator
    expect("pr2-nonconsecutive-run", "fused-runs",
           lambda: check_packed(packed(n_cols=4, col_block=(0, 1, 0),
                                       out_slot=(0, 1, 0),
                                       out_col=(0, 1, 0))))
    # historical pack_tiles bug: duplicated schedule index packs one tile
    # twice and silently drops another
    tiles = [Tile("w", 0, 0, 2, 2, core=0), Tile("w", 2, 0, 2, 2, core=1)]
    expect("duplicate-schedule-index", "permutation",
           lambda: check_schedule(
               tiles, TileSchedule(order=(0, 0), n_passes=1, pass_len=2)))

    if failures:
        print(f"\nself-test: {failures} failure(s)")
        return 1
    print("\nself-test: all checks passed")
    return 0


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("paths", nargs="*", default=["src", "tests"],
                    help="files or directories to lint (default: src tests)")
    ap.add_argument("--self-test", action="store_true",
                    help="check the linter against its fixtures and the "
                         "chip-IR verifier against known-bad artifacts")
    args = ap.parse_args(argv)
    if args.self_test:
        return self_test()
    violations = lint_paths(args.paths or ["src", "tests"])
    for v in violations:
        print(v)
    if violations:
        print(f"\n{len(violations)} lint violation(s)")
        return 1
    print(f"lint clean ({len(iter_py_files(args.paths))} files)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
