"""repro-lint: project-specific AST rules for the Catwalk repro tree.

The bit-exactness suite cannot see contract regressions — layouts that
silently replicate, host syncs smuggled into jit, Pallas specs that stop
matching the TPU tiling grid. These rules encode those contracts
statically (DESIGN.md §7.1):

  RPR001 private-jax          ``jax._src`` / ``jax.core.Tracer`` outside
                              ``sharding/compat.py``
  RPR003 host-leak-in-jit     host-side ``float()``/``int()``/``bool()``/
                              ``.item()``/``.tolist()``/``np.asarray`` or a
                              Python ``if``/``while`` on a value reachable
                              from the traced params of a function passed
                              to ``jax.jit`` / ``shard_map`` (conservative
                              intraprocedural taint walk)
  RPR004 pallas-lane          integer-literal last dim of a
                              ``pl.BlockSpec`` block shape that is not a
                              multiple of the 128-wide TPU lane
  RPR005 pallas-smem-order    SMEM scalar operand specs listed after
                              VMEM block specs in ``in_specs`` (the
                              kernels declare scalars first, always)
  RPR006 pallas-interpret-literal  ``interpret=<literal>`` on a
                              ``pallas_call`` (must route through
                              ``kernels/common.use_interpret``)
  RPR007 core-unplaced        a ``core/`` function taking both a
                              weights-like and a times-like operand that
                              neither pins its tensors via ``maybe_wsc``
                              nor (transitively) calls a function that
                              does, nor carries a ``# repro-lint:
                              unplaced`` annotation
  RPR008 raw-env              ``os.environ`` / ``os.getenv`` outside
                              ``kernels/common.py`` (strict parsing lives
                              there; ``dict(os.environ)`` snapshots are
                              structurally allowed)

Escape hatch: ``# repro-lint: allow[<slug>]`` on the flagged line or the
line directly above silences that rule there; ``# repro-lint: unplaced``
on (or directly above) a ``def`` line satisfies RPR007 — both are meant
to carry a short justification in the trailing text.

No jax import in this module: the CI ``analyze`` job runs it before
anything heavyweight.

Usage::

    python -m repro.analysis.lint src tests benchmarks examples
"""

from __future__ import annotations

import ast
import dataclasses
import pathlib
import re
import sys
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

# ---------------------------------------------------------------- rules

#: slug -> (code, one-line description)
RULES: Dict[str, Tuple[str, str]] = {
    "private-jax": ("RPR001", "jax._src / jax.core.Tracer outside "
                              "sharding/compat.py"),
    "host-leak-in-jit": ("RPR003", "host-side op on a jit-traced value"),
    "pallas-lane": ("RPR004", "BlockSpec literal last dim not a multiple "
                              "of the 128 TPU lane"),
    "pallas-smem-order": ("RPR005", "SMEM scalar spec declared after "
                                    "block specs in in_specs"),
    "pallas-interpret-literal": ("RPR006", "literal interpret= on "
                                           "pallas_call"),
    "core-unplaced": ("RPR007", "core/ function neither pins via "
                                "maybe_wsc nor is marked unplaced"),
    "raw-env": ("RPR008", "raw os.environ access outside "
                          "kernels/common.py"),
}

#: files exempt from a rule entirely (posix path suffix match)
PATH_EXEMPT: Dict[str, Tuple[str, ...]] = {
    "private-jax": ("sharding/compat.py",),
    "raw-env": ("kernels/common.py",),
}

#: RPR007 fires only on files with a ``core`` path component, for
#: top-level functions whose params hit BOTH operand classes.
_WEIGHTS_PARAMS = {"weights", "params"}
_TIMES_PARAMS = {"times", "volleys", "volley", "in_times", "x"}

_ALLOW_RE = re.compile(r"#\s*repro-lint:\s*allow\[([a-z-]+)\]")
_UNPLACED_RE = re.compile(r"#\s*repro-lint:\s*unplaced\b")


@dataclasses.dataclass(frozen=True)
class Violation:
    path: str
    line: int
    col: int
    slug: str
    message: str

    @property
    def code(self) -> str:
        return RULES[self.slug][0]

    def render(self) -> str:
        return (f"{self.path}:{self.line}:{self.col}: "
                f"{self.code} [{self.slug}] {self.message}")


# ----------------------------------------------------------- AST helpers

def _terminal_name(func: ast.expr) -> Optional[str]:
    """Callee name disregarding the module prefix: ``a.b.f`` -> ``f``."""
    if isinstance(func, ast.Name):
        return func.id
    if isinstance(func, ast.Attribute):
        return func.attr
    return None


def _dotted(node: ast.expr) -> Optional[str]:
    """``jax._src.core`` attribute chain -> dotted string, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _is_exempt(path: pathlib.PurePath, slug: str) -> bool:
    posix = path.as_posix()
    return any(posix.endswith(sfx) for sfx in PATH_EXEMPT.get(slug, ()))


def _const_str_tuple(node: ast.expr) -> Tuple[str, ...]:
    """static_argnames value -> names (string const or tuple/list of)."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for el in node.elts:
            if isinstance(el, ast.Constant) and isinstance(el.value, str):
                out.append(el.value)
        return tuple(out)
    return ()


def _const_int_tuple(node: ast.expr) -> Tuple[int, ...]:
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        return tuple(el.value for el in node.elts
                     if isinstance(el, ast.Constant)
                     and isinstance(el.value, int))
    return ()


# --------------------------------------------------- RPR003: taint walk

class _TaintWalk:
    """Conservative intraprocedural taint pass over one jit-traced fn.

    Taint = the non-static parameters. One forward pass over the body in
    source order; assignments propagate, ``.shape``/``.ndim``/``.dtype``/
    ``.size``/``len()`` launder (shapes are static under trace), and the
    host-sync sinks — ``float``/``int``/``bool``/``np.asarray``/
    ``np.array`` calls, ``.item()``/``.tolist()``, ``if``/``while`` tests
    (``is None`` checks excepted: tracers are never None) — flag."""

    _LAUNDER_ATTRS = {"shape", "ndim", "dtype", "size"}
    _CAST_SINKS = {"float", "int", "bool"}
    _NP_SINKS = {"asarray", "array"}
    _METHOD_SINKS = {"item", "tolist"}

    def __init__(self, fn: ast.AST, static_names: Set[str]):
        self.violations: List[Tuple[int, int, str]] = []
        args = fn.args if not isinstance(fn, ast.Lambda) else fn.args
        names = [a.arg for a in (args.posonlyargs + args.args
                                 + args.kwonlyargs)]
        self.tainted: Set[str] = {n for n in names if n not in static_names}
        if isinstance(fn, ast.Lambda):
            self._expr(fn.body)
        else:
            self._block(fn.body)

    # -- expression taint -------------------------------------------------
    def _expr(self, node: ast.expr) -> bool:
        """True when the expression's value may be a tracer."""
        if isinstance(node, ast.Name):
            return node.id in self.tainted
        if isinstance(node, ast.Attribute):
            if node.attr in self._LAUNDER_ATTRS:
                self._expr(node.value)
                return False
            return self._expr(node.value)
        if isinstance(node, ast.Call):
            return self._call(node)
        if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
            return any(self._expr(e) for e in node.elts)
        if isinstance(node, ast.Dict):
            return any(self._expr(e) for e in
                       list(node.keys) + list(node.values) if e is not None)
        if isinstance(node, ast.BinOp):
            lt = self._expr(node.left)
            return self._expr(node.right) or lt
        if isinstance(node, ast.UnaryOp):
            return self._expr(node.operand)
        if isinstance(node, ast.BoolOp):
            return any([self._expr(v) for v in node.values])
        if isinstance(node, ast.Compare):
            t = self._expr(node.left)
            return any([self._expr(c) for c in node.comparators]) or t
        if isinstance(node, ast.Subscript):
            self._expr(node.slice)
            return self._expr(node.value)
        if isinstance(node, ast.IfExp):
            self._expr(node.test)
            a = self._expr(node.body)
            return self._expr(node.orelse) or a
        if isinstance(node, ast.Starred):
            return self._expr(node.value)
        if isinstance(node, ast.JoinedStr):
            return False
        if isinstance(node, ast.Lambda):
            return False        # deferred body: not executed at trace time
        if isinstance(node, (ast.ListComp, ast.SetComp, ast.GeneratorExp,
                             ast.DictComp)):
            # comprehensions over static ranges are idiomatic in kernels;
            # taint of the element expression propagates
            for gen in node.generators:
                self._expr(gen.iter)
            if isinstance(node, ast.DictComp):
                return self._expr(node.key) or self._expr(node.value)
            return self._expr(node.elt)
        if isinstance(node, ast.Constant):
            return False
        return False

    def _call(self, node: ast.Call) -> bool:
        name = _terminal_name(node.func)
        arg_taint = [self._expr(a) for a in node.args]
        kw_taint = [self._expr(k.value) for k in node.keywords]
        any_taint = any(arg_taint) or any(kw_taint)
        if isinstance(node.func, ast.Name) and name in self._CAST_SINKS \
                and any(arg_taint):
            self._flag(node, f"host {name}() on a traced value")
            return False
        if name == "len":
            return False
        if name in self._NP_SINKS and isinstance(node.func, ast.Attribute):
            root = _dotted(node.func) or ""
            if root.startswith(("np.", "numpy.")) and any(arg_taint):
                self._flag(node, f"host {root}() on a traced value")
                return False
        if name in self._METHOD_SINKS and isinstance(node.func,
                                                     ast.Attribute):
            if self._expr(node.func.value):
                self._flag(node, f"host .{name}() on a traced value")
                return False
        if isinstance(node.func, ast.Attribute):
            # method calls on a traced receiver (x.mean(), x.reshape(...))
            # return traced values; laundering attrs are handled above
            return self._expr(node.func.value) or any_taint
        return any_taint

    # -- statements -------------------------------------------------------
    def _block(self, body: Sequence[ast.stmt]) -> None:
        for st in body:
            self._stmt(st)

    def _stmt(self, st: ast.stmt) -> None:
        if isinstance(st, ast.Assign):
            t = self._expr(st.value)
            for tgt in st.targets:
                self._bind(tgt, t)
        elif isinstance(st, ast.AnnAssign):
            if st.value is not None:
                self._bind(st.target, self._expr(st.value))
        elif isinstance(st, ast.AugAssign):
            t = self._expr(st.value) or self._expr(st.target)
            self._bind(st.target, t)
        elif isinstance(st, (ast.If, ast.While)):
            if not self._is_none_test(st.test) and self._expr(st.test):
                kind = "if" if isinstance(st, ast.If) else "while"
                self._flag(st, f"Python `{kind}` on a traced value "
                               "(host sync; use lax.cond/jnp.where)")
            self._block(st.body)
            self._block(st.orelse)
        elif isinstance(st, ast.For):
            self._expr(st.iter)
            self._bind(st.target, False)
            self._block(st.body)
            self._block(st.orelse)
        elif isinstance(st, (ast.Return, ast.Expr)):
            if st.value is not None:
                self._expr(st.value)
        elif isinstance(st, ast.With):
            for item in st.items:
                self._expr(item.context_expr)
            self._block(st.body)
        elif isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef)):
            pass        # nested defs are analyzed when themselves jitted
        elif isinstance(st, ast.Assert):
            # asserts on traced values are their own host sync, but the
            # tree-wide convention is shape asserts (laundered) — taint
            # only flags via the expression sinks
            self._expr(st.test)
        elif isinstance(st, (ast.Raise,)):
            if st.exc is not None:
                self._expr(st.exc)

    def _bind(self, tgt: ast.expr, tainted: bool) -> None:
        if isinstance(tgt, ast.Name):
            if tainted:
                self.tainted.add(tgt.id)
            else:
                self.tainted.discard(tgt.id)
        elif isinstance(tgt, (ast.Tuple, ast.List)):
            for el in tgt.elts:
                self._bind(el, tainted)
        elif isinstance(tgt, ast.Starred):
            self._bind(tgt.value, tainted)
        # subscript/attribute stores: no name rebinding

    @staticmethod
    def _is_none_test(test: ast.expr) -> bool:
        """``x is None`` / ``x is not None`` (tracers are never None)."""
        if isinstance(test, ast.UnaryOp) and isinstance(test.op, ast.Not):
            return _TaintWalk._is_none_test(test.operand)
        if isinstance(test, ast.BoolOp):
            return all(_TaintWalk._is_none_test(v) for v in test.values)
        return (isinstance(test, ast.Compare)
                and len(test.ops) == 1
                and isinstance(test.ops[0], (ast.Is, ast.IsNot))
                and isinstance(test.comparators[0], ast.Constant)
                and test.comparators[0].value is None)

    def _flag(self, node: ast.AST, msg: str) -> None:
        self.violations.append((node.lineno, node.col_offset, msg))


def _jit_static_names(call: ast.Call, fn: ast.AST) -> Set[str]:
    """Names excluded from tracing by static_argnames/static_argnums."""
    names: Set[str] = set()
    argnums: Tuple[int, ...] = ()
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            names |= set(_const_str_tuple(kw.value))
        elif kw.arg == "static_argnums":
            argnums = _const_int_tuple(kw.value)
    if argnums and not isinstance(fn, ast.Lambda):
        pos = fn.args.posonlyargs + fn.args.args
        for i in argnums:
            if 0 <= i < len(pos):
                names.add(pos[i].arg)
    return names


class _JitSiteFinder(ast.NodeVisitor):
    """Collect (fn-node, static-names) for functions handed to jit or
    shard_map — decorator forms and direct call forms with a resolvable
    local def / lambda argument. Call-expression arguments stay
    unanalyzed (conservative: no false positives on wrappers)."""

    _JIT_NAMES = {"jit", "shard_map"}

    def __init__(self, tree: ast.Module):
        self.sites: List[Tuple[ast.AST, Set[str]]] = []
        #: every def in the module by name (incl. nested), for resolving
        #: ``jax.jit(fn, ...)`` call-form references
        self.defs: Dict[str, List[ast.AST]] = {}
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.defs.setdefault(node.name, []).append(node)
        self.visit(tree)

    def _is_jit_ref(self, func: ast.expr) -> bool:
        name = _terminal_name(func)
        if name not in self._JIT_NAMES:
            return False
        dotted = _dotted(func)
        if dotted is None:
            return True                     # bare jit/shard_map import
        root = dotted.split(".")[0]
        return root in ("jax", "compat", "functools") or dotted in (
            "jax.jit", "jax.experimental.shard_map.shard_map")

    def _unwrap_partial(self, call: ast.Call) -> Optional[ast.Call]:
        """``functools.partial(jax.jit, ...)`` -> the inner jit ref as a
        synthetic call carrying partial's keywords."""
        if _terminal_name(call.func) == "partial" and call.args:
            inner = call.args[0]
            if self._is_jit_ref(inner):
                synth = ast.Call(func=inner, args=call.args[1:],
                                 keywords=call.keywords)
                return synth
        return None

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        for dec in node.decorator_list:
            if isinstance(dec, (ast.Name, ast.Attribute)) \
                    and self._is_jit_ref(dec):
                self.sites.append((node, set()))
            elif isinstance(dec, ast.Call):
                call = dec if self._is_jit_ref(dec.func) \
                    else self._unwrap_partial(dec)
                if call is not None:
                    self.sites.append((node, _jit_static_names(call, node)))
        self.generic_visit(node)

    visit_AsyncFunctionDef = visit_FunctionDef  # type: ignore[assignment]

    def visit_Call(self, node: ast.Call) -> None:
        call: Optional[ast.Call] = None
        if self._is_jit_ref(node.func):
            call = node
        else:
            call = self._unwrap_partial(node)
        if call is not None and call.args:
            target = call.args[0]
            if isinstance(target, ast.Lambda):
                self.sites.append(
                    (target, _jit_static_names(call, target)))
            elif isinstance(target, ast.Name):
                for fn in self.defs.get(target.id, ()):
                    self.sites.append((fn, _jit_static_names(call, fn)))
        self.generic_visit(node)


# ------------------------------------------------------------ file lint

class _FileLint:
    def __init__(self, path: pathlib.Path, source: str):
        self.path = path
        self.posix = pathlib.PurePath(path).as_posix()
        self.source = source
        self.lines = source.splitlines()
        self.tree = ast.parse(source, filename=str(path))
        self.violations: List[Violation] = []
        #: top-level functions that call maybe_wsc directly (RPR007 seed)
        self.pinning: Set[str] = set()
        #: top-level fn name -> terminal names it calls (RPR007 edges)
        self.calls: Dict[str, Set[str]] = {}
        #: RPR007 candidates awaiting the cross-file fixpoint
        self.unplaced_candidates: List[Tuple[str, int, int]] = []

    # -- annotation escape hatch ------------------------------------------
    def _allowed(self, line: int, slug: str) -> bool:
        for ln in (line, line - 1):
            if 1 <= ln <= len(self.lines):
                m = _ALLOW_RE.search(self.lines[ln - 1])
                if m and m.group(1) == slug:
                    return True
        return False

    def _marked_unplaced(self, line: int) -> bool:
        for ln in (line, line - 1):
            if 1 <= ln <= len(self.lines) \
                    and _UNPLACED_RE.search(self.lines[ln - 1]):
                return True
        return False

    def _flag(self, slug: str, node: ast.AST, message: str) -> None:
        if _is_exempt(pathlib.PurePath(self.posix), slug):
            return
        if self._allowed(node.lineno, slug):
            return
        self.violations.append(Violation(
            str(self.path), node.lineno, node.col_offset + 1, slug,
            message))

    # -- rules ------------------------------------------------------------
    def run(self) -> None:
        self._rule_private_jax()
        self._rule_host_leak()
        self._rule_pallas()
        self._rule_raw_env()
        self._collect_unplaced()

    def _rule_private_jax(self) -> None:
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    if alias.name.startswith("jax._src"):
                        self._flag("private-jax", node,
                                   f"import of private `{alias.name}`")
            elif isinstance(node, ast.ImportFrom):
                mod = node.module or ""
                if mod.startswith("jax._src"):
                    self._flag("private-jax", node,
                               f"import from private `{mod}`")
                elif mod == "jax.core" and any(
                        a.name == "Tracer" for a in node.names):
                    self._flag("private-jax", node,
                               "jax.core.Tracer import (use "
                               "sharding.compat.is_tracer)")
            elif isinstance(node, ast.Attribute):
                dotted = _dotted(node)
                if dotted and (dotted.startswith("jax._src")
                               or dotted == "jax.core.Tracer"):
                    self._flag("private-jax", node,
                               f"`{dotted}` access (route through "
                               "sharding/compat.py)")

    def _rule_host_leak(self) -> None:
        finder = _JitSiteFinder(self.tree)
        seen: Set[Tuple[int, int]] = set()
        for fn, static in finder.sites:
            key = (fn.lineno, fn.col_offset)
            if key in seen:
                continue
            seen.add(key)
            walk = _TaintWalk(fn, static)
            for line, col, msg in walk.violations:
                node = ast.Module(body=[], type_ignores=[])
                node.lineno, node.col_offset = line, col  # type: ignore
                self._flag("host-leak-in-jit", node, msg)

    def _rule_pallas(self) -> None:
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.Call):
                continue
            name = _terminal_name(node.func)
            if name == "BlockSpec":
                self._check_blockspec(node)
            elif name == "pallas_call":
                self._check_pallas_call(node)

    def _check_blockspec(self, node: ast.Call) -> None:
        if not node.args:
            return
        shape = node.args[0]
        if isinstance(shape, (ast.Tuple, ast.List)) and shape.elts:
            last = shape.elts[-1]
            if isinstance(last, ast.Constant) \
                    and isinstance(last.value, int) \
                    and last.value % 128 != 0:
                self._flag("pallas-lane", node,
                           f"block shape ends in literal {last.value}; "
                           "the TPU lane quantum is 128 — use a Name "
                           "bound to a lane-aligned width")

    @staticmethod
    def _is_smem_spec(node: ast.expr) -> bool:
        if not isinstance(node, ast.Call):
            return False
        name = _terminal_name(node.func) or ""
        if "smem" in name.lower():
            return True
        for kw in node.keywords:
            if kw.arg == "memory_space":
                dotted = _dotted(kw.value) or ""
                return "SMEM" in dotted or "smem" in dotted
        return False

    def _check_pallas_call(self, node: ast.Call) -> None:
        for kw in node.keywords:
            if kw.arg == "interpret" and isinstance(kw.value, ast.Constant):
                self._flag("pallas-interpret-literal", node,
                           f"interpret={kw.value.value!r} literal; use "
                           "kernels.common.use_interpret()")
            if kw.arg == "in_specs" and isinstance(kw.value,
                                                   (ast.List, ast.Tuple)):
                seen_block = False
                for el in kw.value.elts:
                    if self._is_smem_spec(el):
                        if seen_block:
                            self._flag("pallas-smem-order", el,
                                       "SMEM scalar spec after block "
                                       "specs; scalars go first so the "
                                       "kernel reads them before the grid "
                                       "loop")
                    elif isinstance(el, ast.Call):
                        seen_block = True

    def _rule_raw_env(self) -> None:
        dict_wrapped: Set[Tuple[int, int]] = set()
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Name) \
                    and node.func.id == "dict" and len(node.args) == 1:
                arg = node.args[0]
                if _dotted(arg) == "os.environ":
                    dict_wrapped.add((arg.lineno, arg.col_offset))
        for node in ast.walk(self.tree):
            dotted = _dotted(node) if isinstance(node, ast.Attribute) \
                else None
            if dotted == "os.environ":
                if (node.lineno, node.col_offset) in dict_wrapped:
                    continue
                self._flag("raw-env", node,
                           "raw os.environ access; parse env through "
                           "kernels/common.py helpers (strict 0/1 etc.)")
            elif isinstance(node, ast.Call) \
                    and _dotted(node.func) == "os.getenv":
                self._flag("raw-env", node,
                           "os.getenv; parse env through "
                           "kernels/common.py helpers")

    # -- RPR007 (needs the cross-file fixpoint) ---------------------------
    def _collect_unplaced(self) -> None:
        in_core = "core" in pathlib.PurePath(self.posix).parts
        for node in self.tree.body:
            if not isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                continue
            called: Set[str] = set()
            pins = False
            for sub in ast.walk(node):
                if isinstance(sub, ast.Call):
                    name = _terminal_name(sub.func)
                    if name == "maybe_wsc":
                        pins = True
                    elif name:
                        called.add(name)
            self.calls[node.name] = called
            if pins:
                self.pinning.add(node.name)
            if not in_core or _is_exempt(pathlib.PurePath(self.posix),
                                         "core-unplaced"):
                continue
            params = {a.arg for a in (node.args.posonlyargs
                                      + node.args.args
                                      + node.args.kwonlyargs)}
            if not (params & _WEIGHTS_PARAMS and params & _TIMES_PARAMS):
                continue
            if pins or self._marked_unplaced(node.lineno) \
                    or self._allowed(node.lineno, "core-unplaced"):
                continue
            self.unplaced_candidates.append(
                (node.name, node.lineno, node.col_offset + 1))


def _resolve_unplaced(files: Sequence[_FileLint]) -> None:
    """Cross-file fixpoint: a function is credited when it (transitively)
    calls, by terminal name, any function that pins via maybe_wsc."""
    pinning: Set[str] = set()
    calls: Dict[str, Set[str]] = {}
    for f in files:
        pinning |= f.pinning
        for name, callees in f.calls.items():
            calls.setdefault(name, set()).update(callees)
    changed = True
    while changed:
        changed = False
        for name, callees in calls.items():
            if name not in pinning and callees & pinning:
                pinning.add(name)
                changed = True
    for f in files:
        for name, line, col in f.unplaced_candidates:
            if name in pinning:
                continue
            node = ast.Module(body=[], type_ignores=[])
            node.lineno, node.col_offset = line, col - 1  # type: ignore
            f.violations.append(Violation(
                str(f.path), line, col, "core-unplaced",
                f"core function `{name}` takes mesh-placed operands but "
                "neither pins outputs via maybe_wsc (directly or "
                "transitively) nor carries `# repro-lint: unplaced`"))


# ----------------------------------------------------------- public API

#: directories never entered during a walk (corpus files are linted only
#: when passed explicitly — the self-test does exactly that)
SKIP_DIRS = {"lint_corpus", "__pycache__", ".git", ".ruff_cache",
             ".pytest_cache"}


def iter_py_files(paths: Iterable[str]) -> List[pathlib.Path]:
    out: List[pathlib.Path] = []
    for p in paths:
        path = pathlib.Path(p)
        if path.is_file():
            out.append(path)
        elif path.is_dir():
            for f in sorted(path.rglob("*.py")):
                if not SKIP_DIRS & set(f.parts):
                    out.append(f)
    return out


def lint_source(source: str, path: str = "<string>") -> List[Violation]:
    """Lint one source string (corpus self-tests use this)."""
    fl = _FileLint(pathlib.Path(path), source)
    fl.run()
    _resolve_unplaced([fl])
    return sorted(fl.violations, key=lambda v: (v.line, v.col))


def lint_paths(paths: Iterable[str]) -> List[Violation]:
    files: List[_FileLint] = []
    for f in iter_py_files(paths):
        try:
            src = f.read_text()
            fl = _FileLint(f, src)
        except (SyntaxError, UnicodeDecodeError) as e:
            v = Violation(str(f), getattr(e, "lineno", 1) or 1, 1,
                          "private-jax", f"unparseable: {e}")
            # surface parse failures without inventing a rule slot
            print(v.render(), file=sys.stderr)
            continue
        fl.run()
        files.append(fl)
    _resolve_unplaced(files)
    out: List[Violation] = []
    for fl in files:
        out.extend(fl.violations)
    return sorted(out, key=lambda v: (v.path, v.line, v.col))


def main(argv: Optional[Sequence[str]] = None) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis.lint",
        description="repro-lint: project-specific static rules "
                    "(DESIGN.md §7.1)")
    ap.add_argument("paths", nargs="*", default=["src", "tests"],
                    help="files or directories (default: src tests)")
    ap.add_argument("--list-rules", action="store_true",
                    help="print the rule table and exit")
    args = ap.parse_args(argv)
    if args.list_rules:
        for slug, (code, desc) in RULES.items():
            print(f"{code}  {slug:26s} {desc}")
        return 0
    violations = lint_paths(args.paths or ["src", "tests"])
    for v in violations:
        print(v.render())
    n_files = len(iter_py_files(args.paths or ["src", "tests"]))
    if violations:
        print(f"repro-lint: {len(violations)} violation(s) in "
              f"{n_files} file(s)", file=sys.stderr)
        return 1
    print(f"repro-lint: clean ({n_files} file(s))")
    return 0


if __name__ == "__main__":
    sys.exit(main())
