"""Pass 3: JAX hazard linter — jit-unsafe patterns in the checker stack
and the packed op encoding.

Scope: ``jepsen_tpu/checker/*.py`` and ``jepsen_tpu/ops/encode.py`` —
the files whose functions end up inside ``jax.jit`` traces. Three
hazard classes, all of which historically cost device time to discover:

==========================  ========  =================================
rule                        severity  what it catches
==========================  ========  =================================
JAX-HOST-SYNC               error     host-sync calls inside a traced
                                      body (``.item()``, ``.tolist()``,
                                      ``np.*`` math, ``print``,
                                      ``.block_until_ready()``) — these
                                      either poison the trace or
                                      silently serialize the device
JAX-HOST-CAST               warning   ``float()/int()/bool()`` on a
                                      non-literal inside a traced body
                                      (a concretization point)
JAX-UNHASHABLE-STATIC       error     a list/dict/set literal passed to
                                      an ``lru_cache``'d jit factory
                                      (``_jit_segment``/``_jit_batch``):
                                      unhashable keys
                                      raise — or, worse, near-miss keys
                                      defeat the compile cache
JAX-INT32-OVERFLOW          error     a compile-time integer outside
                                      the target width in an
                                      ``int32``/``uint32`` cast (the
                                      packed encoding is int32
                                      columns). Folds literals AND
                                      module-level named constants —
                                      including names imported from
                                      other repo modules (e.g. widths
                                      from ``ops/encode.py``) — so a
                                      shift or cast routed through a
                                      named width no longer escapes
JAX-SHIFT-WIDTH             error     a constant shift of >= 32 bits (a
                                      32-bit lane shifts by the count
                                      mod 32 on TPU — silent garbage);
                                      same named-constant folding
JAX-TRACE-IN-JIT            error     an ``obs.span``/``obs.event``/
                                      ``observatory.publish`` or
                                      host-clock call
                                      (``time.monotonic``/
                                      ``perf_counter``/...) inside a
                                      traced body: it would time the
                                      TRACE, not the device — device
                                      timing must be measured on the
                                      host around
                                      ``block_until_ready``. The ONE
                                      sanctioned progress-publishing
                                      site (host-side, between
                                      segments) is carried in
                                      :data:`TRACE_IN_JIT_ALLOWLIST`.
==========================  ========  =================================

Traced-body detection is lexical, not dataflow: a function is traced if
it is (a) decorated with ``jit``/``jax.jit``, (b) passed by name to
``jax.jit``, (c) passed by name to ``lax.while_loop``/``lax.scan``/
``lax.cond``/``vmap``/``pmap``, or (d) lexically nested inside one of
those. Host-side *builders* that construct constants with numpy before
returning a traced closure are deliberately not flagged — trace-time
numpy on static data is legitimate and idiomatic.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set

from jepsen_tpu.analysis import ERROR, Finding, WARNING
from jepsen_tpu.analysis.astutil import (dotted, parse_file, scope_map,
                                         snippet)

#: Call targets that hand a function into a traced context (the passed
#: function arguments become traced bodies).
_TRACE_TAKERS = {
    "jax.jit": None, "jit": None,
    "lax.while_loop": None, "jax.lax.while_loop": None,
    "lax.scan": None, "jax.lax.scan": None,
    "lax.cond": None, "jax.lax.cond": None,
    "lax.fori_loop": None, "jax.lax.fori_loop": None,
    "jax.vmap": None, "vmap": None,
    "jax.pmap": None, "pmap": None,
}

#: Method calls that force a device->host sync (or break tracing).
_SYNC_METHODS = ("item", "tolist", "block_until_ready")

#: numpy module aliases whose calls inside a traced body are hazards.
_NP_NAMES = ("np", "numpy")

#: Host-clock attributes: called on a time-module alias inside a traced
#: body they run at TRACE time (once, on host), so the recorded numbers
#: are garbage — and a span context manager would additionally close
#: around the trace, not the execution. The obs discipline
#: (doc/observability.md): measure on the host around
#: ``block_until_ready``.
_CLOCK_ATTRS = ("monotonic", "monotonic_ns", "perf_counter",
                "perf_counter_ns", "time", "time_ns", "process_time")
_TIME_ALIASES = ("time", "_time", "_t", "_hosttime")

#: Span/event/progress call names (module-level helpers, tracer methods
#: or observatory publishers from jepsen_tpu.obs) that must never
#: appear inside a traced body.
_OBS_ALIASES = ("obs", "trace", "tracer", "_tracer", "obs_trace",
                "observatory", "obs_observatory")
_OBS_ATTRS = ("span", "event", "publish", "begin", "finish")

#: JAX-TRACE-IN-JIT allowlist: (repo-relative path, enclosing-qualname
#: prefix) pairs where the rule is suppressed. The ONE sanctioned
#: progress-publishing site is the resilience supervisor's segment
#: loop — host code that runs BETWEEN device segments
#: (doc/observability.md); everything else that wants to publish from
#: near a traced body must restructure, not extend this list.
TRACE_IN_JIT_ALLOWLIST = (
    ("jepsen_tpu/resilience.py", "_supervised_check_packed"),
)


def _trace_in_jit_allowed(path: str, scope: str) -> bool:
    return any(path == p and (scope == q or scope.startswith(q + "."))
               for p, q in TRACE_IN_JIT_ALLOWLIST)

INT32_MIN, INT32_MAX = -(2 ** 31), 2 ** 31 - 1
UINT32_MAX = 2 ** 32 - 1


def _const_int(node: ast.AST, resolve=None) -> Optional[int]:
    """Fold a compile-time integer expression: literals combined with
    + - * ** << >> & | and unary +/- (e.g. ``2**31 - 1``), plus — when
    ``resolve`` is given — module-level named constants (``resolve``
    maps a name to its folded int, or None; shadowed names must come
    back None from the resolver)."""
    if isinstance(node, ast.Constant):
        v = node.value
        return v if isinstance(v, int) and not isinstance(v, bool) \
            else None
    if isinstance(node, ast.Name) and resolve is not None:
        v = resolve(node.id)
        return v if isinstance(v, int) and not isinstance(v, bool) \
            else None
    if isinstance(node, ast.UnaryOp) and isinstance(
            node.op, (ast.USub, ast.UAdd)):
        v = _const_int(node.operand, resolve)
        if v is None:
            return None
        return -v if isinstance(node.op, ast.USub) else v
    if isinstance(node, ast.BinOp):
        left = _const_int(node.left, resolve)
        right = _const_int(node.right, resolve)
        if left is None or right is None:
            return None
        op = node.op
        try:
            if isinstance(op, ast.Add):
                return left + right
            if isinstance(op, ast.Sub):
                return left - right
            if isinstance(op, ast.Mult):
                return left * right
            if isinstance(op, ast.Pow) and 0 <= right <= 128:
                return left ** right
            if isinstance(op, ast.LShift) and 0 <= right <= 128:
                return left << right
            if isinstance(op, ast.RShift) and 0 <= right <= 128:
                return left >> right
            if isinstance(op, ast.BitAnd):
                return left & right
            if isinstance(op, ast.BitOr):
                return left | right
        except (OverflowError, ValueError):
            return None
    return None


# ---------------------------------------------------------------------------
# Named-constant environment: module-level NAME = <int expr> bindings,
# including names imported from other repo modules (depth-limited), so
# a width constant defined in ops/encode.py and shifted in checker code
# no longer escapes the overflow/shift rules.
# ---------------------------------------------------------------------------

#: Calls folded as identity when building the environment: a module
#: constant defined as np.int32(2**31 - 1) (e.g. encode.RET_INF) is a
#: compile-time width too.
_CONST_CASTS = ("int", "int32", "uint32", "int64", "uint64")

#: Import-resolution depth limit (A imports from B imports from C stops
#: here) — enough for the real width chains, bounded against cycles.
_ENV_MAX_DEPTH = 2

#: abspath -> folded module env (memoized per process; the repo scan
#: lints many files importing the same constants module).
_ENV_CACHE: Dict[str, Dict[str, int]] = {}


def _module_file(module: str, root: Optional[str]) -> Optional[str]:
    """Best-effort source path of an absolute dotted module inside the
    repo root (package __init__ or plain module); None otherwise."""
    import os
    if not root or not module:
        return None
    base = os.path.join(root, *module.split("."))
    for cand in (base + ".py", os.path.join(base, "__init__.py")):
        if os.path.exists(cand):
            return cand
    return None


def _fold_binding(value: ast.AST, env: Dict[str, int]) -> Optional[int]:
    v = _const_int(value, env.get)
    if v is None and isinstance(value, ast.Call) and len(value.args) == 1:
        tail = dotted(value.func).rsplit(".", 1)[-1]
        if tail in _CONST_CASTS:
            v = _const_int(value.args[0], env.get)
    return v


def _module_env(tree: ast.Module, root: Optional[str],
                depth: int = 0) -> Dict[str, int]:
    """Fold the module's top-level integer constants to a name -> value
    map. Names rebound at module level are ambiguous and dropped;
    ``from x import NAME`` pulls folded constants out of repo-local
    modules up to _ENV_MAX_DEPTH."""
    env: Dict[str, int] = {}
    if depth < _ENV_MAX_DEPTH:
        for node in tree.body:
            if isinstance(node, ast.ImportFrom) and node.module \
                    and not node.level:
                src = _module_file(node.module, root)
                if src is None:
                    continue
                sub = _file_env(src, root, depth + 1)
                for alias in node.names:
                    if alias.name in sub:
                        env[alias.asname or alias.name] = sub[alias.name]
    assigns = []
    counts: Dict[str, int] = {}
    for node in tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name):
            name = node.targets[0].id
            assigns.append((name, node.value))
            counts[name] = counts.get(name, 0) + 1
        elif isinstance(node, ast.AnnAssign) \
                and isinstance(node.target, ast.Name) \
                and node.value is not None:
            assigns.append((node.target.id, node.value))
            counts[node.target.id] = counts.get(node.target.id, 0) + 1
    changed = True
    while changed:                 # constants referencing constants
        changed = False
        for name, value in assigns:
            if counts.get(name, 0) > 1:
                continue
            v = _fold_binding(value, env)
            if v is not None and env.get(name) != v:
                env[name] = v
                changed = True
    return env


def _file_env(path: str, root: Optional[str], depth: int = 0
              ) -> Dict[str, int]:
    import os
    key = os.path.abspath(path)
    if key in _ENV_CACHE:
        return _ENV_CACHE[key]
    _ENV_CACHE[key] = {}           # cycle guard before recursing
    tree, err, _ = parse_file(path, root)
    if tree is not None:
        _ENV_CACHE[key] = _module_env(tree, root, depth)
    return _ENV_CACHE[key]


def _local_names(fn: ast.AST) -> Set[str]:
    """Names bound directly inside one function scope (args and every
    assignment form), NOT descending into nested functions — a nested
    def's locals don't shadow its enclosing scope."""
    names: Set[str] = set()
    args = getattr(fn, "args", None)
    if args is not None:
        for a in (list(args.posonlyargs) + list(args.args)
                  + list(args.kwonlyargs)):
            names.add(a.arg)
        if args.vararg:
            names.add(args.vararg.arg)
        if args.kwarg:
            names.add(args.kwarg.arg)

    def targets(t):
        if isinstance(t, ast.Name):
            names.add(t.id)
        elif isinstance(t, (ast.Tuple, ast.List)):
            for e in t.elts:
                targets(e)
        elif isinstance(t, ast.Starred):
            targets(t.value)

    body = getattr(fn, "body", None)
    if isinstance(body, ast.AST):          # lambda: body is one expr
        stack = [body]
    else:
        stack = list(body or [])
    while stack:
        n = stack.pop()
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                          ast.ClassDef)):
            names.add(n.name)
            continue
        if isinstance(n, ast.Lambda):
            continue
        if isinstance(n, ast.Assign):
            for t in n.targets:
                targets(t)
        elif isinstance(n, (ast.AugAssign, ast.AnnAssign)):
            targets(n.target)
        elif isinstance(n, (ast.For, ast.AsyncFor)):
            targets(n.target)
        elif isinstance(n, ast.NamedExpr):
            targets(n.target)
        elif isinstance(n, (ast.With, ast.AsyncWith)):
            for item in n.items:
                if item.optional_vars is not None:
                    targets(item.optional_vars)
        elif isinstance(n, (ast.ListComp, ast.SetComp, ast.DictComp,
                            ast.GeneratorExp)):
            for gen in n.generators:
                targets(gen.target)
        stack.extend(ast.iter_child_nodes(n))
    return names


def _shadow_sets(tree: ast.Module) -> Dict[int, frozenset]:
    """id(node) -> names shadowed at that node by enclosing function
    scopes (a local ``W`` must not fold as the module's ``W``)."""
    out: Dict[int, frozenset] = {}

    def walk(node: ast.AST, inherited: frozenset) -> None:
        for child in ast.iter_child_nodes(node):
            out[id(child)] = inherited
            inh = inherited
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.Lambda)):
                inh = inherited | frozenset(_local_names(child))
            walk(child, inh)

    walk(tree, frozenset())
    return out


class _Regions(ast.NodeVisitor):
    """Collect the traced-body function set.

    Two root flavors with different closure behavior: *loop roots*
    (while_loop/scan/cond/vmap bodies) execute per traced step, so
    helpers they call by name are traced too and the region closes over
    the call graph. *jit roots* (functions handed to ``jax.jit``) are
    scanned directly but do NOT seed the call closure: a jitted wrapper
    commonly calls a host-side *builder* that precomputes numpy
    constants before returning the traced closure, and flagging builder
    numpy would be noise (trace-time numpy on static data is idiom)."""

    def __init__(self):
        self.defs: Dict[str, List[ast.AST]] = {}
        self.jit_roots: Set[str] = set()
        self.loop_roots: Set[str] = set()

    def visit_FunctionDef(self, node):
        self.defs.setdefault(node.name, []).append(node)
        for dec in node.decorator_list:
            d = dotted(dec.func) if isinstance(dec, ast.Call) \
                else dotted(dec)
            if d in ("jit", "jax.jit") or d.endswith(".jit"):
                self.jit_roots.add(node.name)
        self.generic_visit(node)

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Call(self, node):
        name = dotted(node.func)
        if name in _TRACE_TAKERS:
            dest = (self.jit_roots if name.endswith("jit")
                    else self.loop_roots)
            for arg in list(node.args) + [kw.value
                                          for kw in node.keywords]:
                if isinstance(arg, ast.Name):
                    dest.add(arg.id)
        self.generic_visit(node)


def _region_nodes(tree: ast.Module) -> List[ast.AST]:
    """All function defs that are traced bodies: the roots, every def
    lexically nested inside a root, and (for loop roots) the same-file
    helpers they call by name."""
    r = _Regions()
    r.visit(tree)
    out: List[ast.AST] = []
    worklist: List[ast.AST] = []
    seen: Set[int] = set()

    def take(fn, close: bool):
        if id(fn) in seen:
            return
        seen.add(id(fn))
        out.append(fn)
        if close:
            worklist.append(fn)
        for node in ast.walk(fn):
            if node is not fn and isinstance(
                    node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                take(node, close)

    # Loop roots first: a loop body lexically nested inside a jitted
    # wrapper must still get the call closure (take() marks nodes seen
    # on first visit, so order decides which flavor wins).
    for name in r.loop_roots:
        for fn in r.defs.get(name, ()):
            take(fn, close=True)
    for name in r.jit_roots:
        for fn in r.defs.get(name, ()):
            take(fn, close=False)
    while worklist:
        fn = worklist.pop()
        for node in ast.walk(fn):
            if isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Name):
                for cand in r.defs.get(node.func.id, ()):
                    take(cand, close=True)
    return out


def _lru_cached_names(tree: ast.Module) -> Set[str]:
    out: Set[str] = set()
    for node in tree.body:
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        for dec in node.decorator_list:
            d = dotted(dec.func) if isinstance(dec, ast.Call) \
                else dotted(dec)
            if "lru_cache" in d or d.endswith(".cache"):
                out.add(node.name)
    return out


def lint_file(path: str, root: Optional[str] = None) -> List[Finding]:
    tree, err, rp = parse_file(path, root)
    if tree is None:
        return [err]
    scopes = scope_map(tree)
    findings: List[Finding] = []
    # Named-constant folding environment: module-level int constants of
    # this file (+ repo-local imports), masked per node by the names its
    # enclosing function scopes rebind.
    env = _module_env(tree, root or None)
    shadows = _shadow_sets(tree)

    def resolver(node: ast.AST):
        shadowed = shadows.get(id(node), frozenset())

        def resolve(name: str):
            return None if name in shadowed else env.get(name)

        return resolve

    def add(rule, sev, node, msg):
        findings.append(Finding(
            rule=rule, severity=sev, path=rp,
            line=getattr(node, "lineno", 0),
            col=getattr(node, "col_offset", 0), message=msg,
            anchor=f"{scopes.get(node, '')}/{snippet(node)}"))

    # -- traced-body hazards ------------------------------------------------
    flagged: Set[int] = set()
    for fn in _region_nodes(tree):
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call) or id(node) in flagged:
                continue
            name = dotted(node.func)
            if isinstance(node.func, ast.Attribute) \
                    and node.func.attr in _SYNC_METHODS \
                    and not node.args:
                flagged.add(id(node))
                add("JAX-HOST-SYNC", ERROR, node,
                    f".{node.func.attr}() inside the traced body "
                    f"{fn.name!r} forces a device->host sync (or "
                    f"fails tracing outright)")
            elif name.split(".", 1)[0] in _NP_NAMES and "." in name:
                flagged.add(id(node))
                add("JAX-HOST-SYNC", ERROR, node,
                    f"{name}() inside the traced body {fn.name!r}: "
                    f"numpy runs on host — use jnp/lax so the op "
                    f"stays on device")
            elif name == "print":
                flagged.add(id(node))
                add("JAX-HOST-SYNC", ERROR, node,
                    f"print() inside the traced body {fn.name!r} "
                    f"(use jax.debug.print for traced values)")
            elif name in ("float", "int", "bool") and node.args \
                    and _const_int(node.args[0], resolver(node)) is None \
                    and not isinstance(node.args[0], ast.Constant):
                flagged.add(id(node))
                add("JAX-HOST-CAST", WARNING, node,
                    f"{name}() on a traced value inside {fn.name!r} "
                    f"is a concretization point (breaks under jit)")
            elif isinstance(node.func, ast.Attribute) \
                    and node.func.attr in _CLOCK_ATTRS \
                    and name.split(".", 1)[0] in _TIME_ALIASES:
                if _trace_in_jit_allowed(rp, scopes.get(node, "")):
                    continue
                flagged.add(id(node))
                add("JAX-TRACE-IN-JIT", ERROR, node,
                    f"{name}() inside the traced body {fn.name!r} runs "
                    f"at trace time, not per step — device timing must "
                    f"be measured on the host around "
                    f"block_until_ready (doc/observability.md)")
            elif (name in ("span", "event")
                  or (isinstance(node.func, ast.Attribute)
                      and node.func.attr in _OBS_ATTRS
                      and name.split(".", 1)[0] in _OBS_ALIASES)):
                if _trace_in_jit_allowed(rp, scopes.get(node, "")):
                    continue
                flagged.add(id(node))
                add("JAX-TRACE-IN-JIT", ERROR, node,
                    f"{name}() inside the traced body {fn.name!r}: a "
                    f"span/progress publication would record the "
                    f"TRACE, not the device execution — instrument "
                    f"the host call site instead")

    # -- whole-file hazards -------------------------------------------------
    cached = _lru_cached_names(tree)
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            name = dotted(node.func)
            if name in cached:
                for arg in list(node.args) + [kw.value
                                              for kw in node.keywords]:
                    if isinstance(arg, (ast.List, ast.Dict, ast.Set,
                                        ast.ListComp, ast.DictComp,
                                        ast.SetComp)):
                        add("JAX-UNHASHABLE-STATIC", ERROR, node,
                            f"unhashable {type(arg).__name__.lower()} "
                            f"literal passed to the lru_cache'd jit "
                            f"factory {name}() — raises TypeError and "
                            f"defeats the compile cache")
            tail = name.rsplit(".", 1)[-1]
            if tail in ("int32", "uint32") and len(node.args) == 1:
                v = _const_int(node.args[0], resolver(node))
                if v is not None:
                    lo, hi = ((0, UINT32_MAX) if tail == "uint32"
                              else (INT32_MIN, INT32_MAX))
                    if not (lo <= v <= hi):
                        add("JAX-INT32-OVERFLOW", ERROR, node,
                            f"compile-time value {v} does not fit "
                            f"{tail} [{lo}, {hi}] — the packed "
                            f"encoding would silently wrap")
        elif isinstance(node, ast.BinOp) and isinstance(
                node.op, (ast.LShift, ast.RShift)):
            sh = _const_int(node.right, resolver(node))
            if sh is not None and sh >= 32 and \
                    _const_int(node.left, resolver(node)) is None:
                add("JAX-SHIFT-WIDTH", ERROR, node,
                    f"constant shift by {sh} bits: a 32-bit lane "
                    f"shifts modulo 32 on device — this is silent "
                    f"garbage, widen the type or split the shift")
    return findings
