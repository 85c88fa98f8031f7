"""graftlint AST rules — the JAX footguns this codebase actually hits.

GL001  host-sync / tracer-leak calls inside jit-traced functions
GL002  import-time backend probes (jax.devices & co)
GL003  Python side effects under jit (print, global/nonlocal mutation)
GL004  PRNG key reuse without split
GL005  mutable default arguments in public APIs
GL007  bare except / swallowed exceptions
GL009  np.* inside a GRAPH_OPS / registry op impl off the numpy-static
       whitelist — silent host fallback under jit, in op-impl form
GL010  time.time() subtraction used as a duration — wall clocks jump with
       NTP; durations belong on time.perf_counter() (timestamps are fine)

(GL006 and GL008 live in rules_consistency — they need the live registries.)

Every rule is deliberately conservative: a static pass that cries wolf gets
deleted from the gate within two rounds. Heuristics and their blind spots
are documented per-rule in docs/LINT.md.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Sequence, Set, Tuple

from deeplearning4j_tpu.lint.core import Finding, ast_rule

# ---------------------------------------------------------------------------
# shared helpers
# ---------------------------------------------------------------------------


def _dotted(node: ast.AST) -> Optional[str]:
    """'jax.experimental.pjit.pjit' for nested Attribute/Name, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


_JIT_NAMES = {"jit", "pjit"}


def _is_jit_expr(node: ast.AST) -> bool:
    """True for expressions denoting jax.jit/pjit (bare, dotted, or wrapped
    in functools.partial(jax.jit, ...))."""
    d = _dotted(node)
    if d is not None and d.split(".")[-1] in _JIT_NAMES:
        return True
    if isinstance(node, ast.Call):
        fd = _dotted(node.func)
        if fd is not None and fd.split(".")[-1] in _JIT_NAMES:
            return True  # jax.jit(static_argnums=...) used as decorator
        if fd is not None and fd.split(".")[-1] == "partial" and node.args:
            return _is_jit_expr(node.args[0])
    return False


def _jit_functions(tree: ast.Module) -> List[ast.FunctionDef]:
    """Functions traced by jit: decorated with jit/pjit (possibly via
    partial), or a local def later wrapped as ``g = jax.jit(f)`` /
    passed directly to a jit call."""
    defs: Dict[str, ast.FunctionDef] = {}
    jitted: List[ast.FunctionDef] = []
    seen: Set[int] = set()

    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            defs.setdefault(node.name, node)
            if any(_is_jit_expr(dec) for dec in node.decorator_list):
                if id(node) not in seen:
                    seen.add(id(node))
                    jitted.append(node)

    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and _is_jit_expr(node.func):
            for arg in node.args[:1]:
                if isinstance(arg, ast.Name) and arg.id in defs:
                    fn = defs[arg.id]
                    if id(fn) not in seen:
                        seen.add(id(fn))
                        jitted.append(fn)
    return jitted


_NUMPY_ALIASES = {"np", "numpy", "onp", "_np", "_numpy"}


# ---------------------------------------------------------------------------
# GL001 — host sync under jit
# ---------------------------------------------------------------------------


@ast_rule("GL001", "host-sync/tracer-leak call inside a jit-traced function")
def rule_host_sync(tree, lines, path) -> List[Finding]:
    findings: List[Finding] = []
    for fn in _jit_functions(tree):
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if isinstance(f, ast.Attribute):
                base = _dotted(f.value)
                if f.attr in ("asarray", "array") and base in _NUMPY_ALIASES:
                    findings.append(Finding(
                        path=path, line=node.lineno, rule="GL001",
                        severity="error",
                        message=f"{base}.{f.attr}() inside jit-traced "
                                f"'{fn.name}' forces a host sync / tracer "
                                f"leak; use jnp.{f.attr} or hoist out of "
                                f"the traced path"))
                elif f.attr in ("item", "tolist") and not node.args:
                    findings.append(Finding(
                        path=path, line=node.lineno, rule="GL001",
                        severity="error",
                        message=f".{f.attr}() inside jit-traced '{fn.name}' "
                                f"blocks on device and fails under trace"))
            elif (isinstance(f, ast.Name) and f.id in ("float", "int")
                  and len(node.args) == 1
                  and not isinstance(node.args[0], ast.Constant)):
                findings.append(Finding(
                    path=path, line=node.lineno, rule="GL001",
                    severity="warning",
                    message=f"{f.id}() on a traced value inside jit-traced "
                            f"'{fn.name}' concretizes the tracer"))
    return findings


# ---------------------------------------------------------------------------
# GL002 — import-time backend probes
# ---------------------------------------------------------------------------

_PROBES = {"devices", "local_devices", "device_count", "local_device_count"}


@ast_rule("GL002", "import-time backend probe (jax.devices & co)")
def rule_backend_probe(tree, lines, path) -> List[Finding]:
    """A probe at module or class scope runs while the module is imported:
    it initializes the backend — and takes the chip, which has one owner —
    in every process that imports the module, test workers and pool
    children included. Inside a function a probe is how a program finds its
    device, in-process; that is not flagged. (A child process started to
    probe is no guard: it holds the chip its parent then needs.)"""
    findings: List[Finding] = []

    # enclosing-function map: node id -> innermost FunctionDef
    enclosing: Dict[int, Optional[ast.AST]] = {}

    def visit(node: ast.AST, fn: Optional[ast.AST]) -> None:
        for child in ast.iter_child_nodes(node):
            enclosing[id(child)] = fn
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.Lambda)):
                visit(child, child)
            else:
                visit(child, fn)

    visit(tree, None)

    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        d = _dotted(node.func)
        if d is None:
            continue
        parts = d.split(".")
        if not (len(parts) >= 2 and parts[0] == "jax" and parts[-1] in _PROBES):
            continue
        if enclosing.get(id(node)) is None:
            findings.append(Finding(
                path=path, line=node.lineno, rule="GL002", severity="error",
                message=f"jax.{parts[-1]}() at import time initializes the "
                        f"backend in every process that imports this "
                        f"module; move it into a function"))
    return findings


# ---------------------------------------------------------------------------
# GL003 — Python side effects under jit
# ---------------------------------------------------------------------------


@ast_rule("GL003", "Python side effect inside a jit-traced function")
def rule_side_effects(tree, lines, path) -> List[Finding]:
    findings: List[Finding] = []
    for fn in _jit_functions(tree):
        for node in ast.walk(fn):
            if isinstance(node, ast.Call) and isinstance(node.func, ast.Name) \
                    and node.func.id == "print":
                findings.append(Finding(
                    path=path, line=node.lineno, rule="GL003",
                    severity="warning",
                    message=f"print() inside jit-traced '{fn.name}' runs at "
                            f"trace time only; use jax.debug.print"))
            elif isinstance(node, (ast.Global, ast.Nonlocal)):
                kind = "global" if isinstance(node, ast.Global) else "nonlocal"
                findings.append(Finding(
                    path=path, line=node.lineno, rule="GL003",
                    severity="error",
                    message=f"{kind} mutation inside jit-traced '{fn.name}' "
                            f"is a trace-time side effect (stale after the "
                            f"first compile)"))
    return findings


# ---------------------------------------------------------------------------
# GL004 — PRNG key reuse
# ---------------------------------------------------------------------------

# jax.random functions that CONSUME a key (same key twice => identical or
# correlated draws). Non-consuming: split/fold_in/key construction/inspection.
_NON_CONSUMING = {"split", "fold_in", "PRNGKey", "key", "wrap_key_data",
                  "clone", "key_data", "key_impl"}


def _jax_random_aliases(tree: ast.Module) -> Tuple[Set[str], Set[str]]:
    """(dotted prefixes bound to jax.random, bare function names imported
    from it) — so stdlib ``random`` never triggers the rule."""
    prefixes: Set[str] = set()
    bare: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name == "jax":
                    prefixes.add((a.asname or "jax") + ".random")
                elif a.name == "jax.random":
                    prefixes.add(a.asname or "jax.random")
        elif isinstance(node, ast.ImportFrom):
            if node.module == "jax":
                for a in node.names:
                    if a.name == "random":
                        prefixes.add(a.asname or "random")
            elif node.module == "jax.random":
                for a in node.names:
                    bare.add(a.asname or a.name)
    return prefixes, bare


def _rebound_names(stmt: ast.AST) -> Set[str]:
    out: Set[str] = set()
    targets: List[ast.AST] = []
    if isinstance(stmt, ast.Assign):
        targets = list(stmt.targets)
    elif isinstance(stmt, (ast.AugAssign, ast.AnnAssign)):
        targets = [stmt.target]
    elif isinstance(stmt, ast.For):
        targets = [stmt.target]
    elif isinstance(stmt, ast.withitem) and stmt.optional_vars is not None:
        targets = [stmt.optional_vars]
    for t in targets:
        for node in ast.walk(t):
            if isinstance(node, ast.Name):
                out.add(node.id)
    return out


class _KeyReuseScanner:
    """Branch-aware scan: mutually exclusive If/Try arms get independent
    copies of the consumed-key state (the weight-init dispatch pattern —
    twenty `if scheme == ...: return jax.random.normal(key, ...)` arms —
    is one consumption per call, not twenty). Uses inside a branch do not
    propagate out: precision over recall — a gate rule that cries wolf
    gets deleted."""

    def __init__(self, prefixes: Set[str], bare: Set[str], fn_name: str,
                 path: str):
        self.prefixes, self.bare = prefixes, bare
        self.fn_name, self.path = fn_name, path
        self.findings: List[Finding] = []

    def _leaf(self, call: ast.Call) -> Optional[str]:
        d = _dotted(call.func)
        if d is None:
            return None
        if d in self.bare:
            return d
        head, _, tail = d.rpartition(".")
        return tail if head in self.prefixes else None

    def _expr(self, node: Optional[ast.AST], consumed: Dict[str, int]) -> None:
        if node is None:
            return
        for sub in ast.walk(node):
            if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.Lambda)):
                continue  # different scope (walk still descends; acceptable)
            if not isinstance(sub, ast.Call):
                continue
            leaf = self._leaf(sub)
            if leaf is None or not sub.args:
                continue
            arg = sub.args[0]           # key is arg 0 by convention
            if not isinstance(arg, ast.Name):
                continue
            if leaf in _NON_CONSUMING:
                consumed.pop(arg.id, None)
            elif arg.id in consumed:
                # message stays line-number-free: it is part of the
                # baseline key, which must survive unrelated edits
                self.findings.append(Finding(
                    path=self.path, line=arg.lineno, rule="GL004",
                    severity="error",
                    message=f"PRNG key '{arg.id}' in '{self.fn_name}' "
                            f"consumed again without jax.random.split — "
                            f"draws are identical/correlated"))
            else:
                consumed[arg.id] = arg.lineno

    def block(self, stmts: Sequence[ast.stmt], consumed: Dict[str, int]) -> None:
        for stmt in stmts:
            if isinstance(stmt, ast.If):
                self._expr(stmt.test, consumed)
                self.block(stmt.body, dict(consumed))
                self.block(stmt.orelse, dict(consumed))
            elif isinstance(stmt, (ast.For, ast.AsyncFor)):
                self._expr(stmt.iter, consumed)
                body_state = dict(consumed)
                for name in _rebound_names(stmt):
                    body_state.pop(name, None)
                self.block(stmt.body, body_state)
                self.block(stmt.orelse, dict(consumed))
            elif isinstance(stmt, ast.While):
                self._expr(stmt.test, consumed)
                self.block(stmt.body, dict(consumed))
                self.block(stmt.orelse, dict(consumed))
            elif isinstance(stmt, ast.Try):
                self.block(stmt.body, dict(consumed))
                for h in stmt.handlers:
                    self.block(h.body, dict(consumed))
                self.block(stmt.orelse, dict(consumed))
                self.block(stmt.finalbody, dict(consumed))
            elif isinstance(stmt, (ast.With, ast.AsyncWith)):
                for item in stmt.items:
                    self._expr(item.context_expr, consumed)
                    if item.optional_vars is not None:
                        for name in _rebound_names(item):
                            consumed.pop(name, None)
                self.block(stmt.body, consumed)   # runs exactly once
            elif isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                                   ast.ClassDef)):
                continue  # nested scope: scanned by its own pass
            else:
                for child in ast.iter_child_nodes(stmt):
                    if isinstance(child, ast.expr):
                        self._expr(child, consumed)
                for name in _rebound_names(stmt):
                    consumed.pop(name, None)


@ast_rule("GL004", "PRNG key consumed twice without split")
def rule_key_reuse(tree, lines, path) -> List[Finding]:
    findings: List[Finding] = []
    prefixes, bare = _jax_random_aliases(tree)
    if not prefixes and not bare:
        return findings
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        scanner = _KeyReuseScanner(prefixes, bare, fn.name, path)
        scanner.block(fn.body, {})
        findings.extend(scanner.findings)
    return findings


# ---------------------------------------------------------------------------
# GL005 — mutable default arguments in public APIs
# ---------------------------------------------------------------------------


@ast_rule("GL005", "mutable default argument in a public API")
def rule_mutable_defaults(tree, lines, path) -> List[Finding]:
    findings: List[Finding] = []
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if fn.name.startswith("_"):
            continue
        defaults = list(fn.args.defaults) + [
            d for d in fn.args.kw_defaults if d is not None]
        for d in defaults:
            bad = isinstance(d, (ast.List, ast.Dict, ast.Set)) or (
                isinstance(d, ast.Call) and isinstance(d.func, ast.Name)
                and d.func.id in ("list", "dict", "set"))
            if bad:
                findings.append(Finding(
                    path=path, line=d.lineno, rule="GL005",
                    severity="warning",
                    message=f"mutable default argument in public "
                            f"'{fn.name}' is shared across calls; default "
                            f"to None and build inside"))
    return findings


# ---------------------------------------------------------------------------
# GL009 — numpy inside graph-op implementations
# ---------------------------------------------------------------------------

# ops whose impls are DOCUMENTED numpy-static (docs/LINT.md, docs/
# ANALYSIS.md): they deliberately stay on host so imported
# tf.shape→Pack→Reshape chains keep trace-time-concrete ints. Everything
# else reaching np.* under a jit trace is the round-5 hang class in
# op-impl form: a silent device→host sync (or a tracer leak) every step.
NUMPY_STATIC_OP_WHITELIST = frozenset(["shape_of", "stack", "unstack"])

_OP_DECORATOR_NAMES = {"op", "_op"}
_OP_REGISTER_METHODS = {"register"}


def _graph_op_impls(tree: ast.Module):
    """Yield (op_name, function-or-lambda node) for every statically
    recognizable graph-op implementation:

    * values of a dict literal assigned to ``GRAPH_OPS``;
    * ``GRAPH_OPS["name"] = <lambda | local def>`` (any ``*GRAPH_OPS``
      spelling — importers patch the table under aliases);
    * functions decorated ``@op("name")`` / ``@_op("name")`` (the
      declarable-op registry idiom);
    * ``<reg>.register("name", fn)`` with a local ``def fn``.
    """
    defs: Dict[str, ast.FunctionDef] = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            defs.setdefault(node.name, node)

    for node in ast.walk(tree):
        # GRAPH_OPS = { "name": <lambda>, ... } — plain OR annotated
        # (the real table is `GRAPH_OPS: Dict[str, Callable] = {...}`)
        dict_targets = []
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Dict):
            dict_targets = list(node.targets)
        elif isinstance(node, ast.AnnAssign) and \
                isinstance(node.value, ast.Dict):
            dict_targets = [node.target]
        for tgt in dict_targets:
            name = _dotted(tgt)
            if name is None or not name.split(".")[-1].endswith("GRAPH_OPS"):
                continue
            for k, v in zip(node.value.keys, node.value.values):
                if isinstance(k, ast.Constant) and isinstance(k.value, str):
                    impl = v if isinstance(v, ast.Lambda) else (
                        defs.get(v.id) if isinstance(v, ast.Name) else None)
                    if impl is not None:
                        yield k.value, impl
        # GRAPH_OPS["name"] = impl
        if isinstance(node, ast.Assign) and node.targets and \
                isinstance(node.targets[0], ast.Subscript):
            sub = node.targets[0]
            name = _dotted(sub.value)
            if name is not None and name.split(".")[-1].endswith("GRAPH_OPS"):
                key = sub.slice
                if isinstance(key, ast.Constant) and isinstance(key.value, str):
                    impl = node.value if isinstance(node.value, ast.Lambda) \
                        else (defs.get(node.value.id)
                              if isinstance(node.value, ast.Name) else None)
                    if impl is not None:
                        yield key.value, impl
        # @op("name") / @_op("name") def impl(...)
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if isinstance(dec, ast.Call) and dec.args and \
                        isinstance(dec.args[0], ast.Constant) and \
                        isinstance(dec.args[0].value, str):
                    d = _dotted(dec.func)
                    if d is not None and d.split(".")[-1] in _OP_DECORATOR_NAMES:
                        yield dec.args[0].value, node
        # reg.register("name", fn)
        if isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute) \
                and node.func.attr in _OP_REGISTER_METHODS \
                and len(node.args) >= 2 \
                and isinstance(node.args[0], ast.Constant) \
                and isinstance(node.args[0].value, str):
            impl = node.args[1] if isinstance(node.args[1], ast.Lambda) else (
                defs.get(node.args[1].id)
                if isinstance(node.args[1], ast.Name) else None)
            if impl is not None:
                yield node.args[0].value, impl


@ast_rule("GL009", "np.* inside a graph-op impl off the numpy-static whitelist")
def rule_numpy_in_op_impl(tree, lines, path) -> List[Finding]:
    findings: List[Finding] = []
    seen: Set[Tuple[str, int]] = set()
    for op_name, impl in _graph_op_impls(tree):
        if op_name in NUMPY_STATIC_OP_WHITELIST:
            continue
        for node in ast.walk(impl):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if not isinstance(f, ast.Attribute):
                continue
            base = _dotted(f.value)
            if base not in _NUMPY_ALIASES:
                continue
            key = (op_name, node.lineno)
            if key in seen:  # one op impl can be yielded via two idioms
                continue
            seen.add(key)
            findings.append(Finding(
                path=path, line=node.lineno, rule="GL009",
                severity="error",
                message=f"{base}.{f.attr}() inside graph-op impl "
                        f"'{op_name}' runs on host under jit (silent "
                        f"fallback / tracer leak); use jnp, or add the op "
                        f"to the documented numpy-static whitelist "
                        f"(shape_of/stack/unstack) with justification"))
    return findings


# ---------------------------------------------------------------------------
# GL010 — wall-clock subtraction used as a duration
# ---------------------------------------------------------------------------


def _walltime_aliases(tree: ast.Module) -> Set[str]:
    """Dotted spellings that denote ``time.time`` in this module:
    ``{"time.time"}`` under ``import time`` (any asname), plus bare names
    from ``from time import time``. Stdlib-only — a local ``def time()``
    never registers because it is not an import."""
    out: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name == "time":
                    out.add((a.asname or "time") + ".time")
        elif isinstance(node, ast.ImportFrom) and node.module == "time":
            for a in node.names:
                if a.name == "time":
                    out.add(a.asname or "time")
    return out


def _is_walltime_call(node: ast.AST, aliases: Set[str]) -> bool:
    return (isinstance(node, ast.Call) and not node.args
            and _dotted(node.func) in aliases)


@ast_rule("GL010", "time.time() subtraction used as a duration")
def rule_walltime_duration(tree, lines, path) -> List[Finding]:
    """``time.time()`` is a WALL clock: NTP steps/slews move it, so a
    subtraction of two readings is not a duration — it can be negative or
    hours off, silently corrupting training-time stats, ETA math, and time
    budgets (the reference's PerformanceListener class of bugs).

    Flagged: ``a - b`` where BOTH operands are wall-time readings — a
    direct ``time.time()`` call or a name/attribute assigned from one
    anywhere in the module (``self._t0 = time.time()`` in ``__init__``,
    subtracted in another method, is the repo's own pattern). Requiring
    both sides keeps timestamps whitelisted: ``time.time() - 86400``
    (epoch arithmetic) and plain timestamp fields never fire. Blind spot
    (documented in docs/LINT.md): deadline COMPARISONS
    (``time.time() > t0 + budget``) are not subtractions and pass."""
    aliases = _walltime_aliases(tree)
    if not aliases:
        return []
    timeish: Set[str] = set()
    for node in ast.walk(tree):
        targets: List[ast.AST] = []
        if isinstance(node, ast.Assign) and \
                _is_walltime_call(node.value, aliases):
            targets = list(node.targets)
        elif isinstance(node, (ast.AnnAssign, ast.AugAssign)) and \
                node.value is not None and \
                _is_walltime_call(node.value, aliases):
            targets = [node.target]
        for t in targets:
            name = _dotted(t)
            if name:
                timeish.add(name)

    def is_timeish(node: ast.AST) -> bool:
        if _is_walltime_call(node, aliases):
            return True
        d = _dotted(node)
        return d is not None and d in timeish

    findings: List[Finding] = []
    for node in ast.walk(tree):
        if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Sub) \
                and is_timeish(node.left) and is_timeish(node.right):
            findings.append(Finding(
                path=path, line=node.lineno, rule="GL010", severity="error",
                message="time.time() subtraction used as a duration — the "
                        "wall clock jumps with NTP; use time.perf_counter() "
                        "for both readings (timestamps themselves are fine)"))
    return findings


# ---------------------------------------------------------------------------
# GL007 — bare / swallowed exceptions
# ---------------------------------------------------------------------------

_BROAD = {"Exception", "BaseException"}


@ast_rule("GL007", "bare except / swallowed exception")
def rule_bare_except(tree, lines, path) -> List[Finding]:
    findings: List[Finding] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.ExceptHandler):
            continue
        if node.type is None:
            findings.append(Finding(
                path=path, line=node.lineno, rule="GL007", severity="error",
                message="bare 'except:' catches KeyboardInterrupt/SystemExit;"
                        " name the exception"))
            continue
        type_name = _dotted(node.type)
        broad = type_name is not None and type_name.split(".")[-1] in _BROAD
        body_is_pass = all(isinstance(s, ast.Pass) for s in node.body)
        if broad and body_is_pass:
            findings.append(Finding(
                path=path, line=node.lineno, rule="GL007", severity="warning",
                message=f"'except {type_name}: pass' swallows every error "
                        f"silently; log or narrow it"))
    return findings
