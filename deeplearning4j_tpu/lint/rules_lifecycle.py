"""graftlife — resource-lifecycle & exactly-once static analysis.

GR001  unbalanced page ownership: a refcounted-page acquisition
       (``alloc_page``/``retain``/``cow_page``/``map_shared``) that some
       path — including raise and early-return paths — exits without a
       matching ``release``/``free_slot``/tree-insert handoff; plus the
       call-graph arm: a call to a page-acquiring intra-module callee
       sitting OUTSIDE the raise-unwind protection its sibling
       admission path has (the engine-step leak shape)
GR002  double-release hazard: a second ``release`` of the same page
       reference on one path, or two release-loops draining the same
       page list
GR003  terminal-vocabulary exactly-once: a function that completes a
       request future (``set_result``/``set_exception``, including the
       deferred-lambda form) without routing the outcome through the
       ``count_terminal`` funnel (or a funnel-calling helper); plus the
       double-count arm (two ``count_terminal`` on one straight line)
GR004  unstoppable thread: a started ``Thread(...)`` with no
       join/stop reachable (class-level for ``self._thread`` workers,
       function-level for locals) — ``daemon=True`` does NOT exempt,
       only a written justification does
GR005  non-atomic durable write: ``open(.., "w")``/``np.save*`` into a
       durable file without the tmp + ``os.replace`` dance in the same
       function (and not itself writing the ``*.tmp`` side)

Same house rules as graftlock/graftshape: deliberately conservative
(precision over recall — a gate rule that cries wolf gets deleted),
blind spots documented in docs/LINT.md, and a true positive the code
*means* is suppressed inline with ``# graftlife: justified(GR00x):
<reason>`` — the reason is mandatory; a bare marker does not suppress.

Beyond the per-file rules this module exports the repo-wide static
ownership inventory (:func:`static_ownership_inventory`): every
function span that touches the allocator vocabulary, in span units the
runtime resource tracer (``testing/lifetrace.py``) checks observed
acquire/release callsites against — an observed callsite outside the
inventory is an analyzer blind spot, not a baseline candidate.
"""

from __future__ import annotations

import ast
import os
import re
from typing import Dict, List, Optional, Sequence, Set, Tuple

from deeplearning4j_tpu.lint.core import Finding, ast_rule, iter_py_files

GR_RULES = ("GR001", "GR002", "GR003", "GR004", "GR005")

# ---------------------------------------------------------------------------
# inline justification (the graftlife analog of "graftlint: disable")
# ---------------------------------------------------------------------------

_JUSTIFIED_RE = re.compile(
    r"graftlife:\s*justified\((GR\d{3})\)\s*:\s*(\S.*)")


def _justified_lines(lines: Sequence[str]) -> Dict[int, Set[str]]:
    """1-based line -> rule ids justified there. Only matches carrying a
    nonempty written reason suppress — acceptance requires every
    justified site to say WHY."""
    out: Dict[int, Set[str]] = {}
    for i, text in enumerate(lines, start=1):
        for m in _JUSTIFIED_RE.finditer(text):
            out.setdefault(i, set()).add(m.group(1))
    return out


def _apply_justified(findings: List[Finding],
                     lines: Sequence[str]) -> List[Finding]:
    """A justification suppresses a finding on its own line or anywhere in
    the contiguous comment block directly above it (real reasons often run
    to two or three comment lines)."""
    just = _justified_lines(lines)

    def _suppressed(f: Finding) -> bool:
        if f.rule in just.get(f.line, ()):
            return True
        ln = f.line - 1
        while 1 <= ln <= len(lines) and lines[ln - 1].lstrip().startswith("#"):
            if f.rule in just.get(ln, ()):
                return True
            ln -= 1
        return False

    return [f for f in findings if not _suppressed(f)]


def _in_library(path: str) -> bool:
    """The lifecycle rules cover library code; bench/driver scripts in
    tools/ and examples/ own their throwaway threads and futures."""
    return not (path.startswith("tools/") or path.startswith("examples/"))


# ---------------------------------------------------------------------------
# the ownership vocabulary (serving/cache.py's allocator + the radix tree)
# ---------------------------------------------------------------------------

# value-returning acquisitions: ``p = cache.alloc_page()`` binds a ref
_ALLOC_METHODS = {"alloc_page", "cow_page"}
# every acquisition the refcount bookkeeping must balance
_ACQUIRE_METHODS = {"alloc_page", "cow_page", "retain", "map_shared"}
# tree-insert hands pages to the radix tree (insert() retains what it
# keeps — the documented handoff convention, docs/ROBUSTNESS.md)
_HANDOFF_METHODS = {"insert"}
# terminal funnels: count_terminal itself plus the helpers that call it
# (scheduler.fail_all/fail_pending count per future; engine
# _finish_unslotted counts; frontend _deny counts)
_TERMINAL_FUNNELS = {"count_terminal", "fail_all", "fail_pending",
                     "_finish_unslotted", "_deny"}
_COMPLETERS = {"set_result", "set_exception"}

# by-name intra-module call resolution must not alias through names every
# builtin container also has (graftlock's precedent)
_GENERIC_CALLEES = (set(dir(list)) | set(dir(dict)) | set(dir(set))
                    | set(dir(str)) | set(dir(bytes))
                    | {"min", "max", "sum", "len", "start", "run", "join",
                       "acquire", "release", "wait", "notify", "put",
                       "submit", "result", "insert"})


def _call_name(node: ast.AST) -> Optional[str]:
    if not isinstance(node, ast.Call):
        return None
    fn = node.func
    if isinstance(fn, ast.Attribute):
        return fn.attr
    if isinstance(fn, ast.Name):
        return fn.id
    return None


def _is_release_call(node: ast.Call) -> bool:
    """``X.release(p)`` (with a page argument — ``lock.release()`` takes
    none) or ``X.free_slot(...)``."""
    name = _call_name(node)
    if name == "free_slot":
        return True
    return name == "release" and bool(node.args)


def _is_acquire_call(node: ast.Call) -> bool:
    return _call_name(node) in _ACQUIRE_METHODS


def _walk_no_defs(node: ast.AST):
    """Walk an AST without descending into nested function/class bodies
    or lambdas — closure bodies run later, on someone else's path."""
    stack = list(ast.iter_child_nodes(node))
    while stack:
        n = stack.pop()
        yield n
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                          ast.ClassDef, ast.Lambda)):
            continue
        stack.extend(ast.iter_child_nodes(n))


# ---------------------------------------------------------------------------
# GR001/GR002 — the per-function ownership path simulation
# ---------------------------------------------------------------------------


class _PathState:
    """Held page refs (name -> acquisition line) and already-released
    refs along one abstract path."""

    __slots__ = ("held", "released")

    def __init__(self, held: Optional[Dict[str, int]] = None,
                 released: Optional[Dict[str, int]] = None):
        self.held: Dict[str, int] = dict(held or {})
        self.released: Dict[str, int] = dict(released or {})

    def copy(self) -> "_PathState":
        return _PathState(self.held, self.released)

    @staticmethod
    def merge(states: List["_PathState"]) -> "_PathState":
        """Join of fall-through branches: a ref is held after the join if
        it is still held on ANY branch (might-be-held is what leak exits
        must see)."""
        out = _PathState()
        for st in states:
            for k, v in st.held.items():
                out.held.setdefault(k, v)
            for k, v in st.released.items():
                out.released.setdefault(k, v)
        return out


class _Exit:
    __slots__ = ("kind", "line", "held")

    def __init__(self, kind: str, line: int, held: Dict[str, int]):
        self.kind = kind
        self.line = line
        self.held = dict(held)


class _FnSim:
    """Abstract interpretation of one function body: tracks named page
    acquisitions and reports every exit (return / raise / fall-through)
    that still holds a reference, plus double releases on a path.

    Ownership transfer discharges a held name: released/free_slot'ed,
    handed to the radix tree (``insert``), returned to the caller,
    stored into an attribute/subscript/container, or passed as an
    argument to ANY call (the callee — e.g. an intra-module helper that
    releases its parameter — now owns it; precision over recall)."""

    def __init__(self, func: ast.AST):
        self.func = func
        self.exits: List[_Exit] = []
        self.double: List[Tuple[str, int]] = []
        self.acquires = False  # any acquisition vocabulary in the body

    # -- expression scanning -------------------------------------------------
    def _calls_in(self, node: ast.AST) -> List[ast.Call]:
        # the node itself first: _walk_no_defs yields children only, and
        # a statement like ``cache.release(p)`` IS the top-level Call
        head = [node] if isinstance(node, ast.Call) else []
        return head + [n for n in _walk_no_defs(node)
                       if isinstance(n, ast.Call)]

    def _arg_names(self, call: ast.Call) -> List[str]:
        names = [a.id for a in call.args if isinstance(a, ast.Name)]
        names += [k.value.id for k in call.keywords
                  if isinstance(k.value, ast.Name)]
        # a list literal argument transfers its held elements too:
        # tree.insert(prompt, [p1, p2])
        for a in list(call.args) + [k.value for k in call.keywords]:
            if isinstance(a, (ast.List, ast.Tuple)):
                names += [e.id for e in a.elts if isinstance(e, ast.Name)]
        return names

    def _scan_calls(self, node: ast.AST, st: _PathState) -> None:
        for call in self._calls_in(node):
            name = _call_name(call)
            if name in _ACQUIRE_METHODS:
                self.acquires = True
            if _is_release_call(call):
                if name == "free_slot":
                    # free_slot releases every page the slot owns — all
                    # slot-attributed ownership in flight is discharged
                    st.held.clear()
                    continue
                arg = call.args[0]
                if isinstance(arg, ast.Name):
                    if arg.id in st.released:
                        self.double.append((arg.id, call.lineno))
                    elif arg.id in st.held:
                        st.released[arg.id] = call.lineno
                        del st.held[arg.id]
                continue
            # any other call that receives a held name transfers
            # ownership to the callee/container (append, insert, a
            # helper that releases its parameter, a ctor that keeps it)
            for n in self._arg_names(call):
                if n in st.held:
                    del st.held[n]

    def _discharge_names_in(self, node: ast.AST, st: _PathState) -> None:
        # the node itself first: ``return p`` hands over a bare Name and
        # _walk_no_defs yields children only
        for n in [node] + list(_walk_no_defs(node)):
            if isinstance(n, ast.Name) and n.id in st.held:
                del st.held[n.id]

    # -- None-guard specialization -------------------------------------------
    @staticmethod
    def _none_guard(test: ast.AST) -> Tuple[Optional[str], Optional[str]]:
        """(name dropped in the TRUE branch, name dropped in the FALSE
        branch) for the allocator's None-on-exhaustion contract:
        ``if p is None: return`` holds nothing on the failure branch."""
        if isinstance(test, ast.Compare) and len(test.ops) == 1 and \
                isinstance(test.left, ast.Name) and \
                len(test.comparators) == 1 and \
                isinstance(test.comparators[0], ast.Constant) and \
                test.comparators[0].value is None:
            if isinstance(test.ops[0], ast.Is):
                return test.left.id, None
            if isinstance(test.ops[0], ast.IsNot):
                return None, test.left.id
        if isinstance(test, ast.UnaryOp) and isinstance(test.op, ast.Not) \
                and isinstance(test.operand, ast.Name):
            return test.operand.id, None
        if isinstance(test, ast.Name):
            return None, test.id
        return None, None

    # -- statement interpretation --------------------------------------------
    def _block(self, stmts: List[ast.stmt],
               st: _PathState) -> Optional[_PathState]:
        """Returns the fall-through state, or None when every path in
        the block exits the function."""
        for s in stmts:
            if isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.ClassDef)):
                continue
            if isinstance(s, ast.Assign):
                self._scan_calls(s.value, st)
                tgt = s.targets[0] if len(s.targets) == 1 else None
                if isinstance(tgt, ast.Name) and \
                        isinstance(s.value, ast.Call) and \
                        _call_name(s.value) in _ALLOC_METHODS:
                    st.held[tgt.id] = s.lineno
                    st.released.pop(tgt.id, None)
                    self.acquires = True
                elif tgt is not None and not isinstance(tgt, ast.Name):
                    # stored into an attribute/subscript — transferred
                    self._discharge_names_in(s.value, st)
                elif isinstance(tgt, ast.Name) and tgt.id in st.held:
                    # rebinding a held name loses our handle (blind spot:
                    # treated as a transfer, not a leak)
                    del st.held[tgt.id]
                continue
            if isinstance(s, (ast.AugAssign, ast.AnnAssign)):
                if s.value is not None:
                    self._scan_calls(s.value, st)
                continue
            if isinstance(s, ast.Expr):
                self._scan_calls(s.value, st)
                continue
            if isinstance(s, ast.Return):
                if s.value is not None:
                    self._scan_calls(s.value, st)
                    self._discharge_names_in(s.value, st)
                self.exits.append(_Exit("return", s.lineno, st.held))
                return None
            if isinstance(s, ast.Raise):
                self.exits.append(_Exit("raise", s.lineno, st.held))
                return None
            if isinstance(s, ast.If):
                self._scan_calls(s.test, st)
                t_st, f_st = st.copy(), st.copy()
                drop_true, drop_false = self._none_guard(s.test)
                if drop_true:
                    t_st.held.pop(drop_true, None)
                if drop_false:
                    f_st.held.pop(drop_false, None)
                rt = self._block(s.body, t_st)
                rf = self._block(s.orelse, f_st) if s.orelse else f_st
                live = [x for x in (rt, rf) if x is not None]
                if not live:
                    return None
                merged = _PathState.merge(live)
                st.held, st.released = merged.held, merged.released
                continue
            if isinstance(s, (ast.For, ast.AsyncFor)):
                self._scan_calls(s.iter, st)
                body_st = st.copy()
                if isinstance(s.target, ast.Name):
                    body_st.held.pop(s.target.id, None)
                rb = self._block(s.body, body_st)
                live = [st] + ([rb] if rb is not None else [])
                merged = _PathState.merge(live)
                st.held, st.released = merged.held, merged.released
                continue
            if isinstance(s, ast.While):
                self._scan_calls(s.test, st)
                body_st = st.copy()
                rb = self._block(s.body, body_st)
                live = [st] + ([rb] if rb is not None else [])
                merged = _PathState.merge(live)
                st.held, st.released = merged.held, merged.released
                continue
            if isinstance(s, (ast.With, ast.AsyncWith)):
                for item in s.items:
                    self._scan_calls(item.context_expr, st)
                r = self._block(s.body, st)
                if r is None:
                    return None
                continue
            if isinstance(s, ast.Try):
                r = self._try(s, st)
                if r is None:
                    return None
                st.held, st.released = r.held, r.released
                continue
            # everything else (pass/assert/del/global/break/continue...):
            # scan embedded expressions for calls
            self._scan_calls(s, st)
        return st

    def _finally_discharges(self, finalbody: List[ast.stmt]
                            ) -> Tuple[Set[str], bool]:
        """(names discharged, clears-everything) for a finally block:
        applied to every exit recorded inside the guarded region."""
        names: Set[str] = set()
        clears = False
        for s in finalbody:
            for call in (n for n in _walk_no_defs(s)
                         if isinstance(n, ast.Call)):
                if _call_name(call) == "free_slot":
                    clears = True
                elif _is_release_call(call):
                    if call.args and isinstance(call.args[0], ast.Name):
                        names.add(call.args[0].id)
                else:
                    names.update(n for n in self._arg_names(call))
        return names, clears

    def _try(self, s: ast.Try, st: _PathState) -> Optional[_PathState]:
        mark = len(self.exits)
        body_st = st.copy()
        rb = self._block(s.body, body_st)
        raised = [e for e in self.exits[mark:] if e.kind == "raise"]
        if s.handlers:
            # a handler intercepts in-body raises; the handler may see
            # anything acquired at ANY point of the body still held
            self.exits[mark:] = [e for e in self.exits[mark:]
                                 if e.kind != "raise"]
            entry = _PathState.merge([st, body_st if rb is None else rb])
            for e in raised:
                for k, v in e.held.items():
                    entry.held.setdefault(k, v)
            entry.released = dict(st.released)
            live: List[_PathState] = []
            if rb is not None:
                live.append(rb)
            for h in s.handlers:
                h_st = entry.copy()
                rh = self._block(h.body, h_st)
                if rh is not None:
                    live.append(rh)
        else:
            live = [rb] if rb is not None else []
        if s.finalbody:
            names, clears = self._finally_discharges(s.finalbody)
            for e in self.exits[mark:]:
                if clears:
                    e.held.clear()
                for n in names:
                    e.held.pop(n, None)
            for x in live:
                r = self._block(s.finalbody, x)
                if r is None:
                    return None
        if not live:
            return None
        out = _PathState.merge(live)
        if s.orelse:
            r = self._block(s.orelse, out)
            if r is None:
                return None
            out = r
        return out

    # -- entry ---------------------------------------------------------------
    def run(self) -> None:
        st = _PathState()
        end = self._block(self.func.body, st)
        if end is not None:
            last = getattr(self.func, "end_lineno", self.func.lineno)
            self.exits.append(_Exit("fall-through", last, end.held))

    def leaks(self) -> List[Tuple[str, int, str, int]]:
        """(name, acq_line, exit_kind, exit_line), one per leaked ref."""
        seen: Set[Tuple[str, int]] = set()
        out = []
        for e in self.exits:
            for name, acq in e.held.items():
                if (name, acq) in seen:
                    continue
                seen.add((name, acq))
                out.append((name, acq, e.kind, e.line))
        return out


# ---------------------------------------------------------------------------
# the per-module lifecycle model (cached on the tree, graftlock-style)
# ---------------------------------------------------------------------------


class _LifeModel:
    """Functions/methods of one module with their lifecycle summaries:
    which acquire page ownership (directly or through the intra-module
    call graph), which funnel terminal outcomes, and the raw nodes for
    the per-rule passes."""

    def __init__(self, tree: ast.Module, path: str):
        self.path = path
        self.functions: Dict[Tuple[Optional[str], str], ast.AST] = {}
        self.classes: Dict[str, ast.ClassDef] = {}
        for node in tree.body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.functions[(None, node.name)] = node
            elif isinstance(node, ast.ClassDef):
                self.classes[node.name] = node
                for sub in node.body:
                    if isinstance(sub, (ast.FunctionDef,
                                        ast.AsyncFunctionDef)):
                        self.functions[(node.name, sub.name)] = sub
        self._names = {name for (_cls, name) in self.functions}

        self.direct_acquires: Set[Tuple[Optional[str], str]] = set()
        self.direct_counts: Set[Tuple[Optional[str], str]] = set()
        self.calls: Dict[Tuple[Optional[str], str],
                         List[Tuple[str, int]]] = {}
        for key, fn in self.functions.items():
            callees: List[Tuple[str, int]] = []
            for n in _walk_no_defs(fn):
                if not isinstance(n, ast.Call):
                    continue
                name = _call_name(n)
                if name in _ACQUIRE_METHODS:
                    self.direct_acquires.add(key)
                if name == "count_terminal":
                    self.direct_counts.add(key)
                if name in self._names and name not in _GENERIC_CALLEES:
                    callees.append((name, n.lineno))
            self.calls[key] = callees

    def _fixpoint(self, seed: Set[Tuple[Optional[str], str]]
                  ) -> Set[Tuple[Optional[str], str]]:
        marked = set(seed)
        marked_names = {name for (_c, name) in marked}
        changed = True
        while changed:
            changed = False
            for key, callees in self.calls.items():
                if key in marked:
                    continue
                if any(name in marked_names for name, _ln in callees):
                    marked.add(key)
                    marked_names.add(key[1])
                    changed = True
        return marked

    def acquiring(self) -> Set[Tuple[Optional[str], str]]:
        """Functions that acquire page ownership, transitively through
        the intra-module call graph (graftlock's held-lock fixpoint,
        applied to ownership)."""
        return self._fixpoint(self.direct_acquires)

    def counting(self) -> Set[str]:
        """Names of module functions that transitively reach
        count_terminal — module-local funnels for GR003."""
        return {name for (_c, name) in self._fixpoint(self.direct_counts)}


def _model(tree: ast.Module, path: str) -> _LifeModel:
    model = getattr(tree, "_graftlife_model", None)
    if model is None or model.path != path:
        model = _LifeModel(tree, path)
        tree._graftlife_model = model
    return model


def _qual(key: Tuple[Optional[str], str]) -> str:
    cls, name = key
    return f"{cls}.{name}" if cls else name


# ---------------------------------------------------------------------------
# GR001 — unbalanced page ownership
# ---------------------------------------------------------------------------


def _release_unwind_trys(fn: ast.AST) -> List[ast.Try]:
    """Try statements whose handler or finally discharges page
    ownership (release/free_slot) — the function's raise-unwind
    protection for admission paths."""
    out = []
    for n in _walk_no_defs(fn):
        if not isinstance(n, ast.Try):
            continue
        cleanup = [s for h in n.handlers for s in h.body] + list(n.finalbody)
        for s in cleanup:
            if any(_is_release_call(c) for c in ast.walk(s)
                   if isinstance(c, ast.Call)):
                out.append(n)
                break
    return out


@ast_rule("GR001", "unbalanced page ownership: an alloc/retain/cow/"
                   "map_shared acquisition that a path (incl. raise/"
                   "early-return) exits without release/free_slot/"
                   "tree-handoff")
def rule_page_ownership(tree, lines, path) -> List[Finding]:
    if not _in_library(path):
        return []
    model = _model(tree, path)
    findings: List[Finding] = []
    acquiring = model.acquiring()
    acquiring_names = {name for (_c, name) in acquiring}
    for key, fn in model.functions.items():
        sim = _FnSim(fn)
        sim.run()
        for name, acq, kind, _exit_line in sim.leaks():
            findings.append(Finding(path, acq, "GR001", "error",
                f"page ref '{name}' acquired in {_qual(key)}() can exit "
                f"via {kind} without release/free_slot/handoff"))
        # the call-graph arm: sibling admission calls are protected by a
        # raise-unwind that releases, this acquiring call is not — the
        # engine-step leak shape (an exception between remove_pending
        # and admit leaks every page already mapped to the slot)
        trys = _release_unwind_trys(fn)
        if not trys:
            continue
        protected = [(t.lineno, getattr(t, "end_lineno", t.lineno))
                     for t in trys]
        for callee, line in model.calls.get(key, ()):
            if callee not in acquiring_names:
                continue
            if any(a <= line <= b for a, b in protected):
                continue
            findings.append(Finding(path, line, "GR001", "error",
                f"{_qual(key)}() calls page-acquiring '{callee}' outside "
                f"the raise-unwind protection its sibling admission path "
                f"has — an exception here leaks the mapped pages"))
    return _apply_justified(findings, lines)


# ---------------------------------------------------------------------------
# GR002 — double-release hazard
# ---------------------------------------------------------------------------


@ast_rule("GR002", "double-release hazard: a second release of the same "
                   "page ref on one path, or two release-loops draining "
                   "the same page list")
def rule_double_release(tree, lines, path) -> List[Finding]:
    if not _in_library(path):
        return []
    model = _model(tree, path)
    findings: List[Finding] = []
    for key, fn in model.functions.items():
        sim = _FnSim(fn)
        sim.run()
        for name, line in sim.double:
            findings.append(Finding(path, line, "GR002", "error",
                f"page ref '{name}' released twice on one path in "
                f"{_qual(key)}() — the second release corrupts the "
                f"refcount (or trips the allocator's assertion)"))
        # two loops draining the SAME page list both release per element
        release_loops: Dict[str, int] = {}
        for n in _walk_no_defs(fn):
            if not isinstance(n, (ast.For, ast.AsyncFor)):
                continue
            if not isinstance(n.iter, ast.Name) or \
                    not isinstance(n.target, ast.Name):
                continue
            body_releases = any(
                _is_release_call(c) and c.args
                and isinstance(c.args[0], ast.Name)
                and c.args[0].id == n.target.id
                for s in n.body for c in ast.walk(s)
                if isinstance(c, ast.Call))
            if not body_releases:
                continue
            if n.iter.id in release_loops:
                findings.append(Finding(path, n.lineno, "GR002", "error",
                    f"{_qual(key)}() releases the pages of "
                    f"'{n.iter.id}' in two separate loops — every "
                    f"element is double-released"))
            else:
                release_loops[n.iter.id] = n.lineno
    return _apply_justified(findings, lines)


# ---------------------------------------------------------------------------
# GR003 — terminal-vocabulary exactly-once
# ---------------------------------------------------------------------------


@ast_rule("GR003", "terminal-vocabulary exactly-once: a future completed "
                   "(set_result/set_exception, incl. deferred lambdas) "
                   "without routing through the count_terminal funnel")
def rule_terminal_exactly_once(tree, lines, path) -> List[Finding]:
    if not _in_library(path):
        return []
    model = _model(tree, path)
    funnels = _TERMINAL_FUNNELS | model.counting()
    findings: List[Finding] = []
    for key, fn in model.functions.items():
        completer_line: Optional[int] = None
        has_funnel = False
        # completion sites INCLUDE lambda/closure bodies — the deferred-
        # completion idiom must still pair with a count in the same
        # function (the frontend's _deny shape)
        for n in ast.walk(fn):
            if not isinstance(n, ast.Call):
                continue
            name = _call_name(n)
            if name in _COMPLETERS and completer_line is None:
                completer_line = n.lineno
            if name in funnels:
                has_funnel = True
        if completer_line is not None and not has_funnel:
            findings.append(Finding(path, completer_line, "GR003", "error",
                f"{_qual(key)}() completes a request future without "
                f"routing the outcome through the count_terminal "
                f"funnel — the terminal vocabulary loses this exit"))
        # double-count arm: two count_terminal calls in one suite (no
        # branch between them) count one request exit twice
        for n in [fn] + list(_walk_no_defs(fn)):
            for field in ("body", "orelse", "finalbody"):
                suite = getattr(n, field, None)
                if not isinstance(suite, list):
                    continue
                direct = [s for s in suite if isinstance(s, ast.Expr)
                          and isinstance(s.value, ast.Call)
                          and _call_name(s.value) == "count_terminal"]
                if len(direct) >= 2:
                    findings.append(Finding(path, direct[1].lineno, "GR003", "error",
                        f"{_qual(key)}() counts count_terminal twice on "
                        f"one straight-line path — one request exit "
                        f"would increment two terminal labels"))
    return _apply_justified(findings, lines)


# ---------------------------------------------------------------------------
# GR004 — unstoppable thread
# ---------------------------------------------------------------------------


def _is_thread_ctor(node: ast.AST) -> bool:
    if not isinstance(node, ast.Call):
        return False
    fn = node.func
    if isinstance(fn, ast.Attribute):
        return fn.attr == "Thread"
    return isinstance(fn, ast.Name) and fn.id == "Thread"


def _has_daemon_kwarg(call: ast.Call) -> bool:
    return any(k.arg == "daemon" and isinstance(k.value, ast.Constant)
               and k.value.value for k in call.keywords)


def _fn_has_join(fn: ast.AST) -> bool:
    return any(isinstance(n, ast.Call)
               and isinstance(n.func, ast.Attribute)
               and n.func.attr in ("join", "wait_until_finished")
               for n in ast.walk(fn))


@ast_rule("GR004", "unstoppable thread: a started Thread with no "
                   "join/stop reachable from any shutdown path "
                   "(daemon=True does not exempt — justify it)")
def rule_unstoppable_thread(tree, lines, path) -> List[Finding]:
    if not _in_library(path):
        return []
    model = _model(tree, path)
    findings: List[Finding] = []
    # class-level: a worker stored on self is stoppable iff some method
    # of the class joins (the stop()/close() convention); blind spot:
    # join-presence is per-class, not matched to the exact attribute
    joining_classes = {cname for cname, cnode in model.classes.items()
                       if any(_fn_has_join(m) for m in cnode.body
                              if isinstance(m, (ast.FunctionDef,
                                                ast.AsyncFunctionDef)))}
    for (cls, name), fn in model.functions.items():
        stoppable_class = cls in joining_classes
        fn_joins = _fn_has_join(fn)
        for n in _walk_no_defs(fn):
            if isinstance(n, ast.Assign) and _is_thread_ctor(n.value):
                tgt = n.targets[0] if len(n.targets) == 1 else None
                stored_on_self = isinstance(tgt, ast.Attribute)
                if stored_on_self and stoppable_class:
                    continue
                if isinstance(tgt, ast.Name) and (fn_joins or
                                                  stoppable_class):
                    # a local worker joined in-function, or handed to
                    # the class's joining shutdown path
                    continue
                daemon = _has_daemon_kwarg(n.value)
                findings.append(Finding(path, n.lineno, "GR004", "error",
                    f"thread started in {_qual((cls, name))}() has no "
                    f"reachable join/stop — an unstoppable thread"
                    + (" (daemon=True needs a written justification)"
                       if daemon else "")))
            elif isinstance(n, ast.Expr) and isinstance(n.value, ast.Call) \
                    and isinstance(n.value.func, ast.Attribute) \
                    and n.value.func.attr == "start" \
                    and _is_thread_ctor(n.value.func.value):
                # inline Thread(...).start(): nothing can ever join it
                daemon = _has_daemon_kwarg(n.value.func.value)
                findings.append(Finding(path, n.lineno, "GR004", "error",
                    f"anonymous Thread(...).start() in "
                    f"{_qual((cls, name))}() can never be joined — an "
                    f"unstoppable thread"
                    + (" (daemon=True needs a written justification)"
                       if daemon else "")))
    return _apply_justified(findings, lines)


# ---------------------------------------------------------------------------
# GR005 — non-atomic durable write
# ---------------------------------------------------------------------------

_NP_SAVERS = {"save", "savez", "savez_compressed"}


def _expr_mentions_tmp(node: ast.AST) -> bool:
    for n in ast.walk(node):
        if isinstance(n, ast.Name) and "tmp" in n.id.lower():
            return True
        if isinstance(n, ast.Attribute) and "tmp" in n.attr.lower():
            return True
        if isinstance(n, ast.Constant) and isinstance(n.value, str) \
                and ".tmp" in n.value:
            return True
    return False


def _fn_has_replace(fn: ast.AST) -> bool:
    return any(isinstance(n, ast.Call)
               and isinstance(n.func, ast.Attribute)
               and n.func.attr in ("replace", "rename")
               and isinstance(n.func.value, ast.Name)
               and n.func.value.id == "os"
               for n in ast.walk(fn))


@ast_rule("GR005", "non-atomic durable write: open(.., 'w')/np.save* "
                   "without the tmp + os.replace dance — a torn write "
                   "publishes a corrupt file")
def rule_atomic_durable_write(tree, lines, path) -> List[Finding]:
    if not _in_library(path):
        return []
    model = _model(tree, path)
    findings: List[Finding] = []
    for key, fn in model.functions.items():
        has_replace = _fn_has_replace(fn)
        for n in _walk_no_defs(fn):
            if not isinstance(n, ast.Call):
                continue
            name = _call_name(n)
            target: Optional[ast.AST] = None
            what = None
            if isinstance(n.func, ast.Name) and n.func.id == "open" \
                    and n.args:
                mode = None
                if len(n.args) >= 2 and isinstance(n.args[1], ast.Constant):
                    mode = n.args[1].value
                for k in n.keywords:
                    if k.arg == "mode" and isinstance(k.value, ast.Constant):
                        mode = k.value.value
                if isinstance(mode, str) and mode[:1] in ("w", "x"):
                    target, what = n.args[0], f"open(.., {mode!r})"
            elif name in _NP_SAVERS and isinstance(n.func, ast.Attribute) \
                    and isinstance(n.func.value, ast.Name) \
                    and n.func.value.id in ("np", "numpy") and n.args \
                    and not isinstance(n.args[0], ast.Name):
                # np.save("path", ...) with a direct path; np.savez(f)
                # into an open()-produced handle is the open's business
                target, what = n.args[0], f"np.{name}(..)"
            if target is None:
                continue
            if has_replace or _expr_mentions_tmp(target):
                continue
            findings.append(Finding(path, n.lineno, "GR005", "error",
                f"{_qual(key)}() writes durably via {what} without the "
                f"tmp + os.replace dance — a torn write publishes a "
                f"corrupt file"))
    return _apply_justified(findings, lines)


# ---------------------------------------------------------------------------
# the repo-wide static ownership inventory (lifetrace's ground truth)
# ---------------------------------------------------------------------------

_INVENTORY_OPS = _ACQUIRE_METHODS | {"release", "free_slot"}


class OwnershipInventory:
    """Every function span in the scanned roots that touches the
    allocator vocabulary, in SPAN units (function start..end line): the
    runtime tracer attributes each observed acquire/release callsite to
    a span, and a callsite outside every span is an analyzer blind
    spot."""

    def __init__(self):
        self.spans: List[Dict] = []

    def add_span(self, path: str, qualname: str, start: int, end: int,
                 ops: List[Tuple[str, int]]) -> None:
        self.spans.append({"path": path, "qualname": qualname,
                           "start": int(start), "end": int(end),
                           "ops": [(o, int(ln)) for o, ln in ops]})

    def attributes_callsite(self, path: str, line: int) -> bool:
        return any(s["path"] == path and s["start"] <= line <= s["end"]
                   for s in self.spans)

    def op_count(self) -> int:
        return sum(len(s["ops"]) for s in self.spans)

    def as_dict(self) -> Dict:
        return {"spans": [dict(s) for s in self.spans],
                "ops": self.op_count()}


def static_ownership_inventory(
        repo_root: str,
        roots: Sequence[str] = ("deeplearning4j_tpu",)
) -> OwnershipInventory:
    """Scan ``roots`` for functions touching the allocator vocabulary.
    The tracer's contract: every observed acquire/release callsite must
    fall inside one of these spans."""
    inv = OwnershipInventory()
    for rel in iter_py_files(roots, repo_root):
        full = os.path.join(repo_root, rel)
        try:
            with open(full, "r", encoding="utf-8") as f:
                src = f.read()
            tree = ast.parse(src)
        except (OSError, SyntaxError):
            continue
        for node in ast.walk(tree):
            if not isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                continue
            ops: List[Tuple[str, int]] = []
            for n in ast.walk(node):
                if isinstance(n, ast.Call):
                    name = _call_name(n)
                    if name in _ACQUIRE_METHODS or _is_release_call(n):
                        ops.append((name, n.lineno))
            if ops:
                inv.add_span(rel, node.name, node.lineno,
                             getattr(node, "end_lineno", node.lineno),
                             ops)
    return inv
