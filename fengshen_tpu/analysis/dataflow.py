"""Phase-1.5 of the analyzer: flow-sensitive per-file dataflow facts.

The project index (``analysis/project.py``) records *where* things
happen — calls, writes, guard scopes. The rules added by the dataflow
tier need to know *what happens next on each path*:

- **donation tracking**: a ``jax.jit(fn, donate_argnums=...)``
  binding makes specific positional arguments of every later call
  through that binding *donated* — the caller's buffer is invalidated by dispatch. The flow
  engine arms the variables passed in donated positions at each call
  site and reports any read on any later path; rebinding from the
  call's outputs (``state = step(state, ...)``) disarms, which is
  exactly the clean idiom.
- **resource lifecycle**: a small typestate engine over the declared
  acquire/release protocols in ``PROTOCOLS`` (allocator alloc/free,
  slot assignment/rollback, lane export/detach, drain, bare file
  handles). It flags a release that can be skipped by an exception
  (acquire .. raising-call .. release with no ``finally`` and no broad
  ``except`` that releases) and double-release along a single path.
- **contract extraction**: every ``fstpu_*`` metric get-or-create
  site (name, kind, label set) — cheap facts the contract rule diffs
  across files and against docs.

Everything here is pure stdlib ``ast``, runs per file with no project
state, and returns sorted tuples of primitives, so results are cached
in the ``FileSummary`` (content-sha keyed) and stay byte-deterministic
across ``PYTHONHASHSEED`` values.

The analysis is deliberately per-file: a donated callable bound in one
module and called from another is out of scope (no such site exists in
the package — bindings are ``self._step_jit``-style attributes used by
their own class). Conservatism runs toward silence: an unresolvable
``donate_argnums`` expression, an aliased resource, or a branch where
states disagree drops out of tracking instead of guessing.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, Iterator, List, Optional, Set, Tuple

# --------------------------------------------------------------------
# shared small helpers
# --------------------------------------------------------------------

_SKIP_SCOPES = (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)

_TRY_TYPES = (ast.Try,) + ((ast.TryStar,) if hasattr(ast, "TryStar")
                           else ())


def _scan(node: ast.AST) -> Iterator[ast.AST]:
    """ast.walk that does not descend into nested function bodies —
    a closure's reads happen at *its* call time, not here."""
    stack = [node]
    while stack:
        n = stack.pop()
        if isinstance(n, _SKIP_SCOPES) and n is not node:
            continue
        yield n
        stack.extend(ast.iter_child_nodes(n))


def _int_tuple(node: ast.AST) -> Optional[Tuple[int, ...]]:
    if isinstance(node, ast.Constant) and isinstance(node.value, int) \
            and not isinstance(node.value, bool):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        vals: List[int] = []
        for e in node.elts:
            if isinstance(e, ast.Constant) and \
                    isinstance(e.value, int) and \
                    not isinstance(e.value, bool):
                vals.append(e.value)
            else:
                return None
        return tuple(vals)
    return None


def _str_tuple(node: ast.AST) -> Optional[Tuple[str, ...]]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        vals: List[str] = []
        for e in node.elts:
            if isinstance(e, ast.Constant) and \
                    isinstance(e.value, str):
                vals.append(e.value)
            else:
                return None
        return tuple(vals)
    return None


def _expr_text(node: ast.AST) -> str:
    """Dotted text of a Name/Attribute chain (``self._allocator``);
    "" for anything else (calls, subscripts)."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        base = _expr_text(node.value)
        return f"{base}.{node.attr}" if base else ""
    return ""


# --------------------------------------------------------------------
# donation tracking
# --------------------------------------------------------------------


def _donate_positions(call: ast.Call,
                      defs_by_name: Dict[str, ast.AST],
                      ) -> Optional[Tuple[int, ...]]:
    """Donated positional indices of a wrapping call, or None when
    they are not statically constant. ``donate_argnames`` resolves to
    positions through the wrapped function's own def when that def is
    in the same file."""
    kws = {k.arg: k.value for k in call.keywords if k.arg}
    if "donate_argnums" in kws:
        return _int_tuple(kws["donate_argnums"])
    if "donate_argnames" in kws:
        names = _str_tuple(kws["donate_argnames"])
        if names is None or not call.args or \
                not isinstance(call.args[0], ast.Name):
            return None
        fdef = defs_by_name.get(call.args[0].id)
        if fdef is None:
            return None
        params = [a.arg for a in fdef.args.args]
        try:
            return tuple(params.index(n) for n in names)
        except ValueError:
            return None
    return None


def _find_donate_calls(value: ast.AST,
                       defs_by_name: Dict[str, ast.AST],
                       ) -> List[Tuple[ast.Call, Tuple[int, ...]]]:
    """Every call carrying a resolvable donate keyword anywhere inside
    ``value`` — sees through a wrapping call and conditional-expression
    branches."""
    hits: List[Tuple[ast.Call, Tuple[int, ...]]] = []
    for n in ast.walk(value):
        if isinstance(n, ast.Call):
            pos = _donate_positions(n, defs_by_name)
            if pos is not None:
                hits.append((n, pos))
    return hits


class _DonationCollector:
    """One pass binding donated callables to stable scope keys.

    Keys: ``qual::name`` for a local/module variable (``qual`` is the
    project-index function qual, "" at module level), ``Cls.attr`` for
    ``self.attr`` bindings and class-level assignments. The flow pass
    looks keys up through the lexical scope chain."""

    def __init__(self, tree: ast.Module) -> None:
        self.defs_by_name: Dict[str, ast.AST] = {
            n.name: n for n in ast.walk(tree)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))}
        # key -> (donated positions, bind line)
        self.bindings: Dict[str, Tuple[Tuple[int, ...], int]] = {}
        # (fdef, qual, class qual or None)
        self.functions: List[Tuple[ast.AST, str, Optional[str]]] = []
        self._walk(tree.body, "", None, in_class=False)

    def _bind(self, key: Optional[str], pos: Tuple[int, ...],
              line: int) -> None:
        if key:
            self.bindings[key] = (pos, line)

    def _target_key(self, target: ast.AST, qual: str,
                    cls: Optional[str], in_class: bool,
                    ) -> Optional[str]:
        if isinstance(target, ast.Name):
            if in_class and cls:
                return f"{cls}.{target.id}"
            return f"{qual}::{target.id}"
        if isinstance(target, ast.Attribute) and \
                isinstance(target.value, ast.Name) and \
                target.value.id == "self" and cls:
            return f"{cls}.{target.attr}"
        return None

    def _walk(self, body: List[ast.stmt], qual: str,
              cls: Optional[str], in_class: bool) -> None:
        for node in body:
            if isinstance(node, (ast.FunctionDef,
                                 ast.AsyncFunctionDef)):
                sub = f"{qual}.{node.name}" if qual else node.name
                self.functions.append((node, sub, cls))
                for dec in node.decorator_list:
                    if isinstance(dec, ast.Call):
                        pos = _donate_positions(dec, self.defs_by_name)
                        if pos is not None:
                            key = f"{cls}.{node.name}" \
                                if in_class and cls else \
                                f"{qual}::{node.name}"
                            self._bind(key, pos, node.lineno)
                self._walk(node.body, sub, cls, in_class=False)
            elif isinstance(node, ast.ClassDef):
                cq = f"{qual}.{node.name}" if qual else node.name
                self._walk(node.body, cq, cq, in_class=True)
            elif isinstance(node, ast.Assign):
                hits = _find_donate_calls(node.value, self.defs_by_name)
                possets = {p for _, p in hits}
                if len(possets) == 1:
                    pos = next(iter(possets))
                    for t in node.targets:
                        self._bind(self._target_key(t, qual, cls,
                                                    in_class),
                                   pos, node.lineno)
            elif isinstance(node, (ast.If, ast.For, ast.AsyncFor,
                                   ast.While, ast.With,
                                   ast.AsyncWith) + _TRY_TYPES):
                for field in ("body", "orelse", "finalbody"):
                    self._walk(getattr(node, field, []) or [],
                               qual, cls, in_class)
                for h in getattr(node, "handlers", []) or []:
                    self._walk(h.body, qual, cls, in_class)


class _DonationFlow:
    """Read-after-donation walk of one function body.

    State: armed variable key -> info about the donating call. A read
    of an armed key is a finding; any rebinding kills the key. ``If``
    forks and joins by union (read on *any* path is the bug); loops
    re-walk their body once so a second-iteration read of a buffer
    donated on the first iteration is seen."""

    def __init__(self, coll: _DonationCollector, fdef: ast.AST,
                 qual: str, cls: Optional[str],
                 findings: Set[Tuple]) -> None:
        self.coll = coll
        self.fdef = fdef
        self.cls = cls
        self.findings = findings
        # lexical lookup chain: "A.b.c" -> ["A.b.c", "A.b", "A", ""]
        chain = [qual]
        while "." in chain[-1]:
            chain.append(chain[-1].rsplit(".", 1)[0])
        if chain[-1]:
            chain.append("")
        self.scope_chain = chain

    def run(self) -> None:
        self._walk_body(self.fdef.body, {})

    # -- binding lookup ----------------------------------------------

    def _match_call(self, call: ast.Call,
                    ) -> Optional[Tuple[str, Tuple[int, ...], int]]:
        f = call.func
        if isinstance(f, ast.Name):
            for scope in self.scope_chain:
                entry = self.coll.bindings.get(f"{scope}::{f.id}")
                if entry is not None:
                    return (f.id,) + entry
        elif isinstance(f, ast.Attribute) and \
                isinstance(f.value, ast.Name) and \
                f.value.id == "self" and self.cls:
            entry = self.coll.bindings.get(f"{self.cls}.{f.attr}")
            if entry is not None:
                return (f"self.{f.attr}",) + entry
        return None

    @staticmethod
    def _arg_key(arg: ast.AST) -> Optional[Tuple[str, str]]:
        """(state key, display name) for a trackable donated arg."""
        if isinstance(arg, ast.Name):
            return (f"n:{arg.id}", arg.id)
        if isinstance(arg, ast.Attribute) and \
                isinstance(arg.value, ast.Name) and \
                arg.value.id == "self":
            return (f"a:{arg.attr}", f"self.{arg.attr}")
        return None

    # -- per-statement read/arm/kill ----------------------------------

    def _use(self, state: Dict[str, dict], exprs: List[ast.AST],
             kill_targets: List[ast.AST]) -> None:
        armed: Dict[str, dict] = {}
        reads: List[Tuple[str, int, int]] = []
        for expr in exprs:
            if expr is None:
                continue
            for n in _scan(expr):
                if isinstance(n, ast.Call):
                    m = self._match_call(n)
                    if m is None:
                        continue
                    callee, positions, bind_line = m
                    for p in positions:
                        if p >= len(n.args):
                            continue
                        ak = self._arg_key(n.args[p])
                        if ak is None:
                            continue
                        key, disp = ak
                        armed[key] = {
                            "var": disp, "callee": callee,
                            "bind": bind_line, "call": n.lineno}
                elif isinstance(n, ast.Name) and \
                        isinstance(n.ctx, ast.Load):
                    reads.append((f"n:{n.id}", n.lineno,
                                  n.col_offset))
                elif isinstance(n, ast.Attribute) and \
                        isinstance(n.ctx, ast.Load) and \
                        isinstance(n.value, ast.Name) and \
                        n.value.id == "self":
                    reads.append((f"a:{n.attr}", n.lineno,
                                  n.col_offset))
        # reads check against the state *before* this statement's
        # armings; earliest read of each armed key wins
        for key, line, col in sorted(reads, key=lambda r: (r[1], r[2])):
            info = state.get(key)
            if info is None:
                continue
            self.findings.add((info["var"], info["callee"],
                               info["bind"], info["call"], line, col))
            del state[key]
        state.update(armed)
        for t in kill_targets:
            self._kill_target(state, t)

    def _kill_target(self, state: Dict[str, dict],
                     target: ast.AST) -> None:
        if isinstance(target, ast.Name):
            state.pop(f"n:{target.id}", None)
        elif isinstance(target, ast.Attribute) and \
                isinstance(target.value, ast.Name) and \
                target.value.id == "self":
            state.pop(f"a:{target.attr}", None)
        elif isinstance(target, (ast.Tuple, ast.List)):
            for e in target.elts:
                self._kill_target(state, e)
        elif isinstance(target, ast.Starred):
            self._kill_target(state, target.value)

    # -- control flow -------------------------------------------------

    @staticmethod
    def _join(a: Optional[Dict[str, dict]],
              b: Optional[Dict[str, dict]],
              ) -> Optional[Dict[str, dict]]:
        if a is None:
            return None if b is None else dict(b)
        if b is None:
            return dict(a)
        out = dict(a)
        for k, v in b.items():
            out.setdefault(k, v)
        return out

    def _walk_body(self, body: List[ast.stmt],
                   state: Optional[Dict[str, dict]],
                   ) -> Optional[Dict[str, dict]]:
        for st in body:
            if state is None:
                return None
            state = self._walk_stmt(st, state)
        return state

    def _walk_stmt(self, st: ast.stmt, state: Dict[str, dict],
                   ) -> Optional[Dict[str, dict]]:
        if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef,
                           ast.ClassDef)):
            state.pop(f"n:{st.name}", None)
            return state
        if isinstance(st, ast.Return):
            self._use(state, [st.value], [])
            return None
        if isinstance(st, ast.Raise):
            self._use(state, [st.exc, st.cause], [])
            return None
        if isinstance(st, (ast.Break, ast.Continue)):
            return None
        if isinstance(st, ast.Assign):
            # subscript/attribute targets read their base expression
            # (``x[0] = v`` writes into the donated buffer — a read)
            extra = [t for t in st.targets
                     if isinstance(t, (ast.Subscript, ast.Attribute))]
            self._use(state, [st.value] + extra, st.targets)
            return state
        if isinstance(st, ast.AugAssign):
            self._use(state, [st.target, st.value], [st.target])
            return state
        if isinstance(st, ast.AnnAssign):
            self._use(state, [st.value],
                      [st.target] if st.value is not None else [])
            return state
        if isinstance(st, ast.Expr):
            self._use(state, [st.value], [])
            return state
        if isinstance(st, ast.Assert):
            self._use(state, [st.test, st.msg], [])
            return state
        if isinstance(st, ast.Delete):
            for t in st.targets:
                self._kill_target(state, t)
            return state
        if isinstance(st, ast.If):
            self._use(state, [st.test], [])
            s1 = self._walk_body(st.body, dict(state))
            s2 = self._walk_body(st.orelse, dict(state)) \
                if st.orelse else dict(state)
            return self._join(s1, s2)
        if isinstance(st, (ast.For, ast.AsyncFor)):
            self._use(state, [st.iter], [])
            self._kill_target(state, st.target)
            s1 = self._walk_body(st.body, dict(state))
            entry2 = self._join(dict(state), s1)
            if entry2 is not None:
                self._kill_target(entry2, st.target)
            s2 = self._walk_body(st.body, entry2) \
                if entry2 is not None else None
            after = self._join(self._join(s1, s2), dict(state))
            if st.orelse and after is not None:
                after = self._walk_body(st.orelse, after)
            return after
        if isinstance(st, ast.While):
            self._use(state, [st.test], [])
            s1 = self._walk_body(st.body, dict(state))
            entry2 = self._join(dict(state), s1)
            s2 = self._walk_body(st.body, entry2) \
                if entry2 is not None else None
            after = self._join(self._join(s1, s2), dict(state))
            if st.orelse and after is not None:
                after = self._walk_body(st.orelse, after)
            return after
        if isinstance(st, (ast.With, ast.AsyncWith)):
            self._use(state, [it.context_expr for it in st.items], [])
            for it in st.items:
                if it.optional_vars is not None:
                    self._kill_target(state, it.optional_vars)
            return self._walk_body(st.body, state)
        if isinstance(st, _TRY_TYPES):
            sb = self._walk_body(st.body, dict(state))
            base = self._join(dict(state), sb) or dict(state)
            cur = sb
            if cur is not None and st.orelse:
                cur = self._walk_body(st.orelse, cur)
            outs = [cur] if cur is not None else []
            for h in st.handlers:
                hstate = dict(base)
                if h.name:
                    hstate.pop(f"n:{h.name}", None)
                sh = self._walk_body(h.body, hstate)
                if sh is not None:
                    outs.append(sh)
            merged: Optional[Dict[str, dict]] = None
            for o in outs:
                merged = self._join(merged, o)
            if st.finalbody:
                fentry = merged if merged is not None else dict(base)
                merged = self._walk_body(st.finalbody, fentry)
            return merged
        return state  # Pass/Import/Global/Nonlocal/...


def analyze_donation_use(tree: ast.Module,
                         ) -> List[Tuple[str, str, int, int, int,
                                         int]]:
    """Read-after-donation findings for one file.

    Returns sorted ``(var, callee, bind_line, call_line, read_line,
    read_col)`` tuples: variable ``var`` was passed in a donated
    position to ``callee`` (whose donate binding is at ``bind_line``)
    at ``call_line`` and read again at ``read_line`` on some path."""
    coll = _DonationCollector(tree)
    if not coll.bindings:
        return []
    findings: Set[Tuple] = set()
    for fdef, qual, cls in coll.functions:
        _DonationFlow(coll, fdef, qual, cls, findings).run()
    return sorted(findings,
                  key=lambda f: (f[4], f[5], f[0], f[3]))


# --------------------------------------------------------------------
# resource-lifecycle typestate
# --------------------------------------------------------------------

#: declared acquire/release protocols. ``receiver`` (regex) restricts
#: matches to calls whose receiver text matches; ``bare_only``
#: restricts the acquire to a bare-name call (``open(...)`` but not
#: ``os.open``/``img.open``). ``leak`` enables the
#: release-can-be-skipped-by-an-exception check; ``double`` the
#: released-twice-on-one-path check. Context-managed acquires
#: (``with open(...) as f``) are clean by construction and never
#: tracked; an allocator that returns its reserved null block is
#: handled by the ``is None`` branch pruning in the walker.
PROTOCOLS: Tuple[Dict[str, object], ...] = (
    {"name": "block-allocator", "acquire": ("alloc",),
     "release": ("free",), "receiver": r"allocat", "bare_only": False,
     "leak": True, "double": True},
    {"name": "slot-pool", "acquire": ("assign_slot", "assign_paged"),
     "release": ("rollback_slots", "reset_free_slots"),
     "receiver": None, "bare_only": False,
     "leak": False, "double": True},
    {"name": "lane-handoff", "acquire": ("export_lane",),
     "release": ("detach_lane",), "receiver": None, "bare_only": False,
     "leak": False, "double": True},
    {"name": "serve-drain", "acquire": ("begin_drain",),
     "release": ("idle",), "receiver": None, "bare_only": False,
     "leak": False, "double": True},
    {"name": "file-handle", "acquire": ("open",),
     "release": ("close",), "receiver": None, "bare_only": True,
     "leak": True, "double": True},
)

_HELD, _RELEASED, _ESCAPED = "held", "released", "escaped"


class _Resource:
    __slots__ = ("proto", "var", "line", "col", "state", "rel_line",
                 "leaked")

    def __init__(self, proto: int, var: str, line: int,
                 col: int) -> None:
        self.proto = proto
        self.var = var
        self.line = line
        self.col = col
        self.state = _HELD
        self.rel_line = 0
        self.leaked = False

    def clone(self) -> "_Resource":
        r = _Resource(self.proto, self.var, self.line, self.col)
        r.state = self.state
        r.rel_line = self.rel_line
        r.leaked = self.leaked
        return r


def _call_parts(call: ast.Call) -> Tuple[Optional[str], Optional[str]]:
    f = call.func
    if isinstance(f, ast.Name):
        return ("", f.id)
    if isinstance(f, ast.Attribute):
        return (_expr_text(f.value), f.attr)
    return (None, None)


def _match_protocol(call: ast.Call, phase: str) -> Optional[int]:
    recv, leaf = _call_parts(call)
    if leaf is None:
        return None
    for i, proto in enumerate(PROTOCOLS):
        if leaf not in proto[phase]:
            continue
        if proto["bare_only"] and phase == "acquire" and recv != "":
            continue
        pat = proto["receiver"]
        if pat is not None and not re.search(pat, recv or ""):
            continue
        return i
    return None


def _broad_handler(h: ast.excepthandler) -> bool:
    def broad(t: ast.AST) -> bool:
        return isinstance(t, ast.Name) and \
            t.id in ("Exception", "BaseException")
    if h.type is None:
        return True
    if broad(h.type):
        return True
    return isinstance(h.type, ast.Tuple) and \
        any(broad(e) for e in h.type.elts)


class _LifecycleFlow:
    """Typestate walk of one function body over ``PROTOCOLS``."""

    def __init__(self, fdef: ast.AST, findings: Set[Tuple]) -> None:
        self.fdef = fdef
        self.findings = findings
        self._protected: List[Set[int]] = []

    def run(self) -> None:
        self._walk_body(self.fdef.body, {})

    # -- statement-level semantics ------------------------------------

    def _stmt_calls(self, st: ast.stmt) -> List[ast.Call]:
        calls = [n for n in _scan(st) if isinstance(n, ast.Call)]
        calls.sort(key=lambda c: (c.lineno, c.col_offset))
        return calls

    def _release_candidates(self, call: ast.Call) -> List[str]:
        names: List[str] = []
        recv, _ = _call_parts(call)
        if recv and "." not in recv and recv != "self":
            names.append(recv)
        for a in call.args:
            if isinstance(a, ast.Name):
                names.append(a.id)
        return names

    def _process_calls(self, st: ast.stmt,
                       state: Dict[str, _Resource],
                       skip_acquire_target: Optional[str] = None,
                       ) -> None:
        calls = self._stmt_calls(st)
        protected: Set[int] = set()
        for s in self._protected:
            protected |= s
        for call in calls:
            rel = _match_protocol(call, "release")
            acq = _match_protocol(call, "acquire")
            if rel is not None:
                self._do_release(call, rel, state)
                continue
            if acq is not None:
                continue  # the acquire itself can't leak its result
            # a plain call may raise: every held, unprotected resource
            # of a leak-checked protocol escapes cleanup on that path
            for var in sorted(state):
                r = state[var]
                if r.state != _HELD or r.leaked or \
                        var == skip_acquire_target:
                    continue
                proto = PROTOCOLS[r.proto]
                if not proto["leak"] or r.proto in protected:
                    continue
                _, leaf = _call_parts(call)
                self.findings.add((
                    "leak", proto["name"], r.var, r.line, r.col,
                    call.lineno, leaf or "call"))
                r.leaked = True

    def _do_release(self, call: ast.Call, proto_idx: int,
                    state: Dict[str, _Resource]) -> None:
        cands = self._release_candidates(call)
        target: Optional[_Resource] = None
        for name in cands:
            r = state.get(name)
            if r is not None and r.proto == proto_idx:
                target = r
                break
        if target is None:
            held = [state[v] for v in sorted(state)
                    if state[v].proto == proto_idx and
                    state[v].state == _HELD]
            if len(held) == 1 and not cands:
                target = held[0]
        if target is None:
            return
        if target.state == _RELEASED and \
                PROTOCOLS[proto_idx]["double"]:
            self.findings.add((
                "double-release", PROTOCOLS[proto_idx]["name"],
                target.var, call.lineno, call.col_offset,
                target.rel_line, ""))
        elif target.state == _HELD:
            target.state = _RELEASED
            target.rel_line = call.lineno
        # ESCAPED: ownership ambiguous — stay silent

    def _escape_if_referenced(self, value: Optional[ast.AST],
                              state: Dict[str, _Resource]) -> None:
        if value is None:
            return
        for n in _scan(value):
            if isinstance(n, ast.Name) and n.id in state:
                state[n.id].state = _ESCAPED
            elif isinstance(n, (ast.Yield, ast.YieldFrom)):
                pass  # children visited anyway

    # -- control flow -------------------------------------------------

    @staticmethod
    def _join(a: Optional[Dict[str, _Resource]],
              b: Optional[Dict[str, _Resource]],
              ) -> Optional[Dict[str, _Resource]]:
        if a is None:
            return None if b is None else b
        if b is None:
            return a
        out: Dict[str, _Resource] = {}
        for k in sorted(set(a) | set(b)):
            ra, rb = a.get(k), b.get(k)
            if ra is None or rb is None:
                out[k] = ra or rb
            elif ra.state == rb.state:
                out[k] = ra
            else:
                merged = ra.clone()
                merged.state = _ESCAPED
                out[k] = merged
        return out

    @staticmethod
    def _fork(state: Dict[str, _Resource]) -> Dict[str, _Resource]:
        return {k: v.clone() for k, v in state.items()}

    def _walk_body(self, body: List[ast.stmt],
                   state: Optional[Dict[str, _Resource]],
                   ) -> Optional[Dict[str, _Resource]]:
        for st in body:
            if state is None:
                return None
            state = self._walk_stmt(st, state)
        return state

    def _none_pruned(self, test: ast.AST, state: Dict[str, _Resource],
                     ) -> Tuple[Dict[str, _Resource],
                                Dict[str, _Resource]]:
        """(body state, else state) for an If, dropping the resource
        on the branch where ``v is None`` holds — the allocator's
        exhaustion/null-block return means nothing was acquired."""
        body_state, else_state = self._fork(state), self._fork(state)
        if isinstance(test, ast.Compare) and \
                isinstance(test.left, ast.Name) and \
                len(test.ops) == 1 and \
                len(test.comparators) == 1 and \
                isinstance(test.comparators[0], ast.Constant) and \
                test.comparators[0].value is None and \
                test.left.id in state:
            if isinstance(test.ops[0], ast.Is):
                body_state.pop(test.left.id, None)
            elif isinstance(test.ops[0], ast.IsNot):
                else_state.pop(test.left.id, None)
        return body_state, else_state

    def _walk_stmt(self, st: ast.stmt, state: Dict[str, _Resource],
                   ) -> Optional[Dict[str, _Resource]]:
        if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef,
                           ast.ClassDef)):
            return state
        if isinstance(st, ast.Return):
            self._escape_if_referenced(st.value, state)
            return None
        if isinstance(st, ast.Raise):
            self._process_calls(st, state)
            return None
        if isinstance(st, (ast.Break, ast.Continue)):
            return None
        if isinstance(st, ast.Assign):
            acquired_var: Optional[str] = None
            if len(st.targets) == 1 and \
                    isinstance(st.targets[0], ast.Name) and \
                    isinstance(st.value, ast.Call):
                acq = _match_protocol(st.value, "acquire")
                if acq is not None:
                    acquired_var = st.targets[0].id
            self._process_calls(st, state,
                                skip_acquire_target=acquired_var)
            # aliasing / storing a live resource hands ownership off
            if any(isinstance(t, (ast.Attribute, ast.Subscript))
                   for t in st.targets) or \
                    (isinstance(st.value, ast.Name) and
                     st.value.id in state):
                self._escape_if_referenced(st.value, state)
            for t in st.targets:
                if isinstance(t, ast.Name):
                    state.pop(t.id, None)
            if acquired_var is not None:
                state[acquired_var] = _Resource(
                    _match_protocol(st.value, "acquire"),
                    acquired_var, st.lineno, st.col_offset)
            return state
        if isinstance(st, (ast.AugAssign, ast.AnnAssign, ast.Expr,
                           ast.Assert, ast.Delete)):
            self._process_calls(st, state)
            if isinstance(st, ast.Expr):
                self._escape_if_yield(st.value, state)
            return state
        if isinstance(st, ast.If):
            self._process_calls_in_expr(st.test, state)
            bstate, estate = self._none_pruned(st.test, state)
            s1 = self._walk_body(st.body, bstate)
            s2 = self._walk_body(st.orelse, estate) if st.orelse \
                else estate
            return self._join(s1, s2)
        if isinstance(st, (ast.For, ast.AsyncFor)):
            self._process_calls_in_expr(st.iter, state)
            s1 = self._walk_body(st.body, self._fork(state))
            after = self._join(s1, state)
            if st.orelse and after is not None:
                after = self._walk_body(st.orelse, after)
            return after
        if isinstance(st, ast.While):
            self._process_calls_in_expr(st.test, state)
            s1 = self._walk_body(st.body, self._fork(state))
            after = self._join(s1, state)
            if st.orelse and after is not None:
                after = self._walk_body(st.orelse, after)
            return after
        if isinstance(st, (ast.With, ast.AsyncWith)):
            # ``with open(...) as f`` is release-by-construction;
            # other context managers may raise like any call
            for it in st.items:
                if not (isinstance(it.context_expr, ast.Call) and
                        _match_protocol(it.context_expr, "acquire")
                        is not None):
                    self._process_calls_in_expr(it.context_expr, state)
            return self._walk_body(st.body, state)
        if isinstance(st, _TRY_TYPES):
            protected = self._try_protection(st)
            self._protected.append(protected)
            sb = self._walk_body(st.body, self._fork(state))
            self._protected.pop()
            base = self._join(self._fork(state), sb)
            cur = sb
            if cur is not None and st.orelse:
                cur = self._walk_body(st.orelse, cur)
            outs = [cur] if cur is not None else []
            for h in st.handlers:
                sh = self._walk_body(h.body, self._fork(base))
                if sh is not None:
                    outs.append(sh)
            merged: Optional[Dict[str, _Resource]] = None
            for o in outs:
                merged = self._join(merged, o)
            if st.finalbody:
                fentry = merged if merged is not None \
                    else self._fork(base)
                merged = self._walk_body(st.finalbody, fentry)
            return merged
        return state

    def _process_calls_in_expr(self, expr: Optional[ast.AST],
                               state: Dict[str, _Resource]) -> None:
        if expr is not None:
            wrapper = ast.Expr(value=expr)
            ast.copy_location(wrapper, expr)
            self._process_calls(wrapper, state)

    def _escape_if_yield(self, value: ast.AST,
                         state: Dict[str, _Resource]) -> None:
        for n in _scan(value):
            if isinstance(n, (ast.Yield, ast.YieldFrom)) and \
                    n.value is not None:
                self._escape_if_referenced(n.value, state)

    def _try_protection(self, st: ast.AST) -> Set[int]:
        """Protocols whose release provably runs when the try body
        raises: a release call in ``finally`` or in a broad handler."""
        nodes: List[ast.AST] = list(st.finalbody)
        for h in st.handlers:
            if _broad_handler(h):
                nodes.extend(h.body)
        prot: Set[int] = set()
        for node in nodes:
            for n in _scan(node):
                if isinstance(n, ast.Call):
                    idx = _match_protocol(n, "release")
                    if idx is not None:
                        prot.add(idx)
        return prot


def analyze_lifecycle(tree: ast.Module,
                      ) -> List[Tuple[str, str, str, int, int, int,
                                      str]]:
    """Typestate findings for one file, sorted.

    ``("leak", protocol, var, acq_line, acq_col, witness_line,
    witness_call)``: the resource acquired at ``acq_line`` has no
    release on the path where the call at ``witness_line`` raises.
    ``("double-release", protocol, var, line, col, first_rel_line,
    "")``: released again at ``line`` after ``first_rel_line`` on one
    path."""
    findings: Set[Tuple] = set()
    for n in ast.walk(tree):
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
            _LifecycleFlow(n, findings).run()
    return sorted(findings, key=lambda f: (f[3], f[4], f[0], f[2]))


# --------------------------------------------------------------------
# metric registration sites
# --------------------------------------------------------------------

_METRIC_KINDS = ("counter", "gauge", "histogram")


def extract_metrics(tree: ast.Module,
                    ) -> List[Tuple[str, str, Tuple[str, ...], int,
                                    int]]:
    """Sorted ``(name, kind, labelnames, line, col)`` for every
    ``fstpu_*`` registry get-or-create site with a statically constant
    name (a string literal or a module-level string constant).
    Dynamically named families (loop variables, f-strings) are
    invisible here and belong on the metric-contract allowlist."""
    consts = {
        node.targets[0].id: node.value.value
        for node in tree.body
        if isinstance(node, ast.Assign) and len(node.targets) == 1 and
        isinstance(node.targets[0], ast.Name) and
        isinstance(node.value, ast.Constant) and
        isinstance(node.value.value, str)}
    out: List[Tuple[str, str, Tuple[str, ...], int, int]] = []
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call) and
                isinstance(node.func, ast.Attribute) and
                node.func.attr in _METRIC_KINDS and node.args):
            continue
        a0 = node.args[0]
        if isinstance(a0, ast.Constant) and isinstance(a0.value, str):
            name = a0.value
        elif isinstance(a0, ast.Name):
            name = consts.get(a0.id, "")
        else:
            continue
        if not name.startswith("fstpu_"):
            continue
        lab_node: Optional[ast.AST] = None
        for k in node.keywords:
            if k.arg == "labelnames":
                lab_node = k.value
        if lab_node is None and len(node.args) > 2:
            lab_node = node.args[2]
        labels: Tuple[str, ...] = ()
        if lab_node is not None:
            resolved = _str_tuple(lab_node)
            if resolved is None:
                continue  # unverifiable label expression
            labels = resolved
        out.append((name, node.func.attr, labels, node.lineno,
                    node.col_offset))
    return sorted(out)


_DOC_ROW = re.compile(
    r"^\|\s*`(?P<name>fstpu_[a-z0-9_]+)"
    r"(?:\{(?P<labels>[^}`]*)\})?`\s*\|\s*"
    r"(?P<kind>counter|gauge|histogram)\b")


def parse_metric_docs(text: str,
                      ) -> Dict[str, Tuple[Tuple[str, ...], str, int]]:
    """The documented metric families out of a markdown metrics table:
    name -> (sorted labelnames, kind, doc line). Rows look like
    ``| `fstpu_http_requests_total{route,code}` | counter | ... |``."""
    docs: Dict[str, Tuple[Tuple[str, ...], str, int]] = {}
    for i, line in enumerate(text.splitlines(), 1):
        m = _DOC_ROW.match(line.strip())
        if m and m.group("name") not in docs:
            raw = m.group("labels") or ""
            labels = tuple(sorted(
                x.strip() for x in raw.split(",") if x.strip()))
            docs[m.group("name")] = (labels, m.group("kind"), i)
    return docs
