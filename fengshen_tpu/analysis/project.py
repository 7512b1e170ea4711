"""Phase 1 of the two-phase analyzer: the whole-package project index.

Per-file rules (phase 2a) see one tree at a time; the concurrency
rules (phase 2b: ``unguarded-shared-state``, ``blocking-under-lock``,
``lock-order``) need facts no single file contains — which class owns
which ``threading.Lock``, which helper is only ever called with that
lock held, which call chain crosses a module boundary into a blocking
socket read. This module builds that view:

- **per-class inventory**: attributes assigned anywhere in the class,
  lock-family attributes (``self._lock = threading.Lock()`` and
  friends), waitables (Event/Queue), threads, jitted callables, and
  attribute *types* when the right-hand side constructs a
  package-internal class (``self.router = Router(...)``) — the hook
  that lets the call graph cross object boundaries
- **guard scopes**: every ``with <lock>:`` body, with the lock
  resolved to a stable identity (``module::Class.attr`` /
  ``module::VAR`` / ``module::fn.<local>``)
- **call graph**: package-internal edges resolved through import
  aliases, ``self.method``, typed attributes, and module singletons
  (``REGISTRY = MetricsRegistry()`` then ``registry.REGISTRY.count``)
- **fixpoints** over the graph: functions *always* called with a lock
  held (so ``_tick_locked``-style helpers don't read as unguarded),
  the transitive blocking-call closure, the transitive lock-
  acquisition closure, and thread-confined private methods

The index is pure stdlib, content-hash cached (``--index-cache``) and
memoised in-process on file stats, and every iteration order is
sorted, so ``--json`` output stays byte-deterministic regardless of
``PYTHONHASHSEED`` or cache state.
"""

from __future__ import annotations

import ast
import dataclasses
import hashlib
import io
import json
import os
import re
import tokenize
from typing import Dict, Iterable, Iterator, List, Optional, Set, Tuple

from fengshen_tpu.analysis import dataflow

INDEX_CACHE_VERSION = 5

#: filled by every build_index() call — files seen, cache hit/miss
#: split, and whether the in-process memo short-circuited the build.
#: The CLI surfaces this via ``--stats`` (perf budget for the
#: analyzer itself: the warm path must stay cheap as rules grow).
LAST_BUILD_STATS: Dict[str, int] = {
    "files": 0, "cache_hits": 0, "cache_misses": 0, "memo_hit": 0}

#: constructor qualnames that make an attribute/variable a *guard*
LOCK_FACTORIES = {
    "threading.Lock": "Lock", "threading.RLock": "RLock",
    "threading.Condition": "Condition",
    "threading.Semaphore": "Semaphore",
    "threading.BoundedSemaphore": "BoundedSemaphore",
    "multiprocessing.Lock": "Lock", "multiprocessing.RLock": "RLock",
}
#: constructors whose instances block on wait()/get()/put()/join()
WAITABLE_FACTORIES = {
    "threading.Event": "Event",
    "queue.Queue": "Queue", "queue.SimpleQueue": "Queue",
    "queue.LifoQueue": "Queue", "queue.PriorityQueue": "Queue",
}
THREAD_FACTORIES = {"threading.Thread": "Thread"}
#: wrapping a function in these makes *calling* it a device dispatch
JIT_FACTORIES = {"jax.jit", "jax.pmap"}

#: free calls that block the calling thread (network, child process,
#: host sleep, device sync) — the direct seeds of blocking-under-lock
BLOCKING_FREE_CALLS = {
    "time.sleep": "time.sleep()",
    "urllib.request.urlopen": "urllib.request.urlopen()",
    "socket.create_connection": "socket.create_connection()",
    "select.select": "select.select()",
    "subprocess.run": "subprocess.run()",
    "subprocess.call": "subprocess.call()",
    "subprocess.check_call": "subprocess.check_call()",
    "subprocess.check_output": "subprocess.check_output()",
    "subprocess.Popen": "subprocess.Popen()",
    "requests.get": "requests.get()", "requests.post": "requests.post()",
    "requests.put": "requests.put()", "requests.request":
        "requests.request()",
    "jax.device_get": "jax.device_get()",
}
#: sync methods that block regardless of receiver type
BLOCKING_ANY_METHODS = {"block_until_ready": ".block_until_ready()"}

INIT_METHODS = frozenset({"__init__", "__post_init__", "__new__",
                          "__del__", "__set_name__"})

#: container/deque/dict/set methods that mutate the receiver in place
MUTATOR_METHODS = frozenset({
    "append", "appendleft", "extend", "extendleft", "insert", "pop",
    "popleft", "popitem", "remove", "clear", "add", "discard",
    "update", "setdefault", "sort", "reverse", "rotate",
})

_SUPPRESS_RE = re.compile(
    r"#\s*fslint:\s*disable(?:=(?P<rules>[\w,\- ]+))?")


# -- shared file-level helpers (engine.py imports these) --------------


def collect_aliases(tree: ast.Module) -> Dict[str, str]:
    """local name -> dotted origin, from import statements."""
    aliases: Dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                aliases[a.asname or a.name.split(".")[0]] = \
                    a.name if a.asname else a.name.split(".")[0]
        elif isinstance(node, ast.ImportFrom) and node.module:
            prefix = ("." * node.level) + node.module
            for a in node.names:
                if a.name == "*":
                    continue
                aliases[a.asname or a.name] = f"{prefix}.{a.name}"
    return aliases


def collect_comments(source: str) -> Dict[int, str]:
    comments: Dict[int, str] = {}
    try:
        for tok in tokenize.generate_tokens(io.StringIO(source).readline):
            if tok.type == tokenize.COMMENT:
                comments[tok.start[0]] = tok.string
    except (tokenize.TokenError, IndentationError, SyntaxError):
        pass  # ast.parse already succeeded; comment map is best-effort
    return comments


def collect_suppressions(
        comments: Dict[int, str]) -> Dict[int, frozenset]:
    """line -> suppressed rule ids (empty frozenset = all rules)."""
    out: Dict[int, frozenset] = {}
    for line, text in comments.items():
        m = _SUPPRESS_RE.search(text)
        if not m:
            continue
        rules = m.group("rules")
        out[line] = frozenset(
            r.strip() for r in rules.split(",") if r.strip()) \
            if rules else frozenset()
    return out


def iter_py_files(paths: Iterable[str]) -> Iterator[str]:
    for path in paths:
        if os.path.isfile(path):
            yield path
            continue
        if not os.path.isdir(path):
            # a typo'd path must fail LOUDLY, not lint nothing and
            # report the tree clean (a vacuous CI gate)
            raise FileNotFoundError(f"no such file or directory: {path}")
        for dirpath, dirnames, filenames in os.walk(path):
            dirnames[:] = sorted(
                d for d in dirnames
                if d not in ("__pycache__", ".git", ".venv"))
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    yield os.path.join(dirpath, fn)


def module_name_for(path: str) -> str:
    """Dotted import name, by climbing ``__init__.py`` parents.

    Files outside any package get their stem (made unique enough by
    the directory name) — lock identities only need to be stable."""
    path = os.path.abspath(path)
    parts = [os.path.splitext(os.path.basename(path))[0]]
    d = os.path.dirname(path)
    while os.path.exists(os.path.join(d, "__init__.py")):
        parts.append(os.path.basename(d))
        parent = os.path.dirname(d)
        if parent == d:
            break
        d = parent
    if parts[0] == "__init__":
        parts = parts[1:] or parts
    return ".".join(reversed(parts))


# -- summaries --------------------------------------------------------


@dataclasses.dataclass
class ClassSummary:
    name: str
    line: int
    attrs: List[str]                    # every self.X ever assigned
    lock_attrs: Dict[str, str]          # attr -> Lock/RLock/Condition/…
    waitable_attrs: Dict[str, str]      # attr -> Event/Queue
    thread_attrs: List[str]
    jit_attrs: List[str]
    attr_types: Dict[str, str]          # attr -> constructed class ref
    thread_targets: List[str]           # methods run on owned threads
    methods: List[str]

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "ClassSummary":
        return cls(**d)


@dataclasses.dataclass
class FunctionSummary:
    qual: str            # "Class.method", "func", "func.inner"
    cls: Optional[str]
    name: str
    line: int
    # (attr, line, col, guards) — self.attr mutations with the lock
    # ids lexically held at the site
    writes: List[Tuple[str, int, int, Tuple[str, ...]]]
    # (callee spec, line, col, guards); spec kinds:
    #   "self:meth" | "obj:attr.meth" | "name:f" | "qual:a.b.c"
    calls: List[Tuple[str, int, int, Tuple[str, ...]]]
    # (line, col, description, exempt-lock-or-"", guards)
    blocking: List[Tuple[int, int, str, str, Tuple[str, ...]]]
    # (lock id, line, col, locks already held)
    acquisitions: List[Tuple[str, int, int, Tuple[str, ...]]]

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "FunctionSummary":
        return cls(
            qual=d["qual"], cls=d["cls"], name=d["name"], line=d["line"],
            writes=[tuple(w[:3]) + (tuple(w[3]),) for w in d["writes"]],
            calls=[tuple(c[:3]) + (tuple(c[3]),) for c in d["calls"]],
            blocking=[tuple(b[:4]) + (tuple(b[4]),)
                      for b in d["blocking"]],
            acquisitions=[tuple(a[:3]) + (tuple(a[3]),)
                          for a in d["acquisitions"]])


@dataclasses.dataclass
class FileSummary:
    relpath: str
    module: str
    classes: Dict[str, ClassSummary]
    functions: Dict[str, FunctionSummary]   # keyed by qual
    module_locks: Dict[str, str]            # var -> lock kind
    module_waitables: Dict[str, str]
    module_jit_vars: List[str]
    module_var_types: Dict[str, str]        # var -> constructed class
    module_thread_targets: List[str]        # fns run on module threads
    suppressions: Dict[int, frozenset]
    parse_error: Optional[str] = None
    # dataflow-tier facts (analysis/dataflow.py), computed at
    # summarise time so warm-cache runs never re-parse:
    # (var, callee, bind_line, call_line, read_line, read_col)
    donation_findings: List[Tuple] = dataclasses.field(
        default_factory=list)
    # (kind, protocol, var, line, col, other_line, detail)
    lifecycle_findings: List[Tuple] = dataclasses.field(
        default_factory=list)
    # (name, kind, labelnames, line, col)
    metrics: List[Tuple] = dataclasses.field(default_factory=list)

    def to_dict(self) -> dict:
        return {
            "relpath": self.relpath, "module": self.module,
            "classes": {k: v.to_dict()
                        for k, v in sorted(self.classes.items())},
            "functions": {k: v.to_dict()
                          for k, v in sorted(self.functions.items())},
            "module_locks": dict(sorted(self.module_locks.items())),
            "module_waitables":
                dict(sorted(self.module_waitables.items())),
            "module_jit_vars": sorted(self.module_jit_vars),
            "module_var_types":
                dict(sorted(self.module_var_types.items())),
            "module_thread_targets": sorted(self.module_thread_targets),
            "suppressions": {str(k): sorted(v) for k, v in
                             sorted(self.suppressions.items())},
            "parse_error": self.parse_error,
            "donation_findings": [list(t) for t in
                                  self.donation_findings],
            "lifecycle_findings": [list(t) for t in
                                   self.lifecycle_findings],
            "metrics": [[t[0], t[1], list(t[2]), t[3], t[4]]
                        for t in self.metrics],
        }

    @classmethod
    def from_dict(cls, d: dict) -> "FileSummary":
        return cls(
            relpath=d["relpath"], module=d["module"],
            classes={k: ClassSummary.from_dict(v)
                     for k, v in d["classes"].items()},
            functions={k: FunctionSummary.from_dict(v)
                       for k, v in d["functions"].items()},
            module_locks=d["module_locks"],
            module_waitables=d["module_waitables"],
            module_jit_vars=list(d["module_jit_vars"]),
            module_var_types=d["module_var_types"],
            module_thread_targets=list(d["module_thread_targets"]),
            suppressions={int(k): frozenset(v) for k, v in
                          d["suppressions"].items()},
            parse_error=d["parse_error"],
            donation_findings=[tuple(t) for t in
                               d["donation_findings"]],
            lifecycle_findings=[tuple(t) for t in
                                d["lifecycle_findings"]],
            metrics=[(t[0], t[1], tuple(t[2]), t[3], t[4])
                     for t in d["metrics"]])


# -- per-file summarisation -------------------------------------------


class _FileSummarizer:
    """One lexical walk of a file, guard-stack aware."""

    def __init__(self, relpath: str, module: str, tree: ast.Module,
                 source: str) -> None:
        self.relpath = relpath
        self.module = module
        self.aliases = collect_aliases(tree)
        self.classes: Dict[str, ClassSummary] = {}
        self.functions: Dict[str, FunctionSummary] = {}
        self.module_locks: Dict[str, str] = {}
        self.module_waitables: Dict[str, str] = {}
        self.module_jit_vars: List[str] = []
        self.module_var_types: Dict[str, str] = {}
        self.module_thread_targets: List[str] = []
        self.suppressions = collect_suppressions(
            collect_comments(source))
        self._scan_module_vars(tree)
        self._pre_scan_classes(tree)
        for node in tree.body:
            self._visit_toplevel(node, cls=None, prefix="")

    # the dotted origin of an expression, through import aliases
    def _qual(self, node: ast.AST) -> Optional[str]:
        if isinstance(node, ast.Name):
            return self.aliases.get(node.id, node.id)
        if isinstance(node, ast.Attribute):
            base = self._qual(node.value)
            return None if base is None else f"{base}.{node.attr}"
        return None

    def _factory_kind(self, value: ast.AST, table: Dict[str, str],
                      ) -> Optional[str]:
        if isinstance(value, ast.Call):
            qn = self._qual(value.func)
            if qn in table:
                return table[qn]
        return None

    def _is_jit_value(self, value: ast.AST) -> bool:
        if not isinstance(value, ast.Call):
            return False
        qn = self._qual(value.func)
        if qn in JIT_FACTORIES:
            return True
        if qn in ("functools.partial", "partial") and value.args:
            return self._qual(value.args[0]) in JIT_FACTORIES
        return False

    def _constructed_class(self, value: ast.AST) -> Optional[str]:
        """``Router(...)`` -> the (possibly dotted) class reference.

        Sees through the default-argument idiom (``metrics or
        MetricsRegistry()``, ``x if x is not None else Router()``)."""
        if isinstance(value, ast.BoolOp):
            for v in value.values:
                ref = self._constructed_class(v)
                if ref:
                    return ref
            return None
        if isinstance(value, ast.IfExp):
            return self._constructed_class(value.body) or \
                self._constructed_class(value.orelse)
        if not isinstance(value, ast.Call):
            return None
        qn = self._qual(value.func)
        if qn is None or qn in LOCK_FACTORIES or qn in \
                WAITABLE_FACTORIES or qn in THREAD_FACTORIES:
            return None
        leaf = qn.rsplit(".", 1)[-1]
        # class-name heuristic: constructors are CapWords
        if leaf[:1].isupper():
            return qn
        return None

    def _scan_module_vars(self, tree: ast.Module) -> None:
        for node in tree.body:
            if not isinstance(node, ast.Assign):
                continue
            names = [t.id for t in node.targets
                     if isinstance(t, ast.Name)]
            if not names:
                continue
            kind = self._factory_kind(node.value, LOCK_FACTORIES)
            if kind:
                for n in names:
                    self.module_locks[n] = kind
                continue
            kind = self._factory_kind(node.value, WAITABLE_FACTORIES)
            if kind:
                for n in names:
                    self.module_waitables[n] = kind
                continue
            if self._is_jit_value(node.value):
                self.module_jit_vars.extend(names)
                continue
            ref = self._constructed_class(node.value)
            if ref:
                for n in names:
                    self.module_var_types[n] = ref
        # module-level threading.Thread(target=fn)
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) and \
                    self._qual(node.func) in THREAD_FACTORIES:
                tgt = self._thread_target(node)
                if tgt and tgt[0] is None:
                    self.module_thread_targets.append(tgt[1])

    def _thread_target(self, call: ast.Call,
                       ) -> Optional[Tuple[Optional[str], str]]:
        """(receiver, name) of a Thread target: (None, 'fn') for a
        bare function, ('self', 'meth') for a bound method."""
        for kw in call.keywords:
            if kw.arg != "target":
                continue
            v = kw.value
            if isinstance(v, ast.Name):
                return (None, v.id)
            if isinstance(v, ast.Attribute) and \
                    isinstance(v.value, ast.Name) and \
                    v.value.id == "self":
                return ("self", v.attr)
        return None

    def _pre_scan_classes(self, tree: ast.Module) -> None:
        """Inventory pass: attribute kinds must be known before the
        guard-stack walk classifies ``with self._lock:`` scopes."""
        for node in ast.walk(tree):
            if not isinstance(node, ast.ClassDef):
                continue
            cs = ClassSummary(
                name=node.name, line=node.lineno, attrs=[],
                lock_attrs={}, waitable_attrs={}, thread_attrs=[],
                jit_attrs=[], attr_types={}, thread_targets=[],
                methods=[n.name for n in node.body
                         if isinstance(n, (ast.FunctionDef,
                                           ast.AsyncFunctionDef))])
            seen: Set[str] = set()

            def annotation_ref(ann: Optional[ast.AST],
                               ) -> Optional[str]:
                # `recorder: Recorder` / `recorder: "Recorder"` /
                # `recorder: Optional[Recorder]` type an attribute
                # assigned straight from the parameter
                if isinstance(ann, ast.Constant) and \
                        isinstance(ann.value, str):
                    leaf = ann.value.rsplit(".", 1)[-1]
                    return ann.value if leaf[:1].isupper() else None
                if isinstance(ann, ast.Subscript):
                    return annotation_ref(ann.slice)
                qn = self._qual(ann) if ann is not None else None
                if qn and qn.rsplit(".", 1)[-1][:1].isupper() and \
                        qn not in ("None", "Optional", "Any"):
                    return qn
                return None

            for sub in ast.walk(node):
                if isinstance(sub, ast.Call) and \
                        self._qual(sub.func) in THREAD_FACTORIES:
                    tgt = self._thread_target(sub)
                    if tgt and tgt[0] == "self":
                        cs.thread_targets.append(tgt[1])
            for meth in ast.walk(node):
                if not isinstance(meth, (ast.FunctionDef,
                                         ast.AsyncFunctionDef)):
                    continue
                params = {a.arg: annotation_ref(a.annotation)
                          for a in (*meth.args.posonlyargs,
                                    *meth.args.args,
                                    *meth.args.kwonlyargs)}
                for sub in ast.walk(meth):
                    tgt_attr = None
                    if isinstance(sub, (ast.Assign, ast.AnnAssign)):
                        targets = sub.targets if isinstance(
                            sub, ast.Assign) else [sub.target]
                        for t in targets:
                            if isinstance(t, ast.Attribute) and \
                                    isinstance(t.value, ast.Name) and \
                                    t.value.id == "self":
                                tgt_attr = t.attr
                    if tgt_attr is None:
                        continue
                    if tgt_attr not in seen:
                        seen.add(tgt_attr)
                        cs.attrs.append(tgt_attr)
                    value = sub.value
                    if value is None:
                        continue
                    kind = self._factory_kind(value, LOCK_FACTORIES)
                    if kind:
                        cs.lock_attrs[tgt_attr] = kind
                        continue
                    kind = self._factory_kind(value,
                                              WAITABLE_FACTORIES)
                    if kind:
                        cs.waitable_attrs[tgt_attr] = kind
                        continue
                    if self._factory_kind(value, THREAD_FACTORIES):
                        cs.thread_attrs.append(tgt_attr)
                        continue
                    if self._is_jit_value(value):
                        cs.jit_attrs.append(tgt_attr)
                        continue
                    ref = self._constructed_class(value)
                    if ref is None and isinstance(value, ast.Name):
                        ref = params.get(value.id)
                    if ref:
                        cs.attr_types.setdefault(tgt_attr, ref)
            cs.thread_targets = sorted(set(cs.thread_targets))
            self.classes[node.name] = cs

    # -- lexical walk --------------------------------------------------

    def _visit_toplevel(self, node: ast.AST, cls: Optional[str],
                        prefix: str) -> None:
        if isinstance(node, ast.ClassDef):
            for sub in node.body:
                self._visit_toplevel(sub, cls=node.name,
                                     prefix=f"{node.name}.")
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            self._summarize_function(node, cls, prefix)

    def _lock_id_for(self, expr: ast.AST, cls: Optional[str],
                     fn_qual: str, local_locks: Dict[str, str],
                     ) -> Optional[str]:
        """Resolve a with-item / acquire receiver to a lock identity,
        or None when it isn't a known lock-family object."""
        if isinstance(expr, ast.Attribute) and \
                isinstance(expr.value, ast.Name) and \
                expr.value.id == "self" and cls is not None:
            cs = self.classes.get(cls)
            if cs and expr.attr in cs.lock_attrs:
                return f"{self.module}::{cls}.{expr.attr}"
            return None
        if isinstance(expr, ast.Name):
            if expr.id in local_locks:
                return f"{self.module}::{fn_qual}.{expr.id}"
            if expr.id in self.module_locks:
                return f"{self.module}::{expr.id}"
            qn = self.aliases.get(expr.id)
            if qn and "." in qn:
                # a lock imported from a sibling module keeps its
                # defining module's identity
                mod, leaf = qn.rsplit(".", 1)
                return f"{mod}::{leaf}" if leaf.lower().find("lock") \
                    >= 0 or leaf.lower().find("cv") >= 0 else None
        return None

    def _summarize_function(self, fn: ast.AST, cls: Optional[str],
                            prefix: str) -> None:
        qual = f"{prefix}{fn.name}"
        fs = FunctionSummary(qual=qual, cls=cls, name=fn.name,
                             line=fn.lineno, writes=[], calls=[],
                             blocking=[], acquisitions=[])
        self.functions[qual] = fs
        local_locks: Dict[str, str] = {}
        local_waitables: Dict[str, str] = {}
        local_threads: Set[str] = set()
        local_jit: Set[str] = set()

        def classify_local(stmt: ast.Assign) -> None:
            names = [t.id for t in stmt.targets
                     if isinstance(t, ast.Name)]
            if not names:
                return
            kind = self._factory_kind(stmt.value, LOCK_FACTORIES)
            if kind:
                local_locks.update({n: kind for n in names})
                return
            kind = self._factory_kind(stmt.value, WAITABLE_FACTORIES)
            if kind:
                local_waitables.update({n: kind for n in names})
                return
            if self._factory_kind(stmt.value, THREAD_FACTORIES):
                local_threads.update(names)
                return
            if self._is_jit_value(stmt.value):
                local_jit.update(names)

        # locals must be known before guard classification: one
        # pre-pass over direct (non-nested) statements
        for sub in ast.walk(fn):
            if isinstance(sub, ast.Assign):
                classify_local(sub)

        cs = self.classes.get(cls) if cls else None

        def waitable_kind(recv: ast.AST) -> Optional[str]:
            if isinstance(recv, ast.Attribute) and \
                    isinstance(recv.value, ast.Name) and \
                    recv.value.id == "self" and cs:
                if recv.attr in cs.waitable_attrs:
                    return cs.waitable_attrs[recv.attr]
                if recv.attr in cs.lock_attrs:
                    return cs.lock_attrs[recv.attr]
            if isinstance(recv, ast.Name):
                if recv.id in local_waitables:
                    return local_waitables[recv.id]
                if recv.id in self.module_waitables:
                    return self.module_waitables[recv.id]
                if recv.id in local_locks:
                    return local_locks[recv.id]
                if recv.id in self.module_locks:
                    return self.module_locks[recv.id]
            return None

        def is_thread(recv: ast.AST) -> bool:
            if isinstance(recv, ast.Attribute) and \
                    isinstance(recv.value, ast.Name) and \
                    recv.value.id == "self" and cs:
                return recv.attr in cs.thread_attrs
            return isinstance(recv, ast.Name) and \
                recv.id in local_threads

        def is_jit_callable(func: ast.AST) -> bool:
            if isinstance(func, ast.Attribute) and \
                    isinstance(func.value, ast.Name) and \
                    func.value.id == "self" and cs:
                return func.attr in cs.jit_attrs
            if isinstance(func, ast.Name):
                return func.id in local_jit or \
                    func.id in self.module_jit_vars
            return False

        def record_write(attr: str, node: ast.AST,
                         guards: Tuple[str, ...]) -> None:
            fs.writes.append((attr, node.lineno, node.col_offset,
                              guards))

        def self_attr(expr: ast.AST) -> Optional[str]:
            if isinstance(expr, ast.Attribute) and \
                    isinstance(expr.value, ast.Name) and \
                    expr.value.id == "self":
                return expr.attr
            return None

        def call_spec(func: ast.AST) -> Optional[str]:
            if isinstance(func, ast.Name):
                imported = self.aliases.get(func.id)
                if imported and "." in imported:
                    return f"qual:{imported}"
                return f"name:{func.id}"
            if isinstance(func, ast.Attribute):
                if isinstance(func.value, ast.Name) and \
                        func.value.id == "self":
                    return f"self:{func.attr}"
                if isinstance(func.value, ast.Attribute) and \
                        isinstance(func.value.value, ast.Name) and \
                        func.value.value.id == "self":
                    return f"obj:{func.value.attr}.{func.attr}"
                qn = self._qual(func)
                if qn:
                    return f"qual:{qn}"
            return None

        def handle_call(node: ast.Call,
                        guards: Tuple[str, ...]) -> None:
            func = node.func
            qn = self._qual(func)
            line, col = node.lineno, node.col_offset
            if qn in BLOCKING_FREE_CALLS:
                fs.blocking.append((line, col,
                                    BLOCKING_FREE_CALLS[qn], "",
                                    guards))
                return
            if isinstance(func, ast.Attribute):
                meth, recv = func.attr, func.value
                if meth in BLOCKING_ANY_METHODS:
                    fs.blocking.append(
                        (line, col, BLOCKING_ANY_METHODS[meth], "",
                         guards))
                    return
                if meth == "wait":
                    kind = waitable_kind(recv)
                    if kind in ("Event", "Condition"):
                        # waiting the condition you HOLD releases it —
                        # that lock is exempt at this site
                        exempt = ""
                        if kind == "Condition":
                            exempt = self._lock_id_for(
                                recv, cls, qual, local_locks) or ""
                        fs.blocking.append(
                            (line, col, f"{kind}.wait()", exempt,
                             guards))
                        return
                if meth == "join" and is_thread(recv):
                    fs.blocking.append(
                        (line, col, "Thread.join()", "", guards))
                    return
                if meth in ("get", "put") and \
                        waitable_kind(recv) == "Queue" and not any(
                            kw.arg == "block" and isinstance(
                                kw.value, ast.Constant) and
                            kw.value.value is False
                            for kw in node.keywords):
                    fs.blocking.append(
                        (line, col, f"Queue.{meth}()", "", guards))
                    return
                if meth == "acquire":
                    lid = self._lock_id_for(recv, cls, qual,
                                            local_locks)
                    if lid:
                        fs.acquisitions.append((lid, line, col,
                                                guards))
                        return
                # in-place mutation of a lock-owning class's state:
                # self.q.append(...) is a write to self.q
                attr = self_attr(recv)
                if attr is not None and meth in MUTATOR_METHODS:
                    record_write(attr, node, guards)
            if is_jit_callable(func):
                fs.blocking.append(
                    (line, col, "jit-compiled dispatch", "", guards))
                return
            spec = call_spec(func)
            if spec:
                fs.calls.append((spec, line, col, guards))

        def visit(node: ast.AST, guards: Tuple[str, ...]) -> None:
            if isinstance(node, (ast.FunctionDef,
                                 ast.AsyncFunctionDef)) and \
                    node is not fn:
                # nested def: its body runs later, outside the
                # current guard scope; summarise it separately
                self._summarize_function(node, cls,
                                         prefix=f"{qual}.")
                return
            if isinstance(node, ast.Lambda):
                return
            if isinstance(node, (ast.With, ast.AsyncWith)):
                new = list(guards)
                for item in node.items:
                    lid = self._lock_id_for(item.context_expr, cls,
                                            qual, local_locks)
                    if lid:
                        fs.acquisitions.append(
                            (lid, item.context_expr.lineno,
                             item.context_expr.col_offset,
                             tuple(new)))
                        new.append(lid)
                    for sub in ast.iter_child_nodes(item.context_expr):
                        visit(sub, guards)
                for stmt in node.body:
                    visit(stmt, tuple(new))
                return
            if isinstance(node, (ast.Assign, ast.AnnAssign,
                                 ast.AugAssign)):
                targets = node.targets if isinstance(
                    node, ast.Assign) else [node.target]
                for t in targets:
                    attr = self_attr(t)
                    if attr is None and isinstance(
                            t, ast.Subscript):
                        attr = self_attr(t.value)
                    if attr is not None:
                        record_write(attr, node, guards)
            if isinstance(node, ast.Delete):
                for t in node.targets:
                    attr = self_attr(t)
                    if attr is None and isinstance(t, ast.Subscript):
                        attr = self_attr(t.value)
                    if attr is not None:
                        record_write(attr, node, guards)
            if isinstance(node, ast.Call):
                handle_call(node, guards)
            for child in ast.iter_child_nodes(node):
                visit(child, guards)

        for stmt in fn.body:
            visit(stmt, ())


def summarize_file(path: str, relpath: str) -> FileSummary:
    try:
        with open(path, encoding="utf-8") as f:
            source = f.read()
        tree = ast.parse(source, filename=path)
    except (OSError, UnicodeDecodeError, SyntaxError) as e:
        return FileSummary(
            relpath=relpath, module=module_name_for(path), classes={},
            functions={}, module_locks={}, module_waitables={},
            module_jit_vars=[], module_var_types={},
            module_thread_targets=[], suppressions={},
            parse_error=str(e))
    s = _FileSummarizer(relpath, module_name_for(path), tree, source)
    return FileSummary(
        relpath=relpath, module=s.module, classes=s.classes,
        functions=s.functions, module_locks=s.module_locks,
        module_waitables=s.module_waitables,
        module_jit_vars=sorted(set(s.module_jit_vars)),
        module_var_types=s.module_var_types,
        module_thread_targets=sorted(set(s.module_thread_targets)),
        suppressions=s.suppressions,
        donation_findings=dataflow.analyze_donation_use(tree),
        lifecycle_findings=dataflow.analyze_lifecycle(tree),
        metrics=dataflow.extract_metrics(tree))


# -- the index --------------------------------------------------------


class ProjectIndex:
    """Resolved whole-package view + lazily computed graph closures.

    Function ids are ``module::qual`` (``fengshen_tpu.fleet.router::
    Router._attempt``), lock ids ``module::Class.attr`` /
    ``module::VAR`` / ``module::fn.name`` — stable across hosts."""

    def __init__(self, files: Dict[str, FileSummary]) -> None:
        self.files = files
        self.by_module: Dict[str, FileSummary] = {}
        for fsum in files.values():
            self.by_module[fsum.module] = fsum
        # fn id -> (FileSummary, FunctionSummary)
        self.functions: Dict[str, Tuple[FileSummary, FunctionSummary]]
        self.functions = {}
        for rel in sorted(files):
            fsum = files[rel]
            for q in sorted(fsum.functions):
                self.functions[f"{fsum.module}::{q}"] = \
                    (fsum, fsum.functions[q])
        self._edges: Optional[Dict[str, List[Tuple[str, int, int,
                                                   Tuple[str, ...]]]]]
        self._edges = None
        self._callers: Optional[Dict[str, List[Tuple[str, Tuple[str,
                                                                ...]]]]]
        self._callers = None
        self._held: Optional[Dict[str, Set[str]]] = None
        self._blocking: Optional[Dict[str, List]] = None
        self._acquired: Optional[Dict[str, Dict[str, List[str]]]] = None
        self._confined: Optional[Set[str]] = None

    # -- resolution ---------------------------------------------------

    def _resolve_class_ref(self, fsum: FileSummary,
                           ref: str) -> Optional[Tuple[str, str]]:
        """class reference -> (module, class name) when indexed."""
        if "." not in ref:
            if ref in fsum.classes:
                return (fsum.module, ref)
            return None
        mod, leaf = ref.rsplit(".", 1)
        target = self.by_module.get(mod)
        if target and leaf in target.classes:
            return (mod, leaf)
        return None

    def resolve_call(self, fn_id: str, spec: str) -> List[str]:
        """Resolve one recorded call spec to candidate fn ids."""
        fsum, fs = self.functions[fn_id]
        kind, _, rest = spec.partition(":")
        out: List[str] = []
        if kind == "self" and fs.cls is not None:
            cand = f"{fsum.module}::{fs.cls}.{rest}"
            if cand in self.functions:
                out.append(cand)
        elif kind == "name":
            # bare name: module-level function, or a sibling nested
            # def in the same enclosing function
            cand = f"{fsum.module}::{rest}"
            if cand in self.functions:
                out.append(cand)
            if "." in fs.qual:
                parent = fs.qual.rsplit(".", 1)[0]
                cand = f"{fsum.module}::{parent}.{rest}"
                if cand in self.functions:
                    out.append(cand)
        elif kind == "obj" and fs.cls is not None:
            attr, _, meth = rest.partition(".")
            cs = fsum.classes.get(fs.cls)
            if cs and attr in cs.attr_types:
                rc = self._resolve_class_ref(fsum, cs.attr_types[attr])
                if rc:
                    cand = f"{rc[0]}::{rc[1]}.{meth}"
                    if cand in self.functions:
                        out.append(cand)
        elif kind == "qual":
            out.extend(self._resolve_qual(fsum, rest))
        return out

    def _resolve_qual(self, fsum: FileSummary, qn: str) -> List[str]:
        parts = qn.split(".")
        # longest-prefix module match
        for i in range(len(parts) - 1, 0, -1):
            mod = ".".join(parts[:i])
            target = self.by_module.get(mod)
            if target is None:
                continue
            tail = parts[i:]
            if len(tail) == 1 and tail[0] in target.functions:
                return [f"{mod}::{tail[0]}"]
            if len(tail) == 2:
                cls_or_var, meth = tail
                if f"{cls_or_var}.{meth}" in target.functions:
                    return [f"{mod}::{cls_or_var}.{meth}"]
                if cls_or_var in target.module_var_types:
                    rc = self._resolve_class_ref(
                        target, target.module_var_types[cls_or_var])
                    if rc:
                        cand = f"{rc[0]}::{rc[1]}.{meth}"
                        if cand in self.functions:
                            return [cand]
            return []
        return []

    # -- graphs -------------------------------------------------------

    def edges(self) -> Dict[str, List[Tuple[str, int, int,
                                            Tuple[str, ...]]]]:
        if self._edges is None:
            self._edges = {}
            for fn_id in self.functions:
                _, fs = self.functions[fn_id]
                out: List[Tuple[str, int, int, Tuple[str, ...]]] = []
                for spec, line, col, guards in fs.calls:
                    for callee in self.resolve_call(fn_id, spec):
                        out.append((callee, line, col, guards))
                self._edges[fn_id] = out
        return self._edges

    def callers(self) -> Dict[str, List[Tuple[str, Tuple[str, ...]]]]:
        """callee -> [(caller id, guards at the call site)]."""
        if self._callers is None:
            self._callers = {}
            for fn_id in sorted(self.edges()):
                for callee, _l, _c, guards in self.edges()[fn_id]:
                    self._callers.setdefault(callee, []).append(
                        (fn_id, guards))
        return self._callers

    def class_lock_ids(self, module: str, cls: ClassSummary,
                       ) -> Set[str]:
        return {f"{module}::{cls.name}.{a}" for a in cls.lock_attrs}

    def guaranteed_held(self) -> Dict[str, Set[str]]:
        """fn id -> locks provably held at EVERY resolved call site
        (plus the ``*_locked`` naming convention: such a method of a
        lock-owning class asserts its class locks are held)."""
        if self._held is not None:
            return self._held
        callers = self.callers()
        all_locks: Set[str] = set()
        for fn_id in self.functions:
            _, fs = self.functions[fn_id]
            for _a, _l, _c, g in fs.writes:
                all_locks.update(g)
            for lid, _l, _c, held in fs.acquisitions:
                all_locks.add(lid)
                all_locks.update(held)
        held: Dict[str, Set[str]] = {}
        convention: Dict[str, Set[str]] = {}
        for fn_id in self.functions:
            fsum, fs = self.functions[fn_id]
            conv: Set[str] = set()
            if fs.name.endswith("_locked") and fs.cls:
                cs = fsum.classes.get(fs.cls)
                if cs and cs.lock_attrs:
                    conv = self.class_lock_ids(fsum.module, cs)
            convention[fn_id] = conv
            held[fn_id] = set(all_locks) if callers.get(fn_id) \
                else set(conv)
        changed = True
        while changed:
            changed = False
            for fn_id in sorted(self.functions):
                sites = callers.get(fn_id)
                if not sites:
                    continue
                new: Optional[Set[str]] = None
                for caller, guards in sites:
                    site_held = set(guards) | held.get(caller, set())
                    new = site_held if new is None else new & site_held
                new = (new or set()) | convention[fn_id]
                if new != held[fn_id]:
                    held[fn_id] = new
                    changed = True
        self._held = held
        return held

    def blocking_closure(self) -> Dict[str, List[Tuple[str, str,
                                                       List[str]]]]:
        """fn id -> [(description, exempt lock, witness chain)] of
        blocking operations reachable from its body (its own ops plus
        resolved callees', chains capped for readability)."""
        if self._blocking is not None:
            return self._blocking
        closure: Dict[str, Dict[Tuple[str, str], List[str]]] = {}
        for fn_id in self.functions:
            _, fs = self.functions[fn_id]
            own: Dict[Tuple[str, str], List[str]] = {}
            for line, _col, desc, exempt, _g in sorted(fs.blocking):
                own.setdefault((desc, exempt), [f"{fn_id}:{line}"])
            closure[fn_id] = own
        changed = True
        while changed:
            changed = False
            for fn_id in sorted(self.functions):
                mine = closure[fn_id]
                for callee, line, _c, _g in self.edges()[fn_id]:
                    for key, chain in closure[callee].items():
                        if key not in mine and len(chain) < 6:
                            mine[key] = [f"{fn_id}:{line}"] + chain
                            changed = True
        self._blocking = {
            fn_id: sorted((d, e, c) for (d, e), c in m.items())
            for fn_id, m in closure.items()}
        return self._blocking

    def acquired_closure(self) -> Dict[str, Dict[str, List[str]]]:
        """fn id -> {lock id: witness chain} of locks acquired in the
        function or any resolved callee."""
        if self._acquired is not None:
            return self._acquired
        closure: Dict[str, Dict[str, List[str]]] = {}
        for fn_id in self.functions:
            _, fs = self.functions[fn_id]
            own: Dict[str, List[str]] = {}
            for lid, line, _c, _h in sorted(fs.acquisitions):
                own.setdefault(lid, [f"{fn_id}:{line}"])
            closure[fn_id] = own
        changed = True
        while changed:
            changed = False
            for fn_id in sorted(self.functions):
                mine = closure[fn_id]
                for callee, line, _c, _g in self.edges()[fn_id]:
                    for lid, chain in closure[callee].items():
                        if lid not in mine and len(chain) < 6:
                            mine[lid] = [f"{fn_id}:{line}"] + chain
                            changed = True
        self._acquired = closure
        return closure

    def thread_confined(self) -> Set[str]:
        """Private functions that only ever run on a dedicated owned
        thread (the scheduler-thread escape hatch): thread targets,
        plus private helpers all of whose resolved callers are
        confined."""
        if self._confined is not None:
            return self._confined
        entries: Set[str] = set()
        for rel in sorted(self.files):
            fsum = self.files[rel]
            for name in fsum.module_thread_targets:
                fid = f"{fsum.module}::{name}"
                if fid in self.functions:
                    entries.add(fid)
            for cname in sorted(fsum.classes):
                cs = fsum.classes[cname]
                for meth in cs.thread_targets:
                    fid = f"{fsum.module}::{cname}.{meth}"
                    if fid in self.functions:
                        entries.add(fid)
        confined = set(entries)
        callers = self.callers()
        changed = True
        while changed:
            changed = False
            for fn_id in sorted(self.functions):
                if fn_id in confined:
                    continue
                _, fs = self.functions[fn_id]
                if not fs.name.startswith("_"):
                    continue  # public: callable from anywhere
                sites = callers.get(fn_id)
                if sites and all(c in confined for c, _g in sites):
                    confined.add(fn_id)
                    changed = True
        self._confined = confined
        return confined

    def relpath_of(self, fn_id: str) -> str:
        return self.functions[fn_id][0].relpath

    def describe_site(self, site: str) -> str:
        """'module::qual:line' -> 'relpath:line (qual)'."""
        fn_id, _, line = site.rpartition(":")
        if fn_id in self.functions:
            fsum, fs = self.functions[fn_id]
            return f"{fsum.relpath}:{line} ({fs.qual})"
        return site

    def is_suppressed(self, relpath: str, line: int,
                      rule_id: str) -> bool:
        fsum = self.files.get(relpath)
        if fsum is None:
            return False
        rules = fsum.suppressions.get(line)
        if rules is None:
            return False
        return not rules or rule_id in rules


# -- building + caching -----------------------------------------------

#: in-process memo: stat signature of the file set -> ProjectIndex.
#: Keeps the test suite's many whole-package runs at one build.
_MEMO: Dict[Tuple, ProjectIndex] = {}
_MEMO_CAP = 8


def _relpath(path: str, root: str) -> str:
    rel = os.path.relpath(os.path.abspath(path), root)
    return rel.replace(os.sep, "/")


def _sha256(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 16), b""):
            h.update(chunk)
    return h.hexdigest()


def build_index(paths: Iterable[str], project_root: str,
                cache_path: Optional[str] = None) -> ProjectIndex:
    """Build (or load) the project index for ``paths``.

    ``cache_path`` enables the on-disk cache: per-file summaries keyed
    by content sha256, so an incremental run only re-parses files
    whose bytes changed. The produced index is identical with a cold,
    warm, or stale cache — the cache can only save time, never change
    findings."""
    files = sorted(set(iter_py_files(paths)))
    sig = tuple((p, os.path.getmtime(p), os.path.getsize(p))
                for p in files) + (project_root,)
    LAST_BUILD_STATS.update(files=len(files), cache_hits=0,
                            cache_misses=0, memo_hit=0)
    memo = _MEMO.get(sig)
    if memo is not None and cache_path is None:
        LAST_BUILD_STATS["memo_hit"] = 1
        return memo

    cache: Dict[str, dict] = {}
    if cache_path and os.path.exists(cache_path):
        try:
            with open(cache_path, encoding="utf-8") as f:
                raw = json.load(f)
            if raw.get("version") == INDEX_CACHE_VERSION:
                cache = raw.get("files", {})
        except (OSError, ValueError):
            cache = {}  # unreadable cache == cold cache

    summaries: Dict[str, FileSummary] = {}
    out_cache: Dict[str, dict] = {}
    for path in files:
        rel = _relpath(path, project_root)
        sha = _sha256(path)
        entry = cache.get(rel)
        if entry is not None and entry.get("sha") == sha:
            try:
                summaries[rel] = FileSummary.from_dict(
                    entry["summary"])
                out_cache[rel] = entry
                LAST_BUILD_STATS["cache_hits"] += 1
                continue
            except (KeyError, TypeError, ValueError):
                pass  # corrupt entry: fall through to re-summarise
        summary = summarize_file(path, rel)
        summaries[rel] = summary
        out_cache[rel] = {"sha": sha, "summary": summary.to_dict()}
        LAST_BUILD_STATS["cache_misses"] += 1

    if cache_path:
        tmp = cache_path + ".tmp"
        try:
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump({"version": INDEX_CACHE_VERSION,
                           "files": out_cache}, f, sort_keys=True)
            os.replace(tmp, cache_path)
        except OSError:
            pass  # a read-only checkout still lints, just uncached

    index = ProjectIndex(summaries)
    if len(_MEMO) >= _MEMO_CAP:
        _MEMO.pop(next(iter(_MEMO)))
    _MEMO[sig] = index
    return index
