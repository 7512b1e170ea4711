"""Persistent AOT executable cache: lower once anywhere, compile once
EVER (per environment).

XLA compilation of the train step and of every serving prefill bucket
costs seconds-to-minutes on real pods, and a restart/redeploy/rewind/
autoscale event re-pays all of it. `cached_compile` splits jit into its
two halves — lower (cheap tracing, always runs, and produces the cache
key) and compile (the expensive XLA invocation, skipped on a hit) — and
persists the compiled executable with
`jax.experimental.serialize_executable`.

Cache key anatomy (docs/aot_cache.md): sha256 over

- the jax version,
- backend platform + device kind + device count,
- mesh axis names/sizes (when a mesh is in play — the same program
  lowered under a different mesh is a different executable),
- compiler options,
- the sha256 of the canonical StableHLO text of the lowered module
  (which already embeds input shapes/dtypes/shardings and donation).

Failure semantics — THE invariant: the cache can never break a job.
Every load failure (truncated blob, unpicklable payload, jax version
drift inside the blob header, deserialize error) logs an event, bumps
`fstpu_aot_cache_errors_total{fn}`, removes the bad file, and falls
back to a fresh compile whose result overwrites the entry newest-wins
via atomic `os.replace`. Stores are also best-effort: a full disk or
read-only cache dir degrades to compile-every-time, not a crash.

Host-side only: everything here (file I/O, pickling, metric bumps) runs
strictly OUTSIDE traced code — `cached_compile` is called between jit
boundaries, never inside one (the `metrics-in-traced-code` /
`blocking-transfer` fslint rules gate this; see
tests/analysis_fixtures/aot_cache_clean.py).
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import pickle
import tempfile
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax

from fengshen_tpu.observability import MetricsRegistry, get_registry, span

#: bump when the on-disk blob layout changes — older blobs become load
#: errors (counted + recompiled), never crashes
BLOB_VERSION = 2


def _devices_of(compiled) -> tuple:
    """The devices a compiled program is assigned to, in order."""
    leaves = jax.tree_util.tree_leaves(
        (compiled.input_shardings, compiled.output_shardings))
    return leaves[0]._device_assignment if leaves else \
        (jax.devices()[0],)

#: file suffix for cache entries ("<name>__<key>.aotx")
BLOB_SUFFIX = ".aotx"

#: default LRU size cap (bytes); generous because blobs are per-shape
DEFAULT_MAX_BYTES = 4 << 30

HITS_METRIC = "fstpu_aot_cache_hits_total"
MISSES_METRIC = "fstpu_aot_cache_misses_total"
ERRORS_METRIC = "fstpu_aot_cache_errors_total"

_METRIC_HELP = {
    HITS_METRIC: "AOT cache loads served from a deserialized executable",
    MISSES_METRIC: "AOT cache lookups that fell through to XLA compile",
    ERRORS_METRIC: "AOT cache load/store failures (fell back to compile)",
}


def _counter(name: str, registry: Optional[MetricsRegistry] = None):
    reg = registry if registry is not None else get_registry()
    return reg.counter(name, _METRIC_HELP[name], labelnames=("fn",))


def _sanitize(name: str) -> str:
    """Function names are span-style ("serving/prefill") — keep them
    readable on disk without path separators."""
    return "".join(c if c.isalnum() or c in "._-" else "-" for c in name)


def _mesh_ident(mesh: Any) -> Optional[list]:
    if mesh is None:
        return None
    return sorted((str(k), int(v)) for k, v in dict(mesh.shape).items())


def cache_key(name: str, lowered: Any, mesh: Any = None,
              compiler_options: Optional[dict] = None,
              extra: str = "") -> str:
    """The content address of one compiled executable (see module
    docstring for the anatomy). `lowered` is a `jax.stages.Lowered`.

    `extra` carries static context that changes the program's runtime
    choreography without necessarily changing its StableHLO — the
    trainer passes the resolved offload placement
    (`OffloadPolicy.fingerprint()`, docs/offload.md) so two placements
    can never share an entry. Empty `extra` keeps the pre-existing key
    derivation (no silent cache invalidation for everyone else)."""
    devices = jax.devices()
    ident = {
        "name": name,
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "mesh": _mesh_ident(mesh),
        "compiler_options": sorted(
            (str(k), str(v))
            for k, v in (compiler_options or {}).items()),
        "stablehlo_sha256": hashlib.sha256(
            lowered.as_text().encode()).hexdigest(),
    }
    if extra:
        ident["extra"] = extra
    return hashlib.sha256(
        json.dumps(ident, sort_keys=True).encode()).hexdigest()


_SOURCE_DIGEST: Optional[str] = None


def package_source_digest() -> str:
    """sha256 over every .py file of the installed fengshen_tpu package
    (path + content, sorted walk) — the code half of the trusted-replay
    fingerprint. Computed once per process (~a few MiB of reads)."""
    global _SOURCE_DIGEST
    if _SOURCE_DIGEST is None:
        import fengshen_tpu
        root = os.path.dirname(os.path.abspath(fengshen_tpu.__file__))
        h = hashlib.sha256()
        for dirpath, dirnames, filenames in os.walk(root):
            dirnames.sort()
            for fn in sorted(filenames):
                if not fn.endswith(".py"):
                    continue
                path = os.path.join(dirpath, fn)
                h.update(os.path.relpath(path, root).encode())
                try:
                    with open(path, "rb") as f:
                        h.update(f.read())
                except OSError:
                    h.update(b"<unreadable>")
        _SOURCE_DIGEST = h.hexdigest()
    return _SOURCE_DIGEST


def trusted_fingerprint(extra: str = "", mesh: Any = None) -> str:
    """The precondition for adopting a cached executable WITHOUT
    re-lowering (docs/aot_cache.md "trusted replay"): lowering is
    deterministic, so identical package source + library versions +
    accelerator topology + static config (`extra` — e.g. the model and
    engine config reprs, which bake constants into the program) imply
    an identical StableHLO module for identical avals. Any drift in any
    component changes this fingerprint and demotes replay to the
    verified lower-and-hash path."""
    try:
        import flax
        flax_version = flax.__version__
    except Exception:  # noqa: BLE001 — fingerprint must not require flax
        flax_version = "none"
    import numpy as np
    devices = jax.devices()
    ident = {
        "jax": jax.__version__,
        "flax": flax_version,
        "numpy": np.__version__,
        "backend": jax.default_backend(),
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "mesh": _mesh_ident(mesh),
        "source": package_source_digest(),
        "extra": extra,
        "blob_version": BLOB_VERSION,
    }
    return hashlib.sha256(
        json.dumps(ident, sort_keys=True).encode()).hexdigest()


class _FlatCall:
    """Adapter for blobs stored in the FLAT calling convention.

    `serialize_executable` must pickle the program's in/out treedefs,
    and some perfectly cacheable programs have unpicklable ones — the
    trainer's TrainState carries its optax transform (a closure) as
    static pytree metadata. Such executables are stored against
    surrogate flat-tuple treedefs instead; this wrapper re-flattens the
    live call args and restores the REAL out tree (supplied by the
    caller's `Lowered` at load time, so flat blobs are only loadable on
    the verified lower-and-hash path — `adopt()` declines them).
    """

    __slots__ = ("_exe", "_out_tree")

    def __init__(self, exe, out_tree):
        self._exe = exe
        self._out_tree = out_tree

    def __call__(self, *args):
        leaves = jax.tree_util.tree_leaves(args)
        outs = self._exe(*leaves)
        return jax.tree_util.tree_unflatten(self._out_tree, outs)


def _flat_treedefs(n_in: int, n_out: int):
    """Surrogate (in, out) treedefs for the flat calling convention:
    positionally identical leaves, trivially picklable."""
    in_tree = jax.tree_util.tree_structure((tuple(range(n_in)), {}))
    out_tree = jax.tree_util.tree_structure(tuple(range(n_out)))
    return in_tree, out_tree


@dataclasses.dataclass(frozen=True)
class CacheEntry:
    """One on-disk executable blob (ls/purge surface)."""

    path: str
    name: str
    key: str
    size_bytes: int
    mtime: float


class ExecutableCache:
    """Directory of serialized executables, LRU-capped by mtime.

    mtime doubles as the recency clock: `load` touches the file on a
    hit, so `purge` (triggered after every store once the dir exceeds
    `max_bytes`) evicts the least-recently-USED blob, not merely the
    oldest-written one.
    """

    def __init__(self, cache_dir: str, max_bytes: int = DEFAULT_MAX_BYTES,
                 registry: Optional[MetricsRegistry] = None,
                 log: Optional[Callable[[dict], None]] = None):
        self.cache_dir = cache_dir
        self.max_bytes = int(max_bytes)
        self._registry = registry
        self._log = log or (lambda entry: None)
        self._lock = threading.Lock()
        os.makedirs(cache_dir, exist_ok=True)

    # ---- paths ------------------------------------------------------

    def path_for(self, name: str, key: str) -> str:
        return os.path.join(self.cache_dir,
                            f"{_sanitize(name)}__{key}{BLOB_SUFFIX}")

    def entries(self) -> List[CacheEntry]:
        """All blobs, newest (most recently used) first."""
        out = []
        try:
            filenames = os.listdir(self.cache_dir)
        except OSError:
            return []
        for fn in filenames:
            if not fn.endswith(BLOB_SUFFIX):
                continue
            path = os.path.join(self.cache_dir, fn)
            stem = fn[:-len(BLOB_SUFFIX)]
            name, _, key = stem.rpartition("__")
            try:
                st = os.stat(path)
            except OSError:
                continue  # racing purge
            out.append(CacheEntry(path=path, name=name or stem, key=key,
                                  size_bytes=st.st_size,
                                  mtime=st.st_mtime))
        out.sort(key=lambda e: (-e.mtime, e.path))
        return out

    def total_bytes(self) -> int:
        return sum(e.size_bytes for e in self.entries())

    # ---- load / store ----------------------------------------------

    def load(self, name: str, key: str, out_tree: Any = None):
        """Deserialize the executable for (name, key); None on miss OR
        on any failure (counted in errors_total, bad file removed).

        `out_tree` (from the caller's `Lowered`) is required to load a
        flat-convention blob — without it such a blob is a plain miss
        (not an error): the trusted-adopt path has no Lowered and falls
        back to the verified path, which passes one."""
        path = self.path_for(name, key)
        if not os.path.exists(path):
            return None
        try:
            with span("aot/deserialize"):
                with open(path, "rb") as f:
                    blob = pickle.load(f)
                if blob.get("version") != BLOB_VERSION:
                    raise ValueError(
                        f"blob version {blob.get('version')!r} != "
                        f"{BLOB_VERSION}")
                if blob.get("jax") != jax.__version__:
                    raise ValueError(
                        f"blob compiled under jax {blob.get('jax')!r}, "
                        f"running {jax.__version__}")
                from jax.experimental.serialize_executable import \
                    deserialize_and_load
                by_id = {d.id: d for d in jax.devices()}
                devices = [by_id[i] for i in blob["device_ids"]]
                if blob.get("tree_mode") == "flat":
                    if out_tree is None:
                        return None
                    in_surr, out_surr = _flat_treedefs(blob["n_in"],
                                                       blob["n_out"])
                    exe = _FlatCall(
                        deserialize_and_load(
                            blob["payload"], in_surr, out_surr,
                            execution_devices=devices), out_tree)
                else:
                    exe = deserialize_and_load(
                        blob["payload"], blob["in_tree"],
                        blob["out_tree"], execution_devices=devices)
            # touch: LRU recency for the size-cap purge
            try:
                os.utime(path, None)
            except OSError:
                pass
            return exe
        except Exception as e:  # noqa: BLE001 — THE invariant: a
            # corrupt/mismatched blob silently recompiles, it never
            # fails the job
            _counter(ERRORS_METRIC, self._registry).labels(name).inc()
            self._log({"event": "aot_cache_error", "fn": name,
                       "stage": "deserialize", "path": path,
                       "error": str(e)[:500]})
            try:
                os.remove(path)
            except OSError:
                pass
            return None

    def store(self, name: str, key: str, compiled: Any) -> bool:
        """Serialize + commit by atomic rename (concurrent writers of
        the same key converge newest-wins; readers never see a torn
        file). Best-effort: failures count + log, never raise."""
        path = self.path_for(name, key)
        tmp = None
        try:
            with span("aot/serialize"):
                from jax.experimental.serialize_executable import \
                    serialize
                payload, in_tree, out_tree = serialize(compiled)
                header = {"version": BLOB_VERSION,
                          "jax": jax.__version__,
                          "name": name, "key": key, "payload": payload,
                          # the devices the program runs on, in
                          # assignment order: deserialize_and_load
                          # otherwise assumes every device of the
                          # backend and wants one shard per device
                          "device_ids": [d.id for d in _devices_of(
                              compiled)]}
                try:
                    blob = pickle.dumps({**header, "in_tree": in_tree,
                                         "out_tree": out_tree})
                except (TypeError, AttributeError,
                        pickle.PicklingError):
                    # unpicklable treedef metadata (e.g. TrainState's
                    # static optax transform): fall back to the FLAT
                    # calling convention — leaf counts only, the real
                    # trees are restored from the loader's Lowered
                    blob = pickle.dumps({
                        **header, "tree_mode": "flat",
                        "n_in": in_tree.num_leaves,
                        "n_out": out_tree.num_leaves})
            fd, tmp = tempfile.mkstemp(dir=self.cache_dir,
                                       prefix=".aot-tmp-")
            with os.fdopen(fd, "wb") as f:
                f.write(blob)
            os.replace(tmp, path)
            tmp = None
            self.purge(max_bytes=self.max_bytes)
            return True
        except Exception as e:  # noqa: BLE001 — a full disk or
            # read-only cache dir degrades to compile-every-time
            _counter(ERRORS_METRIC, self._registry).labels(name).inc()
            self._log({"event": "aot_cache_error", "fn": name,
                       "stage": "serialize", "path": path,
                       "error": str(e)[:500]})
            if tmp is not None:
                try:
                    os.remove(tmp)
                except OSError:
                    pass
            return False

    # ---- maintenance ------------------------------------------------

    def purge(self, max_bytes: Optional[int] = None,
              older_than_s: Optional[float] = None,
              drop_all: bool = False) -> List[CacheEntry]:
        """Evict blobs; returns what was removed. Modes compose:
        `drop_all` clears the dir; `older_than_s` drops blobs idle
        longer than that; `max_bytes` then drops least-recently-used
        blobs (oldest mtime first) until the dir fits."""
        removed: List[CacheEntry] = []
        with self._lock:
            entries = self.entries()   # newest-first
            now = time.time()
            keep: List[CacheEntry] = []
            for e in entries:
                if drop_all or (older_than_s is not None
                                and now - e.mtime > older_than_s):
                    removed.append(e)
                else:
                    keep.append(e)
            if max_bytes is not None:
                total = sum(e.size_bytes for e in keep)
                while keep and total > max_bytes:
                    e = keep.pop()     # least recently used
                    removed.append(e)
                    total -= e.size_bytes
            for e in removed:
                try:
                    os.remove(e.path)
                except OSError:
                    pass
        if removed:
            self._log({"event": "aot_cache_purge",
                       "removed": len(removed),
                       "bytes": sum(e.size_bytes for e in removed)})
        return removed


def cached_compile(fn: Any, name: str, *avals,
                   cache: Optional[ExecutableCache] = None,
                   cache_dir: Optional[str] = None,
                   donate_argnums: Sequence[int] = (),
                   mesh: Any = None,
                   compiler_options: Optional[dict] = None,
                   key_extra: str = "",
                   registry: Optional[MetricsRegistry] = None,
                   log: Optional[Callable[[dict], None]] = None):
    """Lower `fn` at `avals`, then fetch-or-compile the executable.

    `fn` may be a plain python callable (jitted here with
    `donate_argnums`) or an existing `jax.jit` object — the latter keeps
    its own in/out shardings and donation. `avals` are positional
    arguments for `.lower()`: pytrees of `jax.ShapeDtypeStruct` or
    concrete arrays (whose exact avals, weak types included, are what
    get compiled). Returns a callable `jax.stages.Compiled`.
    """
    if cache is None and cache_dir is not None:
        cache = ExecutableCache(cache_dir, registry=registry, log=log)
    jitted = fn if hasattr(fn, "lower") else \
        jax.jit(fn, donate_argnums=tuple(donate_argnums))
    exe, _ = _compile_with_cache(jitted, name, avals, cache=cache,
                                 mesh=mesh,
                                 compiler_options=compiler_options,
                                 key_extra=key_extra,
                                 registry=registry)
    return exe


def _compile_with_cache(jitted, name: str, avals: tuple,
                        cache: Optional[ExecutableCache],
                        mesh: Any, compiler_options: Optional[dict],
                        registry: Optional[MetricsRegistry],
                        key_extra: str = ""):
    """lower → key → load-or-compile; returns (executable, key)."""
    with span("aot/lower"):
        lowered = jitted.lower(*avals)
    key = cache_key(name, lowered, mesh=mesh,
                    compiler_options=compiler_options, extra=key_extra)
    if cache is not None:
        exe = cache.load(name, key, out_tree=lowered.out_tree)
        if exe is not None:
            _counter(HITS_METRIC, registry).labels(name).inc()
            return exe, key
    _counter(MISSES_METRIC, registry).labels(name).inc()
    with span("aot/compile"):
        compiled = lowered.compile(compiler_options) \
            if compiler_options else lowered.compile()
    if cache is not None:
        cache.store(name, key, compiled)
    return compiled, key


class CachedFunction:
    """jit-like callable backed by one AOT executable per input-shape
    signature.

    Drop-in for the `jax.jit(fn)` objects the serving engine and the
    trainer hold: call it with concrete arguments; the first call per
    shape signature lowers, consults the cache, and compiles on a miss
    — subsequent calls dispatch straight to the executable. `warm()`
    compiles/loads without executing (the manifest-replay path).
    `_cache_size()` mirrors the jit introspection hook the serving
    compile-once tests use.
    """

    def __init__(self, fn: Any, name: str,
                 cache: Optional[ExecutableCache] = None,
                 donate_argnums: Sequence[int] = (),
                 mesh: Any = None,
                 compiler_options: Optional[dict] = None,
                 manifest: Any = None,
                 fingerprint_extra: str = "",
                 key_extra: str = "",
                 registry: Optional[MetricsRegistry] = None,
                 log: Optional[Callable[[dict], None]] = None):
        self._jitted = fn if hasattr(fn, "lower") else \
            jax.jit(fn, donate_argnums=tuple(donate_argnums))
        self.name = name
        self.cache = cache
        self.mesh = mesh
        self.compiler_options = compiler_options
        self.manifest = manifest
        self.fingerprint_extra = fingerprint_extra
        #: folded into the content address itself (see `cache_key`):
        #: static placement context two programs must never share
        self.key_extra = key_extra
        self._fingerprint: Optional[str] = None
        self._registry = registry
        self._log = log or (lambda entry: None)
        self._exes: Dict[Tuple, Any] = {}
        #: fast path: when exactly ONE executable exists (the decode
        #: step, the train step), dispatch without recomputing the
        #: pytree signature per call
        self._solo: Optional[Any] = None
        self._lock = threading.Lock()

    def _signature(self, args: tuple) -> Tuple:
        from jax.api_util import shaped_abstractify
        leaves, treedef = jax.tree_util.tree_flatten(args)
        return (treedef, tuple(shaped_abstractify(l) for l in leaves))

    def trusted_fingerprint(self) -> str:
        """The code+env+config identity under which an executable may
        be adopted from the cache WITHOUT re-lowering (see
        `cache.trusted_fingerprint`)."""
        if self._fingerprint is None:
            extra = (f"{self.name}|{self.compiler_options!r}|"
                     f"{self.fingerprint_extra}")
            if self.key_extra:
                # key_extra gates trusted replay too — but ONLY when
                # set: appending unconditionally would change the
                # fingerprint of every existing key_extra="" user and
                # invalidate their recorded warmup manifests
                extra += f"|{self.key_extra}"
            self._fingerprint = trusted_fingerprint(extra=extra,
                                                    mesh=self.mesh)
        return self._fingerprint

    def adopt(self, avals: tuple, key: str) -> bool:
        """Install the cached executable stored under `key` as the
        program for `avals`, skipping lower entirely — ONLY valid when
        the caller has verified `trusted_fingerprint()` matches the one
        recorded alongside `key` (manifest replay does). False on a
        missing/corrupt blob: the caller falls back to `warm()`."""
        if self.cache is None:
            return False
        sig = self._signature(avals)
        if sig in self._exes:
            return True
        exe = self.cache.load(self.name, key)
        if exe is None:
            return False
        _counter(HITS_METRIC, self._registry).labels(self.name).inc()
        self._install(sig, exe)
        return True

    def _install(self, sig: Tuple, exe: Any) -> Any:
        """First-insert-wins registration; keeps the solo fast path
        coherent."""
        with self._lock:
            exe = self._exes.setdefault(sig, exe)
            self._solo = exe if len(self._exes) == 1 else None
            return exe

    def _executable_for(self, args: tuple):
        sig = self._signature(args)
        exe = self._exes.get(sig)
        if exe is not None:
            return exe
        # compile OUTSIDE the lock: XLA compilation releases the GIL,
        # so distinct signatures (the manifest replay's prefill
        # buckets) build in parallel; a duplicate race costs one
        # redundant compile and resolves first-insert-wins (the store
        # converges on the same content-addressed blob anyway)
        exe, key = _compile_with_cache(
            self._jitted, self.name, args, cache=self.cache,
            mesh=self.mesh, compiler_options=self.compiler_options,
            registry=self._registry, key_extra=self.key_extra)
        if self.manifest is not None:
            self.manifest.record(self.name, args, mesh=self.mesh,
                                 key=key,
                                 fingerprint=self.trusted_fingerprint())
        return self._install(sig, exe)

    def __call__(self, *args):
        solo = self._solo
        if solo is not None:
            try:
                return solo(*args)
            except TypeError:
                # a second signature arriving (or an adopted blob whose
                # trees disagree with the live call): resolve properly
                # below. Raised at dispatch, before any donated buffer
                # is consumed.
                pass
        exe = self._executable_for(args)
        try:
            return exe(*args)
        except TypeError as e:
            # a deserialized executable whose pytree container types
            # (e.g. FrozenDict vs dict from a manifest round-trip)
            # disagree with the live call — THE invariant again: fall
            # back to plain jit, never fail the job. Raised at
            # dispatch, before any donated buffer is consumed.
            _counter(ERRORS_METRIC, self._registry).labels(
                self.name).inc()
            self._log({"event": "aot_cache_error", "fn": self.name,
                       "stage": "dispatch", "error": str(e)[:500]})
            with self._lock:
                self._exes.pop(self._signature(args), None)
                self._solo = None
            return self._jitted(*args)

    def warm(self, *avals) -> None:
        """Ensure the executable for `avals` exists (compile or
        deserialize) without running it."""
        self._executable_for(avals)

    def _cache_size(self) -> int:
        return len(self._exes)
