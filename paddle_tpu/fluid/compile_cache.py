"""Persistent AOT executable cache: compiled programs as artifacts.

Every process of this system used to pay the full XLA compile storm
from scratch — the gateway's ``_warm`` compiled each new version during
a hot swap, and a supervised restart recompiled every serving bucket
exactly when the system was degraded.  Following the whole-program-
compilation-as-deployable-artifact model (PAPERS.md arxiv 1810.09868),
this module makes the compiled executable itself a durable, shippable
artifact:

* **keys** are the PR 3 content-addressed program fingerprint
  (``ProgramDesc.fingerprint()``) plus the executor's full dispatch
  signature (mode, feed/state shapes+dtypes, fetch list, guard set,
  mesh axes/devices), **salted** with everything that invalidates a
  serialized executable: jax/jaxlib version, backend platform, device
  kind and count.  A stale salt is a MISS, never a wrong executable.
* **values** are PJRT-serialized executables
  (``jax.experimental.serialize_executable`` — the AOT
  ``compiled.serialize()`` surface), stored one file per entry with a
  sha256 content checksum.  A torn, corrupt, or chaos-flipped entry
  fails the checksum and degrades to a compile (which overwrites it).
* **writes** use the ``utils/journal`` durability idiom — tmp file in
  the same directory, flush + fsync, atomic rename — and never run
  under any of the PR 12 ordered locks: the cache is lock-free by
  construction (atomic renames make concurrent same-key writers
  last-wins-safe, and stats bumps are GIL-atomic).
* **backends that cannot serialize** (some PJRT plugins refuse) fall
  back to compile-without-store; the executor still runs, the cache
  just stays cold and counts ``serialize_unsupported``.
* **no buffer donation** in stored executables: jaxlib's deserialize
  path mishandles donated-input buffer ownership (chained calls over a
  deserialized donating executable corrupt nondeterministically and
  double-free at exit — see Executor._aot_compile).  Cached entries
  trade one output copy per aliased state buffer for zero compiles;
  the donating in-memory jit path is unchanged when the tier is off.

The executor consults this tier between its in-memory executable cache
and XLA (``Executor.cache_stats()["persistent"]``); the gateway's
``ModelRegistry`` mounts a per-version cache at the artifact's
``compiled/`` subdirectory so a published model version *ships* its
compiled bucket set (pre-warmed offline by ``python -m
paddle_tpu.tools.aot_compile``); ``bench.py``'s ``aot`` section prices
restart-to-first-token and swap-to-first-token with and without it.

Eviction: ``max_bytes`` (ctor or ``PADDLE_TPU_AOT_MAX_BYTES``) bounds a
cache directory; stores evict least-recently-used entries (file atime,
falling back to mtime) past the bound.  0/None = unbounded — a model
version's ``compiled/`` dir holds a closed bucket set and needs no
eviction.
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import time
from typing import Any, Dict, List, Optional

__all__ = ["CompileCache", "backend_salt", "default_cache",
           "set_default_cache", "serialize_compiled",
           "deserialize_compiled"]

_MAGIC = b"PDLAOT1\n"
_SUFFIX = ".aotx"

# process-default cache (PADDLE_TPU_AOT_CACHE env, or set_default_cache):
# executors with no explicit cache consult this; None disables the tier.
_default: List[Optional["CompileCache"]] = [None]
_default_resolved = [False]


def backend_salt() -> Dict[str, Any]:
    """Everything that invalidates a serialized executable besides the
    program + dispatch signature.  Keyed INTO the entry name: a version
    or device change simply addresses a different entry (a miss), so a
    cache directory can be shared across heterogeneous readers."""
    import jax
    import jaxlib

    try:
        dev = jax.devices()[0]
        kind, platform = dev.device_kind, dev.platform
    except Exception:           # no backend at all: still hashable
        kind, platform = "none", "none"
    return {
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "platform": platform,
        "device_kind": kind,
        "device_count": jax.device_count(),
    }


def serialize_compiled(compiled) -> Optional[bytes]:
    """PJRT-serialize a ``jax.stages.Compiled`` into one self-contained
    blob (executable payload + arg/out pytree defs); None when the
    backend refuses (compile-and-store fallback: the caller keeps the
    live executable and skips the store)."""
    try:
        from jax.experimental import serialize_executable as _se

        payload, in_tree, out_tree = _se.serialize(compiled)
        return pickle.dumps((payload, in_tree, out_tree),
                            protocol=pickle.HIGHEST_PROTOCOL)
    except Exception:
        return None


def deserialize_compiled(blob: bytes, devices):
    """Load a ``serialize_compiled`` blob back into a callable
    ``jax.stages.Compiled`` bound to ``devices`` — the devices the entry
    was compiled for (one device, or the mesh's).  jax defaults
    ``execution_devices`` to EVERY device of the backend, so an
    executable compiled for one device fails its first call on a
    multi-device host ("Expected args ... to have N shards, got: [1]")."""
    from jax.experimental import serialize_executable as _se

    payload, in_tree, out_tree = pickle.loads(blob)
    return _se.deserialize_and_load(payload, in_tree, out_tree,
                                    execution_devices=list(devices))


def _canon(obj):
    """Canonicalize a key part into something JSON-stable: tuples/lists
    -> lists, dict -> sorted items, everything exotic -> repr."""
    if isinstance(obj, (tuple, list)):
        return [_canon(x) for x in obj]
    if isinstance(obj, dict):
        return [[_canon(k), _canon(v)] for k, v in sorted(obj.items())]
    if obj is None or isinstance(obj, (bool, int, float, str)):
        return obj
    return repr(obj)


class CompileCache:
    """One directory of checksum-framed serialized executables."""

    def __init__(self, dirname: str, extra_salt: Optional[Dict] = None,
                 max_bytes: Optional[int] = None):
        self.dirname = str(dirname)
        # extra_salt is the test/ops override surface: anything a
        # deployment wants to additionally invalidate on (a cluster
        # config epoch, a toolchain build id) folds into every key
        self.extra_salt = dict(extra_salt or {})
        if max_bytes is None:
            max_bytes = int(os.environ.get("PADDLE_TPU_AOT_MAX_BYTES",
                                           "0")) or None
        self.max_bytes = max_bytes
        self._salt: Optional[Dict] = None
        self._stats = {"hits": 0, "misses": 0, "stores": 0,
                       "corrupt": 0, "errors": 0, "evictions": 0,
                       "serialize_unsupported": 0,
                       "bytes_read": 0, "bytes_written": 0,
                       "load_ms": 0.0}
        _register_cache_collector(self)

    # -- keys ----------------------------------------------------------------
    def salt(self) -> Dict[str, Any]:
        if self._salt is None:
            s = backend_salt()
            s.update(self.extra_salt)
            self._salt = s
        return self._salt

    def entry_key(self, parts) -> str:
        """Content-addressed entry name: sha256 over the canonical JSON
        of (dispatch-signature parts, backend salt).  The parts are the
        executor's full in-memory cache key — program fingerprint, mode,
        mesh axes/devices, feed/state signatures, fetch names, guard
        set — so any dispatch the in-memory tier would recompile for
        addresses a distinct persistent entry too."""
        doc = json.dumps([_canon(parts), _canon(self.salt())],
                         sort_keys=True, separators=(",", ":"))
        return hashlib.sha256(doc.encode("utf-8")).hexdigest()

    def _path(self, key: str) -> str:
        return os.path.join(self.dirname, key + _SUFFIX)

    def keys(self) -> List[str]:
        """Entry keys currently on disk (sorted — byte-stable across
        runs, which the lint sweep asserts)."""
        if not os.path.isdir(self.dirname):
            return []
        return sorted(n[:-len(_SUFFIX)] for n in os.listdir(self.dirname)
                      if n.endswith(_SUFFIX))

    # -- load ----------------------------------------------------------------
    def load(self, key: str, devices):
        """Deserialize entry ``key`` into a live executable on
        ``devices`` (those it was compiled for), or None on miss /
        integrity failure (the corrupt entry is deleted so the following
        store overwrites it cleanly)."""
        path = self._path(key)
        t0 = time.perf_counter()
        try:
            with open(path, "rb") as f:
                raw = f.read()
        except OSError:
            self._stats["misses"] += 1
            return None
        # chaos point (`aot.corrupt`): a seeded torn/flipped read —
        # the integrity path must degrade to a compile, never crash or
        # load garbage into the device
        from ..resilience.chaos import injector

        if injector().should("aot.corrupt") and len(raw) > len(_MAGIC):
            raw = raw[:len(raw) // 2]
        blob = self._checked_blob(raw, key)
        if blob is None:
            self._stats["corrupt"] += 1
            self._stats["misses"] += 1
            try:
                os.unlink(path)
            except OSError:
                pass
            return None
        try:
            compiled = deserialize_compiled(blob, devices)
        except Exception:
            # a salt collision can't produce this (the salt is in the
            # key), but a PJRT refusing its own bytes can — degrade
            self._stats["errors"] += 1
            self._stats["misses"] += 1
            return None
        self._stats["hits"] += 1
        self._stats["bytes_read"] += len(raw)
        self._stats["load_ms"] += (time.perf_counter() - t0) * 1e3
        return compiled

    def _checked_blob(self, raw: bytes, key: str) -> Optional[bytes]:
        """Parse + verify one entry file; None on any integrity failure
        (bad magic, torn header, checksum mismatch, stale-salt header —
        a salt that no longer matches ours means the key scheme changed
        under us and the bytes cannot be trusted)."""
        if not raw.startswith(_MAGIC):
            return None
        try:
            head_end = raw.index(b"\n", len(_MAGIC))
            header = json.loads(raw[len(_MAGIC):head_end].decode("utf-8"))
            blob = raw[head_end + 1:]
        except (ValueError, UnicodeDecodeError):
            return None
        if header.get("key") != key:
            return None
        if header.get("salt") != _canon(self.salt()):
            return None
        if len(blob) != header.get("blob_bytes"):
            return None
        if hashlib.sha256(blob).hexdigest() != header.get("sha256"):
            return None
        return blob

    # -- store ---------------------------------------------------------------
    def store(self, key: str, compiled) -> bool:
        """Serialize + durably publish one executable under ``key``;
        False when the backend can't serialize (counted, not raised).
        tmp-file + fsync + atomic-rename (the utils/journal idiom): a
        crash mid-store leaves the old entry or no entry, never a torn
        one — and the checksum catches torn anyway."""
        blob = serialize_compiled(compiled)
        if blob is None:
            self._stats["serialize_unsupported"] += 1
            return False
        header = json.dumps({
            "key": key, "salt": _canon(self.salt()),
            "sha256": hashlib.sha256(blob).hexdigest(),
            "blob_bytes": len(blob), "created": time.time(),
        }, sort_keys=True, separators=(",", ":")).encode("utf-8")
        raw = _MAGIC + header + b"\n" + blob
        path = self._path(key)
        # pid AND thread id: two threads of one process missing the same
        # key must not interleave into one tmp file (the atomic-rename
        # last-wins guarantee is per WRITER, not just per process)
        import threading

        tmp = f"{path}.{os.getpid()}.{threading.get_ident()}.tmp"
        try:
            os.makedirs(self.dirname, exist_ok=True)
            with open(tmp, "wb") as f:
                f.write(raw)
                f.flush()
                os.fsync(f.fileno())
            os.rename(tmp, path)
        except OSError:
            self._stats["errors"] += 1
            try:
                os.unlink(tmp)
            except OSError:
                pass
            return False
        self._stats["stores"] += 1
        self._stats["bytes_written"] += len(raw)
        if self.max_bytes:
            self._evict(keep=path)
        return True

    def _evict(self, keep: Optional[str] = None) -> None:
        """Drop least-recently-used entries until the directory fits
        ``max_bytes`` (the just-written entry is exempt)."""
        entries = []
        total = 0
        try:
            names = os.listdir(self.dirname)
        except OSError:
            return
        for n in names:
            if not n.endswith(_SUFFIX):
                continue
            p = os.path.join(self.dirname, n)
            try:
                st = os.stat(p)
            except OSError:
                continue
            total += st.st_size
            entries.append((max(st.st_atime, st.st_mtime), st.st_size, p))
        entries.sort()
        for _, size, p in entries:
            if total <= self.max_bytes:
                break
            if p == keep:
                continue
            try:
                os.unlink(p)
            except OSError:
                continue
            total -= size
            self._stats["evictions"] += 1

    # -- accounting ----------------------------------------------------------
    def stats(self) -> Dict[str, Any]:
        out = dict(self._stats)
        out["load_ms"] = round(out["load_ms"], 3)
        out["entries"] = len(self.keys())
        out["dir"] = self.dirname
        return out


# -- process default ---------------------------------------------------------
def default_cache() -> Optional[CompileCache]:
    """The process-default persistent tier: a ``CompileCache`` set via
    ``set_default_cache``, else one mounted at ``PADDLE_TPU_AOT_CACHE``
    when that env var names a directory, else None (tier disabled)."""
    if not _default_resolved[0]:
        _default_resolved[0] = True
        path = os.environ.get("PADDLE_TPU_AOT_CACHE", "")
        if path:
            _default[0] = CompileCache(path)
    return _default[0]


def set_default_cache(cache) -> Optional[CompileCache]:
    """Install (or with None, clear) the process-default cache; accepts
    a CompileCache or a directory path.  Returns the installed cache."""
    if isinstance(cache, str):
        cache = CompileCache(cache)
    _default[0] = cache
    _default_resolved[0] = True
    return cache


# -- telemetry ----------------------------------------------------------------
_LIVE_CACHES = None     # lazy weakset: metrics import must stay optional
_collector_registered = [False]


def _register_cache_collector(cache: CompileCache) -> None:
    global _LIVE_CACHES
    import weakref

    if _LIVE_CACHES is None:
        _LIVE_CACHES = weakref.WeakSet()
    _LIVE_CACHES.add(cache)
    if _collector_registered[0]:
        return
    _collector_registered[0] = True
    from ..observability.metrics import registry as _obs_registry

    _obs_registry().register_collector(_collect_aot_metrics)


def _collect_aot_metrics():
    """paddle_aot_* series: per-event counters + bytes moved, summed
    over every live cache (the scrape-time collector idiom of PR 8)."""
    from ..observability.metrics import Sample

    for cache in list(_LIVE_CACHES or ()):
        st = cache._stats
        for ev in ("hits", "misses", "stores", "corrupt", "errors",
                   "evictions", "serialize_unsupported"):
            yield Sample("paddle_aot_cache_events_total", "counter",
                         (("event", ev),), float(st[ev]),
                         "Persistent AOT executable cache events")
        for direction in ("read", "written"):
            yield Sample("paddle_aot_cache_bytes_total", "counter",
                         (("direction", direction),),
                         float(st[f"bytes_{direction}"]),
                         "Serialized executable bytes moved")
        yield Sample("paddle_aot_cache_load_ms_total", "counter", (),
                     float(st["load_ms"]),
                     "Milliseconds spent deserializing cached "
                     "executables")
