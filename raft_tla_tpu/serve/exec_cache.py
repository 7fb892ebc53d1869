"""Persistent AOT bucket-executable cache (ROADMAP item 1, round 13).

The batched serving layer AOT-compiles one executable per (bucket,
padded job count) via ``.lower().compile()`` — tens of seconds per
program on a TPU (not measured on the current code) — and until now
every process restart re-paid all of
them.  This module serializes compiled executables to disk around that
call (``serve/batch.BucketEngine``), keyed so a stale or foreign entry
can never be silently executed:

- **key** — sha256 of the canonical JSON of every compile-relevant
  part: backend fingerprint (platform, device kind + count, jax
  version), spec name + IR structure fingerprint, the bucket CEILING
  config repr + bucket params, the padded job count JP, the wave-mesh
  shape (the ``[J, S]`` grid — resharding is a different GSPMD
  program, so a mesh-shape change is a NAMED miss, never a wrong
  load), and the engine's program-shaping option/mode flags
  (guard/delta matmul, runtime-thresholds mode, ring/cap widths, W,
  family caps).  Any drift in any part is a different key — a miss,
  never a wrong load.
- **entries** — one ``<key>.exec`` file per executable: a pickled
  container embedding the FULL key and its parts next to the
  serializer's blob, published atomically (write + rename).  A corrupt
  or truncated file, a foreign/renamed entry, or an embedded key
  mismatch all read as a labeled miss.
- **honesty** — backends whose runtime cannot (de)serialize
  executables (``jax.experimental.serialize_executable`` raising, or
  absent) degrade to a NAMED miss/store-failure reason, counted and
  surfaced in the batch summary + ledger — never a crash, never a
  silent recompile that the telemetry reports as a hit.

The serializer is injectable (``serializer=``) so CPU tests pin the
keying, the round-trip plumbing, and the corrupt-entry paths without
depending on the backend's serialization support (the CPU runtime
does round-trip, which the tests also exercise for real).
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
from typing import Dict, Optional, Tuple

_FORMAT = 1

# the backend identity helper was born here as part of the cache key;
# ISSUE 17 hoisted it to obs/resources.py (the obs layer stamps the
# same dict on every ledger meta row and registry record) — re-exported
# so cache-key call sites keep importing it from here
from ..obs.resources import backend_fingerprint  # noqa: E402,F401


_CODE_FP = None


def code_fingerprint() -> str:
    """sha256 over every ``raft_tla_tpu`` source file's bytes (path-
    sorted) — the SOURCE identity of the compiled program.  Without
    this a warm cache would happily serve executables compiled from
    an older checkout after a semantics-affecting engine/kernel/spec
    change: every other key part (backend, ceiling repr, shape flags)
    would still match, and the service would return the OLD code's
    answers while telemetry reports a healthy hit.  Hashing the
    package source makes any code drift a guaranteed (coarse but
    safe) miss.  Computed once per process."""
    global _CODE_FP
    if _CODE_FP is None:
        import raft_tla_tpu
        root = os.path.dirname(os.path.abspath(raft_tla_tpu.__file__))
        h = hashlib.sha256()
        for dirpath, dirnames, filenames in sorted(os.walk(root)):
            dirnames.sort()
            for nm in sorted(filenames):
                if not nm.endswith(".py"):
                    continue
                rel = os.path.relpath(os.path.join(dirpath, nm), root)
                h.update(rel.encode())
                with open(os.path.join(dirpath, nm), "rb") as fh:
                    h.update(fh.read())
        _CODE_FP = h.hexdigest()[:16]
    return _CODE_FP


def exec_key(parts: Dict) -> str:
    """Canonical-JSON sha256 of the key parts (order-independent)."""
    desc = json.dumps(parts, sort_keys=True, separators=(",", ":"),
                      default=str)
    return hashlib.sha256(desc.encode()).hexdigest()[:32]


class JaxExecSerializer:
    """The real thing: ``jax.experimental.serialize_executable``.
    ``serialize`` returns one bytes blob (payload + in/out pytree defs
    pickled together); ``deserialize`` loads it back into a callable
    Compiled.  Either side may raise on backends without serialization
    support — ExecCache turns that into a labeled miss."""

    name = "jax.experimental.serialize_executable"

    def serialize(self, compiled) -> bytes:
        import jax
        from jax.experimental import serialize_executable as se
        if (jax.config.jax_enable_compilation_cache
                and jax.config.jax_compilation_cache_dir
                and jax.default_backend() == "cpu"):
            # jax 0.9's XLA:CPU re-serializes an executable that JAX's
            # persistent cache loaded into a blob that fails at run
            # time ("Function ... not found"); that cache already
            # spares the compile, so store nothing
            raise RuntimeError("XLA:CPU cannot re-serialize executables "
                               "while the persistent compilation cache "
                               "is on")
        payload, in_tree, out_tree = se.serialize(compiled)
        return pickle.dumps((payload, in_tree, out_tree))

    def deserialize(self, blob: bytes):
        from jax.experimental import serialize_executable as se
        payload, in_tree, out_tree = pickle.loads(blob)
        return se.deserialize_and_load(payload, in_tree, out_tree)


class ExecCache:
    """One directory of serialized bucket executables + honest hit/miss
    accounting.  ``load``/``store`` never raise on entry or backend
    problems — every failure is a counted, named miss (the acceptance
    contract: a non-serializable backend reads as a labeled miss, not
    a crash or a silent wrong result)."""

    def __init__(self, path: str, serializer=None,
                 max_bytes: Optional[int] = None):
        if max_bytes is not None and int(max_bytes) <= 0:
            raise ValueError(
                f"executable-cache max_bytes must be positive (got "
                f"{max_bytes}); omit it for an unbounded cache")
        self.path = path
        # LRU-by-bytes eviction bound (mirrors serve/cache.ResultCache,
        # the ROADMAP item-1 leftover: bucket executables are MBs each
        # on TPU, so a long-lived service needs a directory bound).
        # Recency = file mtime, refreshed on every warm LOAD, so a hot
        # bucket survives cold ones regardless of insertion order; the
        # just-stored entry is never the victim (one oversized
        # executable may transiently exceed the bound — the next store
        # retires it like any other cold entry).  None = the historical
        # unbounded behavior, exactly.
        self.max_bytes = None if max_bytes is None else int(max_bytes)
        os.makedirs(path, exist_ok=True)
        self._ser = serializer if serializer is not None \
            else JaxExecSerializer()
        self.hits = 0
        self.misses = 0
        self.stores = 0
        self.store_failures = 0
        self.evictions = 0
        # the most recent miss/store-failure reasons, newest last
        # (bounded: telemetry, not a log)
        self.miss_reasons = []
        self.store_fail_reasons = []

    # -- accounting ----------------------------------------------------

    def _miss(self, reason: str) -> Tuple[None, str]:
        self.misses += 1
        self.miss_reasons = (self.miss_reasons + [reason])[-8:]
        return None, reason

    def stats(self) -> Dict:
        return {
            "exec_cache_hits": self.hits,
            "exec_cache_misses": self.misses,
            "exec_cache_stores": self.stores,
            "exec_cache_store_failures": self.store_failures,
            "exec_cache_evictions": self.evictions,
            "exec_cache_miss_reasons": list(self.miss_reasons),
            "exec_cache_store_fail_reasons":
                list(self.store_fail_reasons),
        }

    def _touch(self, key: str):
        """LRU recency refresh on a warm load — bounded caches only
        (unbounded reads stay write-free, the historical behavior)."""
        if self.max_bytes is None:
            return
        try:
            os.utime(self._entry_path(key))
        except OSError:
            pass

    def _evict(self, keep: str):
        """Trim the directory back under max_bytes, least-recently-
        used (oldest mtime) first, never touching the just-written
        ``keep`` entry.  A racing deletion reads as already-evicted,
        never an error."""
        if self.max_bytes is None:
            return
        entries = []
        total = 0
        for nm in os.listdir(self.path):
            if not nm.endswith(".exec"):
                continue
            fp = os.path.join(self.path, nm)
            try:
                st = os.stat(fp)
            except OSError:
                continue
            total += st.st_size
            entries.append((st.st_mtime, st.st_size, nm))
        if total <= self.max_bytes:
            return
        for _mtime, size, nm in sorted(entries):
            if nm == keep + ".exec":
                continue
            try:
                os.remove(os.path.join(self.path, nm))
            except OSError:
                continue
            self.evictions += 1
            total -= size
            if total <= self.max_bytes:
                break

    def _entry_path(self, key: str) -> str:
        return os.path.join(self.path, key + ".exec")

    # -- the two operations BucketEngine wraps around lower/compile ----

    def load(self, key: str, parts: Optional[Dict] = None):
        """(executable | None, reason).  Every None is a labeled
        miss: cold entry, corrupt/truncated pickle, foreign entry
        (embedded key mismatch — e.g. a renamed or hand-copied file),
        serializer mismatch, or a backend that cannot deserialize."""
        fp = self._entry_path(key)
        if not os.path.exists(fp):
            return self._miss("cold: no entry for this key")
        try:
            with open(fp, "rb") as fh:
                obj = pickle.load(fh)
        except Exception as e:
            return self._miss(
                f"corrupt entry (unreadable: {type(e).__name__})")
        if not isinstance(obj, dict) or obj.get("format") != _FORMAT:
            return self._miss("corrupt entry (bad container format)")
        if obj.get("key") != key:
            return self._miss(
                "foreign entry (embedded key mismatch — file renamed "
                "or copied across caches)")
        if parts is not None and obj.get("parts") != dict(parts):
            # belt + suspenders under the truncated-sha key: the FULL
            # part set must match, not just its digest
            return self._miss(
                "foreign entry (embedded key parts mismatch)")
        ser_name = getattr(self._ser, "name", type(self._ser).__name__)
        if obj.get("serializer") != ser_name:
            return self._miss(
                f"serializer mismatch (entry: {obj.get('serializer')!r},"
                f" runtime: {ser_name!r})")
        try:
            ex = self._ser.deserialize(obj["blob"])
        except Exception as e:
            return self._miss(
                f"backend cannot deserialize executables "
                f"({type(e).__name__}: {str(e)[:120]})")
        self.hits += 1
        self._touch(key)
        return ex, "hit"

    def store(self, key: str, compiled, parts: Optional[Dict] = None
              ) -> bool:
        """Serialize + atomically publish one executable; False (with
        a recorded named reason) when the backend cannot serialize —
        the compile that just happened still served the run, the cache
        simply stays cold."""
        try:
            blob = self._ser.serialize(compiled)
        except Exception as e:
            self.store_failures += 1
            self.store_fail_reasons = (self.store_fail_reasons + [
                f"backend cannot serialize executables "
                f"({type(e).__name__}: {str(e)[:120]})"])[-8:]
            return False
        obj = {"format": _FORMAT, "key": key,
               "parts": dict(parts or {}),
               "serializer": getattr(self._ser, "name",
                                     type(self._ser).__name__),
               "blob": blob}
        fp = self._entry_path(key)
        tmp = fp + ".tmp"
        try:
            with open(tmp, "wb") as fh:
                pickle.dump(obj, fh)
            os.replace(tmp, fp)
        except OSError as e:
            self.store_failures += 1
            self.store_fail_reasons = (self.store_fail_reasons + [
                f"cache dir unwritable ({e})"])[-8:]
            return False
        self.stores += 1
        self._evict(keep=key)
        return True
