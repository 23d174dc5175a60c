"""Crash-safe persistent cache of serialized AOT executables.

Every elastic restart (PR 10), serving-replica replacement (PR 12) and
autoscale boot pays a full XLA compile from scratch — recovery time after a
preemption is DOMINATED by recompilation (the r04 bench round died with 8/8
probes hung in exactly that window). This module makes the compile a
once-per-fleet cost: the first process to compile a program serializes the
executable into a content-addressed on-disk entry, and every later process
generation — a supervisor respawn, a replacement replica, a new autoscaled
worker sharing the directory — loads it back in milliseconds instead of
recompiling.

**Keying.** An entry is addressed by :class:`CacheKey`: the SHA-256 of the
traced program's StableHLO text plus everything that changes what XLA would
produce for it — the mesh axis→size map, the device kind and visible device
count, the jax/jaxlib/backend versions, and the XLA compile flags. Any
difference lands on a different entry id, so a version bump or topology
change is a clean *miss*, never a wrong load.

**Crash consistency** (the PR 5 checkpoint protocol, applied to executables):
a writer serializes into a private ``<entry>.tmp-<pid>-<nonce>`` staging
directory, fsyncs every file, writes the CRC32-carrying ``MANIFEST.json``
*last*, fsyncs the staging dir, then atomically ``os.rename``s it onto the
final entry name. A ``kill -9`` at ANY point leaves either a fully committed
entry or an orphaned staging dir (swept on a later store) — never a torn
entry under the committed name. Concurrent writers race benignly: the first
rename wins, losers discard their staging.

**Defensive reads.** A poisoned cache must never crash a restart or load the
wrong executable. Every load re-validates the manifest (parseable, schema,
every key field equal to the *requested* key — a swapped manifest or a
tampered version/topology field fails here) and the payload CRC32 before
deserializing; any failure **quarantines** the entry (moved aside for the
operator, so the next restart does not re-trip on it) and reports a corrupt
outcome — the caller falls back to a fresh compile with a warning.

**Eviction.** ``ACCELERATE_COMPILE_CACHE_MAX_MB`` bounds the directory;
least-recently-HIT entries go first — every successful load touches the
entry's ``LAST_HIT`` stamp, so the executables a fleet actually reloads stay
resident while write-once-never-read entries age out (never-hit entries fall
back to their write time). ``ACCELERATE_COMPILE_CACHE_FN_QUOTA_MB`` bounds
each *function*'s share on top (the manifest ``fn`` field groups entries):
one model's serving lattice filling the directory evicts its OWN stale
points, not another fleet's step executables. Either way, an entry another
process currently holds a shared ``flock`` on (it is mid-load) is skipped —
eviction can never yank an executable out from under a reader.

The payload is a pickle of :func:`jax.experimental.serialize_executable.
serialize` output; like JAX's own persistent compilation cache, the
directory must be trusted (treat it with the same care as the checkpoint
dir it usually sits next to).
"""

from __future__ import annotations

import fcntl
import hashlib
import json
import os
import pickle
import shutil
import time
import zlib
from dataclasses import asdict, dataclass, field
from typing import Any, Optional

from ..logging import get_logger

logger = get_logger(__name__)

SCHEMA_VERSION = 1
MANIFEST_NAME = "MANIFEST.json"
PAYLOAD_NAME = "executable.bin"
LAST_HIT_NAME = "LAST_HIT"
QUARANTINE_DIRNAME = "quarantine"

#: Orphaned staging dirs (a writer killed mid-write) older than this are
#: swept by the next store; younger ones may belong to a live writer.
STALE_STAGING_AGE_S = 15 * 60.0


def _chaos_inject(point: str) -> None:
    # lazy import, same pattern as serving/engine.py: the cache must not pay
    # for (or cyclically import) the resilience stack at module load
    from ..resilience import chaos as _chaos

    _chaos.maybe_inject(point)


def _fsync_path(path: str) -> None:
    """fsync a file or directory by path (directory fsync is how a rename /
    create is made durable — same helper contract as checkpointing.py)."""
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def _file_crc32(path: str) -> int:
    crc = 0
    with open(path, "rb") as f:
        for block in iter(lambda: f.read(1 << 20), b""):
            crc = zlib.crc32(block, crc)
    return crc & 0xFFFFFFFF


# ------------------------------------------------------------------ keys ----
def environment_fingerprint() -> "dict[str, Any]":
    """The environment half of every cache key: anything that changes what
    XLA would compile for the same StableHLO. Collected defensively — a field
    an old jaxlib cannot report becomes ``"?"`` (still part of the key, so
    two processes disagree only if their environments actually differ)."""
    import jax
    import jaxlib

    try:
        try:
            from jax.extend.backend import get_backend
        except ImportError:  # older jax spells it differently
            from jax.lib.xla_bridge import get_backend
        backend_version = str(
            getattr(get_backend(), "platform_version", "?")
        ).strip()
    except Exception:
        backend_version = "?"
    try:
        devices = jax.devices()
        device_kind = str(getattr(devices[0], "device_kind", "?") or "?")
        num_devices = len(devices)
    except Exception:
        device_kind, num_devices = "?", 0
    return {
        "jax_version": jax.__version__,
        "jaxlib_version": jaxlib.__version__,
        "backend_version": backend_version,
        "device_kind": device_kind,
        "num_devices": num_devices,
        "flags": compile_flags(),
    }


def compile_flags() -> str:
    """Canonicalized XLA compile flags (order-independent): flag strings that
    differ only in token order must not split the cache."""
    return " ".join(sorted(os.environ.get("XLA_FLAGS", "").split()))


@dataclass(frozen=True)
class CacheKey:
    """Content address of one executable. ``fn`` is informational only (two
    identically-traced functions share an entry); every OTHER field is hashed
    into :attr:`entry_id` and re-verified against the manifest at load time."""

    fn: str
    fingerprint: str  # sha256 of the lowered StableHLO text
    mesh_axes: "tuple[tuple[str, int], ...]" = ()
    device_kind: str = "?"
    num_devices: int = 0
    jax_version: str = "?"
    jaxlib_version: str = "?"
    backend_version: str = "?"
    flags: str = ""

    def identity(self) -> "dict[str, Any]":
        """The hashed/verified fields (everything except ``fn``)."""
        out = asdict(self)
        out.pop("fn")
        out["mesh_axes"] = [[a, int(s)] for a, s in self.mesh_axes]
        return out

    @property
    def entry_id(self) -> str:
        digest = hashlib.sha256(
            json.dumps(self.identity(), sort_keys=True).encode()
        ).hexdigest()
        return digest[:24]


def key_from_lowered(name: str, lowered: Any, mesh: Optional[Any] = None) -> CacheKey:
    """Build the :class:`CacheKey` for a ``jax.stages.Lowered`` program.

    The StableHLO text embeds the traced computation including shardings, so
    its hash is stable across processes for the same program (proven by the
    cross-process key test); the mesh axis→size map is keyed explicitly on
    top because two meshes can produce the same module text for trivially
    replicated programs while compiling differently."""
    text = lowered.as_text()
    mesh_axes: "tuple[tuple[str, int], ...]" = ()
    if mesh is not None:
        try:
            mesh_axes = tuple((str(a), int(s)) for a, s in dict(mesh.shape).items())
        except Exception:
            mesh_axes = ()
    env = environment_fingerprint()
    return CacheKey(
        fn=name,
        fingerprint=hashlib.sha256(text.encode()).hexdigest(),
        mesh_axes=mesh_axes,
        **env,
    )


# --------------------------------------------------------------- results ----
@dataclass
class LoadResult:
    """Outcome of one :meth:`CompileCache.load`.

    ``outcome``: ``hit`` | ``miss`` | ``corrupt`` (validation failed, entry
    quarantined) — a corrupt outcome NEVER carries an executable; the caller
    must fall back to a fresh compile."""

    outcome: str
    executable: Optional[Any] = None
    reason: Optional[str] = None
    nbytes: int = 0
    seconds: float = 0.0
    quarantined_to: Optional[str] = None


@dataclass
class StoreResult:
    """Outcome of one :meth:`CompileCache.store`: ``stored`` | ``raced``
    (another writer committed first — benign) | ``error`` (serialization or
    IO failed; the cache stays as it was)."""

    outcome: str
    reason: Optional[str] = None
    nbytes: int = 0
    seconds: float = 0.0
    evicted: "list[str]" = field(default_factory=list)


class CompileCacheCorrupt(RuntimeError):
    """Internal: entry failed validation (caught inside :meth:`load`)."""


# ----------------------------------------------------------------- cache ----
class CompileCache:
    """One on-disk executable cache directory (shareable across hosts).

    All methods are safe against concurrent readers/writers in other
    processes and against being killed at any point; none of them raise on a
    sick filesystem or a poisoned entry — degraded outcomes are returned, not
    thrown (the one exception: the constructor raises ``OSError`` if the
    directory cannot be created, which :func:`~accelerate_tpu.compile_cache.
    runtime.pretouch` turns into a visible cold-start warning)."""

    def __init__(
        self,
        directory: str,
        max_mb: Optional[float] = None,
        fn_quota_mb: Optional[float] = None,
    ):
        self.directory = os.path.abspath(directory)
        self.max_mb = max_mb
        self.fn_quota_mb = fn_quota_mb
        os.makedirs(self.directory, exist_ok=True)

    # -- layout ---------------------------------------------------------------
    def entry_dir(self, key: CacheKey) -> str:
        return os.path.join(self.directory, key.entry_id)

    def quarantine_dir(self) -> str:
        return os.path.join(self.directory, QUARANTINE_DIRNAME)

    def entries(self) -> "list[str]":
        """Committed entry dirs (manifest present), least-recently-hit first
        (a never-hit entry's recency is its write time)."""
        out = []
        try:
            names = os.listdir(self.directory)
        except OSError:
            return []
        for n in names:
            p = os.path.join(self.directory, n)
            if n == QUARANTINE_DIRNAME or ".tmp-" in n:
                continue
            if os.path.isfile(os.path.join(p, MANIFEST_NAME)):
                out.append(p)
        return sorted(out, key=lambda p: self._last_hit(p))

    @staticmethod
    def _mtime(path: str) -> float:
        try:
            return os.path.getmtime(path)
        except OSError:
            return 0.0

    def _last_hit(self, path: str) -> float:
        """Eviction recency: the ``LAST_HIT`` stamp a load touches, falling
        back to the entry's write time for entries never read back."""
        try:
            return os.path.getmtime(os.path.join(path, LAST_HIT_NAME))
        except OSError:
            return self._mtime(path)

    @staticmethod
    def _touch_last_hit(entry: str) -> None:
        """Stamp read recency after a validated load (best effort, no fsync:
        recency is advisory — losing a stamp to a crash just demotes the
        entry to write-time order, it can never corrupt the entry)."""
        try:
            with open(os.path.join(entry, LAST_HIT_NAME), "w") as f:
                f.write(f"{time.time():.3f}\n")
        except OSError:
            pass

    def _entry_fn(self, path: str) -> str:
        """The manifest's ``fn`` label (the per-function quota group);
        unreadable manifests group under ``"?"`` — they still count against
        SOME quota rather than escaping accounting."""
        try:
            with open(os.path.join(path, MANIFEST_NAME)) as f:
                fn = json.load(f).get("fn")
            return str(fn) if fn else "?"
        except (OSError, ValueError):
            return "?"

    @staticmethod
    def _dir_bytes(path: str) -> int:
        total = 0
        try:
            for n in os.listdir(path):
                try:
                    total += os.path.getsize(os.path.join(path, n))
                except OSError:
                    pass
        except OSError:
            pass
        return total

    def total_bytes(self) -> int:
        return sum(self._dir_bytes(p) for p in self.entries())

    # -- store ----------------------------------------------------------------
    def store(self, key: CacheKey, compiled: Any) -> StoreResult:
        """Serialize ``compiled`` (a ``jax.stages.Compiled``) and commit it
        under ``key`` with the staged-fsync-manifest-rename protocol."""
        t0 = time.monotonic()
        final_dir = self.entry_dir(key)
        # already-committed check BEFORE serialization: a fleet of replicas
        # missing simultaneously must not all pickle a large executable just
        # to discard it (the rename race still covers the true concurrent
        # window below)
        if os.path.isfile(os.path.join(final_dir, MANIFEST_NAME)):
            return StoreResult("raced", reason="already committed")
        try:
            from jax.experimental import serialize_executable as _se

            payload = pickle.dumps(_se.serialize(compiled), protocol=pickle.HIGHEST_PROTOCOL)
            # the devices the executable runs on (the attribute serialize()
            # itself reads): load() must hand exactly these back, or jax binds
            # the executable to every device of the backend
            device_ids = [
                int(d.id) for d in compiled._executable._unloaded_executable.device_list
            ]
        except Exception as exc:
            return StoreResult("error", reason=f"serialize: {type(exc).__name__}: {exc}")
        self._sweep_stale_staging()
        staging = f"{final_dir}.tmp-{os.getpid()}-{os.urandom(4).hex()}"
        try:
            os.makedirs(staging)
            payload_path = os.path.join(staging, PAYLOAD_NAME)
            with open(payload_path, "wb") as f:
                f.write(payload)
                f.flush()
                os.fsync(f.fileno())
            # chaos fault point: a seeded ``kill -9`` lands HERE — payload on
            # disk, manifest not yet committed; the restart must see only
            # committed entries (resilience/chaos.py, one None-check disarmed)
            _chaos_inject("compile_cache_store")
            manifest = {
                "schema": SCHEMA_VERSION,
                "key": key.identity(),
                "fn": key.fn,
                "payload": {
                    "file": PAYLOAD_NAME,
                    "bytes": len(payload),
                    "crc32": zlib.crc32(payload) & 0xFFFFFFFF,
                    "device_ids": device_ids,
                },
                "created_unix": round(time.time(), 3),
            }
            manifest_path = os.path.join(staging, MANIFEST_NAME)
            with open(manifest_path, "w") as f:
                json.dump(manifest, f, sort_keys=True)
                f.flush()
                os.fsync(f.fileno())
            _fsync_path(staging)
            try:
                os.rename(staging, final_dir)  # first writer wins
            except OSError:
                # a concurrent writer committed first — discard our staging
                shutil.rmtree(staging, ignore_errors=True)
                return StoreResult(
                    "raced", reason="concurrent writer committed first",
                    nbytes=len(payload), seconds=round(time.monotonic() - t0, 6),
                )
            _fsync_path(self.directory)
        except OSError as exc:
            shutil.rmtree(staging, ignore_errors=True)
            return StoreResult("error", reason=f"io: {exc}")
        evicted = self.evict(protect=(final_dir,))
        return StoreResult(
            "stored", nbytes=len(payload),
            seconds=round(time.monotonic() - t0, 6), evicted=evicted,
        )

    def _sweep_stale_staging(self, max_age_s: float = STALE_STAGING_AGE_S) -> "list[str]":
        """Remove orphaned ``*.tmp-*`` staging dirs older than ``max_age_s``
        (a writer killed mid-store). Never touches young staging — it may
        belong to a live writer racing us."""
        swept = []
        try:
            names = os.listdir(self.directory)
        except OSError:
            return swept
        now = time.time()
        for n in names:
            if ".tmp-" not in n:
                continue
            p = os.path.join(self.directory, n)
            if now - self._mtime(p) >= max_age_s:
                shutil.rmtree(p, ignore_errors=True)
                swept.append(p)
        return swept

    # -- load -----------------------------------------------------------------
    def load(self, key: CacheKey) -> LoadResult:
        """Validate-then-deserialize the entry for ``key``.

        NEVER raises and never returns a wrong executable: any validation or
        deserialization failure quarantines the entry and reports
        ``corrupt`` so the caller compiles fresh."""
        t0 = time.monotonic()
        entry = self.entry_dir(key)
        manifest_path = os.path.join(entry, MANIFEST_NAME)
        try:
            f = open(manifest_path, "rb")
        except OSError:
            return LoadResult("miss", reason="no committed entry")
        try:
            # shared lock: eviction (LOCK_EX | LOCK_NB) skips entries a
            # reader currently holds — a load can never lose its payload
            try:
                fcntl.flock(f.fileno(), fcntl.LOCK_SH)
            except OSError:
                pass  # exotic fs without flock: proceed unlocked
            try:
                executable, nbytes = self._validate_and_load(key, entry, f)
            except CompileCacheCorrupt as exc:
                qpath = self._quarantine(entry, str(exc))
                return LoadResult(
                    "corrupt", reason=str(exc), quarantined_to=qpath,
                    seconds=round(time.monotonic() - t0, 6),
                )
            except Exception as exc:  # unpickle/deserialize blew up
                qpath = self._quarantine(entry, f"deserialize: {type(exc).__name__}")
                return LoadResult(
                    "corrupt",
                    reason=f"deserialize: {type(exc).__name__}: {exc}",
                    quarantined_to=qpath,
                    seconds=round(time.monotonic() - t0, 6),
                )
        finally:
            f.close()  # releases the flock
        self._touch_last_hit(entry)
        return LoadResult(
            "hit", executable=executable, nbytes=nbytes,
            seconds=round(time.monotonic() - t0, 6),
        )

    def _validate_and_load(self, key: CacheKey, entry: str, manifest_file) -> "tuple[Any, int]":
        try:
            manifest = json.load(manifest_file)
        except ValueError as exc:
            raise CompileCacheCorrupt(f"manifest unparseable: {exc}")
        if not isinstance(manifest, dict) or manifest.get("schema") != SCHEMA_VERSION:
            raise CompileCacheCorrupt(
                f"manifest schema {manifest.get('schema') if isinstance(manifest, dict) else '?'}"
                f" != {SCHEMA_VERSION}"
            )
        want = key.identity()
        got = manifest.get("key")
        if not isinstance(got, dict):
            raise CompileCacheCorrupt("manifest carries no key")
        for fname, wanted in want.items():
            if got.get(fname) != wanted:
                # a swapped/tampered manifest: version, topology and
                # fingerprint mismatches all land here (an honestly different
                # environment hashes to a different entry and misses instead)
                raise CompileCacheCorrupt(
                    f"key field {fname!r} mismatch: entry has {got.get(fname)!r}, "
                    f"this process needs {wanted!r}"
                )
        spec = manifest.get("payload") or {}
        payload_path = os.path.join(entry, str(spec.get("file") or PAYLOAD_NAME))
        try:
            size = os.path.getsize(payload_path)
        except OSError:
            raise CompileCacheCorrupt("payload file missing")
        if size != spec.get("bytes"):
            raise CompileCacheCorrupt(
                f"payload truncated: {size} bytes on disk, manifest says {spec.get('bytes')}"
            )
        if _file_crc32(payload_path) != spec.get("crc32"):
            raise CompileCacheCorrupt("payload CRC32 mismatch")
        with open(payload_path, "rb") as pf:
            blob = pf.read()
        from jax.experimental import serialize_executable as _se

        import jax

        by_id = {d.id: d for d in jax.devices()}
        try:
            execution_devices = [by_id[i] for i in spec["device_ids"]]
        except (KeyError, TypeError) as exc:
            raise CompileCacheCorrupt(f"manifest names no usable devices: {exc!r}")
        executable = _se.deserialize_and_load(
            *pickle.loads(blob), execution_devices=execution_devices
        )
        return executable, size

    def _quarantine(self, entry: str, reason: str) -> Optional[str]:
        """Move a failed entry aside so the NEXT restart misses cleanly
        instead of re-validating the same poison; keeps the evidence for the
        operator. Best-effort — an unmovable entry is deleted, and a failure
        to do even that still must not break the fallback compile."""
        qdir = self.quarantine_dir()
        dest = None
        try:
            os.makedirs(qdir, exist_ok=True)
            base = os.path.basename(entry)
            dest = os.path.join(qdir, f"{base}-{os.getpid()}-{os.urandom(3).hex()}")
            os.rename(entry, dest)
            with open(os.path.join(dest, "QUARANTINE_REASON"), "w") as f:
                f.write(reason + "\n")
        except OSError:
            try:
                shutil.rmtree(entry, ignore_errors=True)
            except OSError:
                pass
            dest = None
        logger.warning(
            f"compile cache entry {os.path.basename(entry)} failed validation "
            f"({reason}); quarantined{f' to {dest}' if dest else ''} — falling "
            "back to a fresh compile"
        )
        return dest

    # -- eviction -------------------------------------------------------------
    def evict(self, max_mb: Optional[float] = None, protect: "tuple[str, ...]" = ()) -> "list[str]":
        """Delete least-recently-HIT committed entries until every function's
        share fits the per-fn quota (``fn_quota_mb`` /
        ``ACCELERATE_COMPILE_CACHE_FN_QUOTA_MB``) and the whole directory
        fits ``max_mb`` (default: the instance/env cap). No cap and no quota
        → no-op. The quota pass runs FIRST, so under directory pressure the
        function that overfilled the cache sheds its own stale entries before
        the global pass can touch anyone else's. Entries in ``protect`` and
        entries another process holds a read lock on are skipped."""
        entries = self.entries()  # least-recently-hit first
        sizes = {p: self._dir_bytes(p) for p in entries}
        evicted: "list[str]" = []

        def drop(p: str) -> bool:
            if p in protect or not self._try_evict_one(p):
                return False  # protected, or a reader holds it open
            evicted.append(p)
            return True

        quota_mb = self._fn_quota_mb()
        # no group can exceed the quota when the WHOLE directory fits it —
        # skip the per-entry manifest parses (store() calls evict after every
        # commit; a fleet-shared directory should not pay them every time)
        if quota_mb is not None and sum(sizes.values()) > int(quota_mb * 1024 * 1024):
            quota_bytes = int(quota_mb * 1024 * 1024)
            groups: "dict[str, list[str]]" = {}
            for p in entries:
                groups.setdefault(self._entry_fn(p), []).append(p)
            for group in groups.values():
                total = sum(sizes[p] for p in group)
                for p in group:  # this fn's least-recently-hit first
                    if total <= quota_bytes:
                        break
                    if drop(p):
                        total -= sizes[p]
        cap_mb = max_mb if max_mb is not None else self._cap_mb()
        if cap_mb is None:
            return evicted
        cap_bytes = int(cap_mb * 1024 * 1024)
        remaining = [p for p in entries if p not in evicted]
        total = sum(sizes[p] for p in remaining)
        for p in remaining:
            if total <= cap_bytes:
                break
            if drop(p):
                total -= sizes[p]
        return evicted

    def _cap_mb(self) -> Optional[float]:
        if self.max_mb is not None:
            return self.max_mb
        from ..utils.environment import parse_optional_float_from_env

        from .runtime import CACHE_MAX_MB_ENV_VAR

        return parse_optional_float_from_env(CACHE_MAX_MB_ENV_VAR)

    def _fn_quota_mb(self) -> Optional[float]:
        if self.fn_quota_mb is not None:
            return self.fn_quota_mb
        from ..utils.environment import parse_optional_float_from_env

        from .runtime import CACHE_FN_QUOTA_MB_ENV_VAR

        return parse_optional_float_from_env(CACHE_FN_QUOTA_MB_ENV_VAR)

    def _try_evict_one(self, entry: str) -> bool:
        manifest_path = os.path.join(entry, MANIFEST_NAME)
        try:
            f = open(manifest_path, "rb")
        except OSError:
            return False
        try:
            try:
                fcntl.flock(f.fileno(), fcntl.LOCK_EX | fcntl.LOCK_NB)
            except OSError:
                return False  # open for read somewhere — never delete it
            shutil.rmtree(entry, ignore_errors=True)
            return not os.path.exists(entry)
        finally:
            f.close()

    # -- introspection --------------------------------------------------------
    def stats(self) -> dict:
        entries = self.entries()
        qdir = self.quarantine_dir()
        try:
            quarantined = len(os.listdir(qdir))
        except OSError:
            quarantined = 0
        return {
            "dir": self.directory,
            "entries": len(entries),
            "bytes": sum(self._dir_bytes(p) for p in entries),
            "quarantined": quarantined,
        }
