"""Lazy native-library build: compile ``src/*.cc`` with g++ on first use and
cache the .so (git-ignored) next to the package, keyed on a content hash of
the sources. Returns None when no toolchain is available: callers then use
the pure-Python path, and ``native.is_native_available()`` says which is live."""

from __future__ import annotations

import hashlib
import os
import subprocess
import tempfile
import threading

_SRC_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
_LIB_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "_lib")
_LIB = os.path.join(_LIB_DIR, "libatpu_pipeline.so")
_STAMP = _LIB + ".sha256"  # digest of the sources the .so was built from
_lock = threading.Lock()


def _sources() -> list[str]:
    return sorted(
        os.path.join(_SRC_DIR, name)
        for name in os.listdir(_SRC_DIR)
        if name.endswith(".cc")
    )


_FLAGS = ("-O3", "-std=c++17", "-shared", "-fPIC", "-pthread")


def _source_digest() -> str:
    """sha256 over the sources' names and contents and the compile flags: what
    the library was built FROM. The rebuild is keyed on this, not on mtimes —
    a copied or freshly checked-out tree has none worth trusting."""
    h = hashlib.sha256(" ".join(_FLAGS).encode())
    for src in _sources():
        h.update(os.path.basename(src).encode() + b"\0")
        with open(src, "rb") as f:
            h.update(f.read())
    return h.hexdigest()


def _needs_build(digest: str) -> bool:
    try:
        with open(_STAMP) as f:
            return not os.path.isfile(_LIB) or f.read().strip() != digest
    except OSError:
        return True


def build_library(verbose: bool = False) -> str | None:
    """Return the path to the compiled library, building it if stale. None if
    the build fails (no compiler, sandboxed, …)."""
    with _lock:
        digest = _source_digest()
        if not _needs_build(digest):
            return _LIB
        try:
            os.makedirs(_LIB_DIR, exist_ok=True)
            # build to a temp name then rename: concurrent importers never see
            # a half-written .so
            fd, tmp = tempfile.mkstemp(suffix=".so", dir=_LIB_DIR)
            os.close(fd)
        except OSError as e:  # read-only install → silent numpy fallback
            if verbose:
                print(f"native build unavailable: {e}")
            return None
        cmd = [
            os.environ.get("CXX", "g++"), *_FLAGS, *_sources(), "-o", tmp,
        ]
        try:
            res = subprocess.run(cmd, capture_output=True, text=True, timeout=300)
            if res.returncode != 0:
                if verbose:
                    print(f"native build failed:\n{res.stderr}")
                os.unlink(tmp)
                return None
            os.replace(tmp, _LIB)
            with open(_STAMP + ".tmp", "w") as f:
                f.write(digest + "\n")
            os.replace(_STAMP + ".tmp", _STAMP)
            return _LIB
        except (OSError, subprocess.SubprocessError) as e:
            if verbose:
                print(f"native build failed: {e}")
            try:
                os.unlink(tmp)
            except OSError:
                pass
            return None
