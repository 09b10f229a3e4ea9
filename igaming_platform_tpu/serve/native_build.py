"""Build ``native/lib/*.so`` on this host, from the current sources, once.

The libraries are compiled with ``-march=native`` and git-ignored, and a
checkout can arrive by copy with binaries another CPU built (their
mtimes say nothing then). So a library is loaded only when the stamp
beside it records THIS host's CPU and THESE sources; on any mismatch
both libraries are rebuilt. The build lands in a scratch directory and
is renamed into place, so a concurrent process loads either the old
complete file or the new one, never a half-written one.
"""

from __future__ import annotations

import hashlib
import logging
import os
import platform
import shutil
import subprocess
import tempfile
import threading

NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "native")
LIB_DIR = os.path.join(NATIVE_DIR, "lib")
_SOURCES = ("build.sh", "feature_store.cpp", "wire_codec.cpp")
_LIBS = ("libfeature_store.so", "libwire_codec.so")
_STAMP = os.path.join(LIB_DIR, "build.stamp")

_lock = threading.Lock()
logger = logging.getLogger(__name__)


def _host_cpu() -> str:
    """What ``-march=native`` compiled for: ISA, model and feature flags
    of this host's CPU."""
    bits = [platform.machine()]
    try:
        with open("/proc/cpuinfo", encoding="utf-8", errors="replace") as f:
            seen = set()
            for line in f:
                key = line.split(":", 1)[0].strip().lower()
                if key in ("model name", "flags", "features") and key not in seen:
                    seen.add(key)
                    bits.append(line.split(":", 1)[1].strip())
    except OSError:  # noqa: CC04 — no /proc/cpuinfo (not Linux): the coarser processor string keys the stamp instead
        bits.append(platform.processor() or "unknown-cpu")
    return "|".join(bits)


def _fingerprint() -> str | None:
    """Digest of the sources + this host's CPU; None when the sources
    are not there to build from."""
    h = hashlib.sha256(_host_cpu().encode())
    for name in _SOURCES:
        try:
            with open(os.path.join(NATIVE_DIR, name), "rb") as f:
                h.update(f.read())
        except OSError:  # noqa: CC04 — sources absent is an answer (None), reported by ensure_built's callers
            return None
    return h.hexdigest()


def _stamp_matches(want: str) -> bool:
    try:
        with open(_STAMP, encoding="ascii") as f:
            stamped = f.read().strip()
    except OSError:  # noqa: CC04 — no stamp simply means "not built here yet"
        return False
    return stamped == want and all(
        os.path.exists(os.path.join(LIB_DIR, lib)) for lib in _LIBS)


def ensure_built(force: bool = False) -> str | None:
    """Directory holding libraries built on this host from the current
    sources — building them now if the stamp says otherwise (or
    ``force``). None when they cannot be built (no sources, no g++, a
    compile error): callers decide whether that is fatal."""
    with _lock:
        want = _fingerprint()
        if want is None:
            return None
        if not force and _stamp_matches(want):
            return LIB_DIR
        os.makedirs(LIB_DIR, exist_ok=True)
        scratch = tempfile.mkdtemp(prefix=".build-", dir=LIB_DIR)
        try:
            subprocess.run(
                ["sh", os.path.join(NATIVE_DIR, "build.sh"), scratch],
                check=True, capture_output=True, timeout=300)
            for lib in _LIBS:
                os.replace(os.path.join(scratch, lib),
                           os.path.join(LIB_DIR, lib))
            with open(os.path.join(scratch, "stamp"), "w",
                      encoding="ascii") as f:
                f.write(want + "\n")
            os.replace(os.path.join(scratch, "stamp"), _STAMP)
        except (subprocess.CalledProcessError, subprocess.TimeoutExpired,
                OSError) as exc:
            detail = getattr(exc, "stderr", b"") or b""
            logger.warning("native build failed: %s %s", exc,
                           detail.decode("utf-8", "replace")[-400:],
                           exc_info=True)
            return None
        finally:
            shutil.rmtree(scratch, ignore_errors=True)
        return LIB_DIR
