"""Device selection and compile-cache placement, shared by every entry point.

One rule everywhere: the process runs on the accelerator JAX finds. It
runs on the CPU only when the caller asked for that with
``JAX_PLATFORMS=cpu``; any other boot that finds no accelerator exits
non-zero with one clear line. Nothing here probes from a subprocess,
retries, or re-pins the platform — a chip belongs to one process, and a
result produced on the CPU must never carry a device's name.
"""

from __future__ import annotations

import os

# <checkout>/.jax_cache, resolved from this file's own location so every
# process of one checkout agrees on it (the path is part of the cache
# key — a directory that moves never hits). Git-ignored.
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
REPO_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def cpu_requested() -> bool:
    """Whether the caller pinned the CPU platform (``JAX_PLATFORMS=cpu``,
    which JAX binds to ``jax_platforms`` at import)."""
    import jax

    return (jax.config.jax_platforms or "").strip().lower() == "cpu"


def require_device() -> str:
    """The backend this process will use: an accelerator, or the CPU the
    caller asked for. Anything else is a non-zero exit."""
    import jax

    try:
        backend = jax.default_backend()
    except RuntimeError as exc:
        raise SystemExit(f"no accelerator: JAX could not initialise "
                         f"its backend ({exc})") from exc
    if backend == "cpu" and not cpu_requested():
        raise SystemExit(
            "no accelerator: JAX found only the CPU and JAX_PLATFORMS=cpu "
            "was not set. Run on a TPU host, or set JAX_PLATFORMS=cpu to "
            "ask for a CPU run explicitly.")
    return backend


def device_label() -> dict:
    """The device as JAX reports it — stamped on every result line."""
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def enable_persistent_compile_cache() -> str | None:
    """Persist XLA executables across boots of this checkout.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX has already bound the cache to
    exactly that directory — it is left alone. Unset on an accelerator:
    the one fixed in-checkout directory. Unset on the CPU: no cache (CPU
    programs compile in well under a second). Must run before the first
    compile — JAX decides once per process whether the cache is in use.
    Returns the directory in effect (None = no cache)."""
    import jax

    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        cache_dir = jax.config.jax_compilation_cache_dir
    elif jax.default_backend() == "cpu":
        return None
    else:
        cache_dir = REPO_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # Cache every program, not only those over JAX's 1 s default: a boot
    # compiles ~150 programs and on a v5e all but 8 take under a second
    # each, yet together they are most of the compile time (PERF.md,
    # PR 21). The operator's own setting wins.
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir
