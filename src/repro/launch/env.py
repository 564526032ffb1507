"""Process/env hygiene applied BEFORE importing jax.

The run.sh idiom from the exemplar repos (SNIPPETS.md), as a callable:
tcmalloc preload note, XLA flags, allocator-warning thresholds and the
x64 policy all must be in the environment before ``import jax`` — after
that, XLA has read its flags and the dtype default is frozen. This
module therefore imports NOTHING heavy (no jax, no numpy) and is safe to
import first in any entrypoint:

    from repro.launch.env import apply_env
    apply_env(devices=8)          # BEFORE any jax import
    import jax                    # sees 8 virtual CPU devices

``apply_env`` is import-order safe and idempotent: it is a silent no-op
for every variable already set (an operator's explicit environment always
wins — CI sets XLA_FLAGS itself), and a no-op with a warning when jax was
imported first (setting the vars then would silently do nothing, which is
worse than saying so). ``launch/serve.py`` and ``launch/solve.py`` call
it on startup.

LD_PRELOAD (tcmalloc) cannot take effect from inside a running process —
:func:`tcmalloc_note` returns the export line to put in a wrapper script
when a system tcmalloc exists and none is preloaded.

:func:`enable_compile_cache` turns on JAX's persistent compilation cache
for the entry points (``chip_smoke.py``, ``launch/solve.py``,
``launch/serve.py``, ``benchmarks/run.py``) before they compile anything.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and nothing is
overridden; otherwise the cache lives at one fixed path in the checkout,
``<repo>/.jax_cache`` (the path is part of the cache key, so it never
moves). Git ignores that directory.
"""
from __future__ import annotations

import os
import sys
import warnings
from pathlib import Path
from typing import Dict, Mapping, Optional, Sequence

__all__ = [
    "apply_env",
    "compile_cache_dir",
    "enable_compile_cache",
    "tcmalloc_note",
    "DEFAULT_ENV",
    "TCMALLOC_PATHS",
]

# <repo>/src/repro/launch/env.py -> <repo>/.jax_cache
_REPO_CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")

# vars applied when (and only when) absent — the SNIPPETS run.sh set
DEFAULT_ENV: Dict[str, str] = {
    # silence tcmalloc's large-alloc warnings for matrix-sized buffers
    "TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD": "60000000000",
    # keep TF/XLA C++ chatter out of serving logs
    "TF_CPP_MIN_LOG_LEVEL": "2",
}

TCMALLOC_PATHS = (
    "/usr/lib/x86_64-linux-gnu/libtcmalloc.so.4",
    "/usr/lib/libtcmalloc.so.4",
)


def tcmalloc_note(env: Mapping[str, str] = os.environ) -> Optional[str]:
    """The LD_PRELOAD line a launcher script should add, or None.

    Returns the export line when a system tcmalloc exists and nothing is
    preloaded yet; preloading must happen before process start, so this
    is advisory — print it, don't set it.
    """
    if env.get("LD_PRELOAD"):
        return None
    for path in TCMALLOC_PATHS:
        if os.path.exists(path):
            return f"export LD_PRELOAD={path}  # faster malloc (set before launch)"
    return None


def apply_env(
    devices: Optional[int] = None,
    x64: Optional[bool] = None,
    extra_xla_flags: Sequence[str] = (),
    env: Optional[Dict[str, str]] = None,
) -> Dict[str, str]:
    """Set the pre-jax environment; returns {var: value} actually set.

    * ``devices`` — virtual host-platform device count
      (``--xla_force_host_platform_device_count``), the CPU idiom for
      exercising shard_map meshes.
    * ``x64`` — the precision policy: sets ``JAX_ENABLE_X64`` (the
      solvers are f32-first; residual replacement is the accuracy net).
    * ``extra_xla_flags`` — appended to ``XLA_FLAGS`` unless the same
      flag is already present.

    Every variable already present in ``env`` is left untouched (no-op),
    and a flag already in ``XLA_FLAGS`` is never duplicated or
    overridden. If jax is already imported (and ``env`` is the real
    ``os.environ``), nothing is set and a warning explains why.
    """
    real = env is None
    if env is None:
        env = os.environ  # type: ignore[assignment]
    if real and "jax" in sys.modules:
        warnings.warn(
            "repro.launch.env.apply_env() called after jax was imported: "
            "XLA flags and the x64 policy are already frozen, so nothing "
            "was changed. Call apply_env() before the first jax import.",
            stacklevel=2,
        )
        return {}

    applied: Dict[str, str] = {}
    for k, v in DEFAULT_ENV.items():
        if k not in env:
            env[k] = v
            applied[k] = v
    if x64 is not None and "JAX_ENABLE_X64" not in env:
        env["JAX_ENABLE_X64"] = "1" if x64 else "0"
        applied["JAX_ENABLE_X64"] = env["JAX_ENABLE_X64"]

    current = env.get("XLA_FLAGS", "")
    new_flags = []
    if devices is not None and "--xla_force_host_platform_device_count" not in current:
        new_flags.append(f"--xla_force_host_platform_device_count={int(devices)}")
    for flag in extra_xla_flags:
        if flag.split("=", 1)[0] not in current:
            new_flags.append(flag)
    if new_flags:
        env["XLA_FLAGS"] = " ".join(([current] if current else []) + new_flags)
        applied["XLA_FLAGS"] = env["XLA_FLAGS"]
    return applied


def compile_cache_dir(env: Mapping[str, str] = os.environ) -> str:
    """Where the persistent compilation cache lives: the operator's
    ``JAX_COMPILATION_CACHE_DIR`` when set, else ``<repo>/.jax_cache``."""
    return env.get("JAX_COMPILATION_CACHE_DIR") or _REPO_CACHE_DIR


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Sets JAX's cache directory only when ``JAX_COMPILATION_CACHE_DIR`` is
    unset (JAX reads that variable itself). Call before compiling.
    """
    import jax

    path = compile_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
