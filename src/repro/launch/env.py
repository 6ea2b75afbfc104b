"""Allocator / XLA environment presets for the hot scan loop.

The two knobs that move the engine's wall-clock on CPU hosts are the
malloc implementation (tcmalloc beats glibc malloc under XLA's
allocation churn) and a handful of XLA flags. Both must be in the
environment *before* the process starts (``LD_PRELOAD``) or before JAX
first initializes its backends (``XLA_FLAGS``), so this module cannot
retrofit them — it is a report-and-hint layer:

- ``preset(name)`` returns the recommended variables for a named
  preset, for launcher scripts to export before exec'ing Python.
- ``apply(name)`` sets any not-yet-set recommendations into
  ``os.environ`` — only useful at the very top of a ``__main__``
  before anything imports jax; harmless but ineffective later.
- ``report(name)`` inspects the live process (environ plus
  ``/proc/self/maps`` for the actually-loaded allocator) and returns a
  JSON-able dict the run manifests embed, so a benchmark entry can be
  audited for its allocator/flag state after the fact.

``enable_compile_cache()`` is the one place that turns on JAX's
persistent compilation cache; entry points call it before their first
compile.
"""
from __future__ import annotations

import os
import pathlib

# JAX's persistent compilation cache when JAX_COMPILATION_CACHE_DIR is
# not set: a fixed directory inside the checkout (gitignored). The path
# is part of what a later run must find again, so it never moves.
COMPILE_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"

# Candidate tcmalloc locations (Debian/Ubuntu multiarch, RHEL).
_TCMALLOC_PATHS = (
    "/usr/lib/x86_64-linux-gnu/libtcmalloc.so.4",
    "/usr/lib/x86_64-linux-gnu/libtcmalloc_minimal.so.4",
    "/usr/lib64/libtcmalloc.so.4",
)

PRESETS: dict[str, dict[str, str]] = {
    # Single-process scan throughput: one host device, step markers on
    # the outer while so profiles attribute time to scan iterations,
    # and tcmalloc when the host has it.
    "throughput": {
        "LD_PRELOAD": _TCMALLOC_PATHS[0],
        "XLA_FLAGS": ("--xla_force_host_platform_device_count=1 "
                      "--xla_step_marker_location=1"),
    },
    # Host-parallel sweeps (launch.mesh shards scenarios over host
    # devices): many virtual CPU devices, allocator as above.
    "sweep": {
        "LD_PRELOAD": _TCMALLOC_PATHS[0],
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
    },
}


def preset(name: str = "throughput") -> dict[str, str]:
    """Recommended environment for ``name`` (KeyError on unknown)."""
    if name not in PRESETS:
        raise KeyError(f"unknown env preset {name!r}; "
                       f"have {sorted(PRESETS)}")
    return dict(PRESETS[name])


def apply(name: str = "throughput") -> dict[str, str]:
    """Set not-yet-set recommendations into ``os.environ``; returns the
    variables actually written. Must run before jax import to have any
    effect (``LD_PRELOAD`` needs a process restart regardless)."""
    written = {}
    for key, val in preset(name).items():
        if not os.environ.get(key):
            os.environ[key] = val
            written[key] = val
    return written


def _loaded_allocator() -> str:
    """Which malloc is actually mapped: "tcmalloc" | "jemalloc" |
    "glibc" | "unknown" (non-Linux)."""
    try:
        with open("/proc/self/maps") as f:
            maps = f.read()
    except OSError:
        return "unknown"
    if "tcmalloc" in maps:
        return "tcmalloc"
    if "jemalloc" in maps:
        return "jemalloc"
    return "glibc"


def report(name: str = "throughput") -> dict:
    """JSON-able audit of the live process against ``name``: the
    recommendation, what is actually set/loaded, and whether they
    agree. Embedded under ``env_preset`` in run manifests."""
    want = preset(name)
    active = {key: os.environ.get(key) for key in
              ("LD_PRELOAD", "XLA_FLAGS", "XLA_PYTHON_CLIENT_PREALLOCATE",
               "JAX_PLATFORMS", "OMP_NUM_THREADS")}
    allocator = _loaded_allocator()
    want_flags = set(want.get("XLA_FLAGS", "").split())
    have_flags = set((active.get("XLA_FLAGS") or "").split())
    return {
        "preset": name,
        "recommended": want,
        "active": {k: v for k, v in active.items() if v},
        "allocator": allocator,
        "satisfied": (allocator == "tcmalloc"
                      and want_flags <= have_flags),
    }


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads it and
    nothing is changed. Otherwise the cache goes to ``COMPILE_CACHE_DIR``.
    Call it from an entry point before the first compile, never while a
    module is being imported."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return str(COMPILE_CACHE_DIR)
