"""Host-platform helpers: CPU pinning, the persistent compilation
cache, and the f64 protocol.

CPU runs (tests, dry runs) are asked for explicitly with
`JAX_PLATFORMS=cpu`; nothing here falls back to the CPU on its own.
"""

from __future__ import annotations

import os

# the checkout this package runs from (…/mpisppy_tpu/utils/platform.py)
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def cpu_requested():
    """True when JAX_PLATFORMS asks for exactly the CPU.  A list that
    merely includes it is not a CPU request: the TPU machine sets
    JAX_PLATFORMS=tpu,cpu, and read as a CPU request that pinned a
    process serve worker there to the CPU (chip run, PR 21)."""
    return os.environ.get("JAX_PLATFORMS", "").strip() == "cpu"


def ensure_cpu_backend(force=False):
    """Pin jax to the CPU backend when JAX_PLATFORMS=cpu asks for it
    (or force=True).  Must run before the first backend
    initialization."""
    if not force and not cpu_requested():
        return
    import jax
    jax.config.update("jax_platforms", "cpu")


def compile_cache_root():
    """Root of every on-disk compile artifact: JAX_COMPILATION_CACHE_DIR
    when set, else the fixed `<checkout>/.jax_cache` (a fixed path: the
    path is part of the cache's key, so a moving directory never
    hits).  The serve layer's AOT executables live in its `aot/`
    subdirectory (serve/compile_cache.aot_cache_dir)."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(_CHECKOUT, ".jax_cache"))


def enable_compile_cache():
    """Turn on jax's persistent compilation cache at
    `compile_cache_root()`, on every backend, so a second process with
    the same shapes compiles nothing (measured on CPU: repeat sizes-3
    MIP runs drop 80.8 s -> 49.3 s).  Every compile is cached, however
    short.  Returns the cache dir."""
    import jax

    path = compile_cache_root()
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def enable_f64_if_cpu():
    """The project-wide precision protocol: device=cpu always means
    f64 (certified-eps paths — MIP diving at 1e-6, golden drives — are
    not reliable in f32; f32 is the accelerator's trade, not the
    host's).  Gates on the ACTUAL backend, so it initializes jax.
    Returns True when the backend is CPU (callers branch on it for
    CPU-vs-accelerator run protocol)."""
    import jax

    on_cpu = jax.devices()[0].platform == "cpu"
    if on_cpu:
        jax.config.update("jax_enable_x64", True)
    return on_cpu
