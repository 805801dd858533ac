"""Shared benchmark utilities: timing, CSV emission, result persistence,
and the host launch preset (tcmalloc + forced host device count) that
``scripts/launch.sh`` applies — importable so benchmarks can detect /
apply it programmatically too."""

from __future__ import annotations

import json
import os
import time

RESULT_DIR = os.path.join(os.path.dirname(__file__), "..", "experiments",
                          "bench")

# common install locations of gperftools' tcmalloc (Snippet-style
# LD_PRELOAD: malloc-heavy host staging — packing ELL metadata, padding,
# pytree stacking — measurably benefits from a thread-caching allocator)
TCMALLOC_CANDIDATES = (
    "/usr/lib/x86_64-linux-gnu/libtcmalloc.so.4",
    "/usr/lib/x86_64-linux-gnu/libtcmalloc_minimal.so.4",
    "/usr/lib/libtcmalloc.so.4",
)


def find_tcmalloc() -> str | None:
    """First present tcmalloc shared object, or None.  Used by
    ``scripts/launch.sh`` (via ``python -m benchmarks.common``) so the
    preset degrades to plain malloc on hosts without gperftools."""
    for cand in TCMALLOC_CANDIDATES:
        if os.path.exists(cand):
            return cand
    return None


def configure_host_devices(n: int | None = None) -> int:
    """Set ``--xla_force_host_platform_device_count=N`` (HomebrewNLP-style)
    BEFORE jax initialises, so the shard_map/pmap map backends see N host
    devices on a many-core CPU box instead of one.  Must run before the
    first ``import jax`` in the process; returns the device count used.
    No-op (returns the current setting) when the flag is already present —
    respects an outer ``scripts/launch.sh`` environment."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" in flags:
        for tok in flags.split():
            if "xla_force_host_platform_device_count" in tok:
                return int(tok.split("=")[1])
    if n is None:
        n = os.cpu_count() or 1
    os.environ["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count={n}".strip())
    return n


# JAX's persistent compilation cache, where JAX_COMPILATION_CACHE_DIR does
# not name one: a fixed path in the checkout (git-ignored), since the path
# is part of what the cache is keyed on
COMPILE_CACHE_DIR = os.path.normpath(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                 ".jax_cache"))


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point and
    return its directory.  JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself,
    so where it is set nothing is set here; otherwise the cache goes to
    :data:`COMPILE_CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR


if __name__ == "__main__":       # scripts/launch.sh queries the preset
    print(find_tcmalloc() or "")


def emit(name: str, us_per_call: float, derived: str = ""):
    """Scaffold contract: ``name,us_per_call,derived`` CSV on stdout."""
    print(f"{name},{us_per_call:.1f},{derived}", flush=True)


def save_json(name: str, payload: dict):
    os.makedirs(RESULT_DIR, exist_ok=True)
    with open(os.path.join(RESULT_DIR, f"{name}.json"), "w") as f:
        json.dump(payload, f, indent=1, default=str)


class Timer:
    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *a):
        self.seconds = time.perf_counter() - self.t0
