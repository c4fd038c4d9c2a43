"""Centralized XLA/JAX performance-environment knobs (`repro.env`).

The knobs that decide what a wall-clock measurement *means* — float
width, target platform, virtual host-device count — were historically
scattered across CI yaml, test shims and launcher docstrings as raw
``JAX_PLATFORMS`` / ``JAX_ENABLE_X64`` / ``XLA_FLAGS`` strings. This
module is the one place that sets them, and the benchmark/tuning entry
points go through :func:`pin_for_benchmarks` so every recorded number
(BENCH_gnn.json rows, autotuned winners) was taken under a *pinned,
describable* environment.

Ordering matters: ``XLA_FLAGS``/``JAX_PLATFORMS`` only take effect
before jax initializes its backends, so the setters mutate ``os.environ``
and warn (rather than silently no-op) once jax is imported. Always call
these at the top of a ``main()``, before the first repro/jax import does
real work.

:func:`enable_compile_cache` is the one place the persistent compilation
cache is turned on (entry points only, never tests).
"""
from __future__ import annotations

import os
import pathlib
import re
import sys
import warnings

_HOST_DEV_FLAG = "--xla_force_host_platform_device_count"
# src/repro/env.py -> the checkout root
_CHECKOUT_ROOT = pathlib.Path(__file__).resolve().parents[2]


def _jax_initialized() -> bool:
    """True once jax is imported. jax reads ``JAX_PLATFORMS`` at import
    and ``XLA_FLAGS`` when its backends start, and offers no public way
    to ask whether they have started — so from import on, an environment
    change may already be too late."""
    return sys.modules.get("jax") is not None


def _warn_if_late(knob: str) -> None:
    if _jax_initialized():
        warnings.warn(
            f"repro.env: {knob} set after jax initialized its backends — "
            f"it will not take effect in this process", RuntimeWarning,
            stacklevel=3)


def set_platform(platform: str) -> None:
    """Pin the jax platform ("cpu" / "gpu" / "tpu") via ``JAX_PLATFORMS``."""
    _warn_if_late("platform")
    os.environ["JAX_PLATFORMS"] = platform


def set_host_device_count(n: int) -> None:
    """Expose ``n`` virtual host devices (CPU mesh testing), merging into
    any existing ``XLA_FLAGS`` instead of clobbering them."""
    _warn_if_late("host device count")
    flags = os.environ.get("XLA_FLAGS", "")
    flags = re.sub(rf"{_HOST_DEV_FLAG}=\d+\s*", "", flags).strip()
    os.environ["XLA_FLAGS"] = f"{flags} {_HOST_DEV_FLAG}={n}".strip()


def enable_x64(on: bool = True) -> None:
    """Toggle 64-bit jax arrays (works before or after jax import)."""
    os.environ["JAX_ENABLE_X64"] = "1" if on else "0"
    if sys.modules.get("jax") is not None:
        import jax
        jax.config.update("jax_enable_x64", bool(on))


def configure(*, platform: str | None = None, x64: bool | None = None,
              host_devices: int | None = None) -> None:
    """Apply any subset of the knobs in the right order."""
    if host_devices is not None:
        set_host_device_count(host_devices)
    if platform is not None:
        set_platform(platform)
    if x64 is not None:
        enable_x64(x64)


def pin_for_benchmarks(*, platform: str | None = None) -> dict:
    """The pinned measurement environment for benchmarks and tuning runs.

    Pins ``platform`` when one is given — otherwise jax picks the
    accelerator it finds, and the record names it — plus 32-bit arrays
    (the kernels' dtype), then returns :func:`describe` for embedding
    into the result record.
    """
    configure(platform=platform, x64=False)
    return describe()


def describe() -> dict:
    """Snapshot of the execution environment a measurement ran under
    (recorded alongside benchmark rows and autotuned winners)."""
    import jax
    return {
        "jax_version": jax.__version__,
        "jax_platform": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "device_count": jax.device_count(),
        "x64": bool(jax.config.read("jax_enable_x64")),
        "xla_flags": os.environ.get("XLA_FLAGS", ""),
    }


def enable_compile_cache() -> str:
    """Turn on jax's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when exported, is the cache (jax reads
    it itself) and no other directory is set. Otherwise the cache lives
    at ``.jax_cache/`` in the checkout root: a fixed path, because the
    path is part of what a later run must find again. Entry points call
    this at the top of ``main()``; tests never do.
    """
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(_CHECKOUT_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
