"""JAX process settings: 64-bit arithmetic, host devices, compile cache.

Nothing here runs at import time; each helper is called by the entry point
that needs it.

* :func:`jax_enable_x64` — flip the global float64 flag for the whole
  process (equivalently set ``JAX_ENABLE_X64=1`` before the first jax
  import). The jax fleet engine needs no global flag: it scopes float64
  to its own entry points with ``jax.enable_x64(True)``.
* :func:`set_host_device_count` — make XLA expose ``n`` virtual CPU
  devices (``--xla_force_host_platform_device_count``) so a batched
  ``sweep()`` can shard its config axis with ``pmap``. Must run before
  jax initializes its backends; calling it later changes nothing for
  the current process (equivalently export
  ``XLA_FLAGS=--xla_force_host_platform_device_count=N``).
* :func:`use_compile_cache` — keep compiled programs in JAX's persistent
  cache: in ``$JAX_COMPILATION_CACHE_DIR`` where that is set, and
  otherwise in ``.jax_cache`` at the root of the checkout. The path is
  part of the cache's key, so it is fixed.
"""
from __future__ import annotations

import os
import re
from pathlib import Path

__all__ = ["jax_enable_x64", "set_host_device_count", "use_compile_cache"]

_CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def jax_enable_x64(enable: bool = True) -> None:
    """Globally enable (or disable) 64-bit jax arithmetic."""
    import jax

    jax.config.update("jax_enable_x64", enable)


def set_host_device_count(n: int) -> None:
    """Force XLA to expose ``n`` host (CPU) devices.

    Rewrites ``XLA_FLAGS``, replacing any existing
    ``--xla_force_host_platform_device_count`` flag. Only effective
    before the process's first jax backend initialization.
    """
    xla_flags = os.getenv("XLA_FLAGS", "")
    rest = re.sub(
        r"--xla_force_host_platform_device_count=\S+", "", xla_flags
    ).split()
    os.environ["XLA_FLAGS"] = " ".join(
        [f"--xla_force_host_platform_device_count={int(n)}", *rest]
    )


def use_compile_cache() -> str:
    """Return the persistent compilation cache's directory:
    ``$JAX_COMPILATION_CACHE_DIR``, which JAX reads itself, if set;
    otherwise point JAX at the checkout's ``.jax_cache``. Call it before
    the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(_CHECKOUT_CACHE))
    return str(_CHECKOUT_CACHE)
