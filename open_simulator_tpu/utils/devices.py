"""Backend/device plumbing shared by tests, bench, and the multichip dry-run.

The virtual-CPU device count flag is only read at the CPU backend's lazy
initialization, and a config-route platform pin must land before the first
device use. This module is the one place that handles both, and places the
persistent compilation cache.
"""

from __future__ import annotations

import os
import re

_FLAG = "--xla_force_host_platform_device_count"


def request_cpu_devices(n: int) -> None:
    """Raise the virtual CPU device count to ≥ n via XLA_FLAGS. Must run before the
    CPU backend's lazy initialization; harmless (but ineffective) afterwards."""
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(re.escape(_FLAG) + r"=(\d+)", flags)
    cur = int(m.group(1)) if m else 0
    if cur < n:
        flags = re.sub(re.escape(_FLAG) + r"=\d+", "", flags).strip()
        os.environ["XLA_FLAGS"] = (flags + f" {_FLAG}={n}").strip()


def force_cpu_platform() -> None:
    """Make CPU the default JAX platform regardless of injected plugin priority.
    Silently a no-op when a backend is already initialized."""
    import jax

    try:
        if not str(jax.config.jax_platforms or "").startswith("cpu"):
            jax.config.update("jax_platforms", "cpu")
    # simonlint: ignore[swallowed-exception] -- documented no-op when a
    # backend already initialized; the caller proceeds on whatever platform
    except Exception:
        pass


# The checkout this package lives in: a fixed path, so the next run in this
# checkout finds the cache entries again.
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")  # listed in .gitignore

_cache_enabled = False


def enable_compilation_cache() -> None:
    """Turn on JAX's persistent compilation cache (idempotent). The engine's
    kernels take seconds to compile; every fresh process (each CLI run,
    server or sweep) would otherwise pay that again.

    Where JAX_COMPILATION_CACHE_DIR is set, JAX reads it itself and this sets
    no directory of its own. Otherwise the cache goes to <checkout>/.jax_cache."""
    global _cache_enabled
    if _cache_enabled:
        return
    _cache_enabled = True
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)


def cpu_devices(n: int):
    """Best-effort list of ≥ n devices, preferring the default platform and falling
    back to virtual CPU devices. May return fewer if the CPU backend already
    initialized with a smaller count."""
    request_cpu_devices(n)
    import jax

    devs = jax.devices()
    if len(devs) < n:
        devs = jax.devices("cpu")
    return devs
