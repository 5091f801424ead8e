"""Runtime environment shared by the launcher, the benchmarks and
``chip_smoke.py``.

Two cold-start levers live here so every entry point pulls the same ones:

  * **Host-platform mesh flags** — ``--xla_force_host_platform_device_count``
    turns one CPU into an N-device mesh (how CI exercises dp8 sharding).
    ``host_device_flags``/``apply_host_devices`` compose the flag into
    ``XLA_FLAGS`` without clobbering whatever the caller already set.
  * **Persistent compilation cache** — ``enable_compilation_cache`` turns
    JAX's disk cache on with the thresholds zeroed, so a process restart
    re-warms the engine's whole bucket ladder from disk (``EsamPlan.warmup``
    + this cache is what makes a restart cheap).  The cache lives where
    ``JAX_COMPILATION_CACHE_DIR`` says when it is set — nothing here
    overrides it — and otherwise at the fixed ``<repo>/.jax_cache``
    (git-ignored): the directory is part of the cache key, so it never
    moves between runs.

Nothing here imports JAX at module load — ``apply_host_devices`` must be able
to run before the backend initializes.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

#: the cache directory when ``JAX_COMPILATION_CACHE_DIR`` is unset: fixed,
#: inside the checkout (src/repro/launch/env.py -> the repository root)
REPO_CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")

HOST_DEVICE_FLAG = "--xla_force_host_platform_device_count"


def host_device_flags(n_devices: int, base: Optional[str] = None) -> str:
    """``XLA_FLAGS`` value forcing an ``n_devices`` host-platform mesh,
    composed with ``base`` (default: the current env var) minus any previous
    setting of the same flag."""
    base = os.environ.get("XLA_FLAGS", "") if base is None else base
    kept = [f for f in base.split() if not f.startswith(HOST_DEVICE_FLAG)]
    kept.append(f"{HOST_DEVICE_FLAG}={int(n_devices)}")
    return " ".join(kept)


def apply_host_devices(n_devices: int) -> None:
    """Set ``XLA_FLAGS`` for an ``n_devices`` host mesh, in-process.

    Must run before the JAX backend initializes (before the first
    ``jax.devices()`` / computation — importing ``jax`` alone is fine).
    Initializes the backend and raises if it does not hold ``n_devices``
    devices — an earlier initialization means the flag silently did not
    apply.
    """
    os.environ["XLA_FLAGS"] = host_device_flags(n_devices)
    import jax

    if len(jax.devices()) != int(n_devices):
        raise RuntimeError(
            f"JAX backend holds {len(jax.devices())} devices, not "
            f"{int(n_devices)}; {HOST_DEVICE_FLAG} can no longer apply — "
            f"set XLA_FLAGS before first device use")


def compilation_cache_dir() -> str:
    """Where the persistent compilation cache lives: the directory
    ``JAX_COMPILATION_CACHE_DIR`` names, else ``REPO_CACHE_DIR``."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or REPO_CACHE_DIR


def enable_compilation_cache() -> str:
    """Turn JAX's persistent compilation cache on, with the size/time
    thresholds zeroed so every executable — including the engine's small
    bucket plans — persists.  JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself
    when it is set; only when it is not is the directory set here, to
    ``REPO_CACHE_DIR``.  Returns the directory used.  Safe to call
    repeatedly."""
    import jax

    d = compilation_cache_dir()
    os.makedirs(d, exist_ok=True)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_enable_xla_caches", "all")
    return d
