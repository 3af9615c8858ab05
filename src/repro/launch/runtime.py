"""Start-up checks shared by the entry points: the device and the compile cache.

Both run from an entry point's ``main`` (never at import), before any
model is built:

- ``require_tpu`` stops a run that would silently land on the CPU.  Off a
  TPU every kernel wrapper falls back to interpret mode or its jnp
  reference, so a CPU run "works" and measures nothing; it is allowed only
  when the user asked for it with ``JAX_PLATFORMS=cpu`` (tests, CI).
- ``enable_compile_cache`` keeps JAX's persistent compilation cache where
  ``JAX_COMPILATION_CACHE_DIR`` says, or else at one fixed, git-ignored
  path in the checkout — the path is part of the cache key, so it is never
  built from a temporary name, a pid or the time.
"""
from __future__ import annotations

import os
import pathlib

import jax

REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_CACHE_DIR = REPO_ROOT / ".jax_cache"


def require_tpu(prog: str, allow_cpu: bool = True):
    """Return the first device; exit non-zero unless it is a TPU.

    ``allow_cpu`` admits an explicit ``JAX_PLATFORMS=cpu`` run; with it
    False (the chip smoke) only a TPU passes.
    """
    dev = jax.devices()[0]
    cpu_requested = os.environ.get("JAX_PLATFORMS", "").lower() == "cpu"
    if dev.platform == "tpu" or (allow_cpu and cpu_requested):
        return dev
    hint = ("; set JAX_PLATFORMS=cpu to run on the CPU on purpose"
            if allow_cpu else "")
    raise SystemExit(f"{prog}: JAX found no TPU (platform "
                     f"{dev.platform!r}, {dev.device_kind!r}){hint}")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory.

    JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself; only when it is unset
    is the cache sent to ``<checkout>/.jax_cache``.  Returns the directory.
    """
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return jax.config.jax_compilation_cache_dir
