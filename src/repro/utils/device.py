"""The device a process measures on, and where its compiled programs
are kept.

JAX is imported inside the functions: importing this module touches no
device state.
"""

from __future__ import annotations

import os
import pathlib

__all__ = ["device_kind", "enable_compile_cache", "COMPILE_CACHE_DIR"]

#: the persistent compilation cache of this checkout: a fixed path, since
#: the path is part of the cache's key and a moving directory never hits
COMPILE_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def device_kind() -> str:
    """``device_kind`` of JAX's first device (``"cpu"``,
    ``"TPU v5 lite"``, ...).  It is part of every key under which a
    measurement or a compiled program is kept, so what one device made
    never answers on another."""
    import jax

    return jax.devices()[0].device_kind


def enable_compile_cache() -> None:
    """Keep JAX's persistent compilation cache in ``COMPILE_CACHE_DIR``.

    Sets nothing where ``JAX_COMPILATION_CACHE_DIR`` names a directory
    (JAX uses that one) or where the cache is switched off
    (``JAX_ENABLE_COMPILATION_CACHE=false``, as the tests run)."""
    import jax

    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    if not jax.config.jax_enable_compilation_cache:
        return
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
