"""Where this checkout keeps JAX's persistent compilation cache.

A cold process on an accelerator spends most of a short run compiling:
the vmapped local fits, the federated round loop and the serving step
are each one large program. The persistent cache lets the next process
that compiles the same program at the same shapes load it instead.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: The fixed in-checkout cache directory used when the environment names
#: none. A fixed path matters: a directory that moves between runs (a
#: temporary name, a pid, a timestamp) never hits.
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it at
    start-up and that directory is used as it is; otherwise the cache goes
    to :data:`CHECKOUT_CACHE_DIR`. Call before the first compile."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
