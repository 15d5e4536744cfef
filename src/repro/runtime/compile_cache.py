"""Where JAX keeps its persistent compilation cache.

A cache entry's key includes nothing about the directory, but a cache is
only found again at the path it was written to, so the path has to be
fixed.  ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself
and wins: this module then sets no directory.  Otherwise the cache lives
in ``.jax_cache/`` at the root of the checkout (listed in ``.gitignore``).

Entries are keyed with the program's metadata (its ``jax.named_scope``
names and source lines).  JAX leaves that out of the key by default, and
then loads a program compiled before a scope was added or renamed with
its old op names: a profile of it would put the device time under names
the program no longer has.

Call ``use_compile_cache()`` from a launcher's ``main()`` — never at
import, so importing the library changes no global JAX state.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Enable the persistent cache; returns the directory in use."""
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
