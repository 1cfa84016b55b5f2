"""Where the persistent XLA compilation cache lives.

One rule for every process that builds an engine (the server,
benchmark/run.py, chip_smoke.py, the tools): when ``JAX_COMPILATION_CACHE_DIR`` is set —
the manifests mount it on the model PVC — JAX reads it itself and this
module sets nothing; otherwise the cache goes to ONE fixed directory
inside the checkout.  The directory is part of what a cache entry is
found by, so a path that moves (a temp dir, a pid, a platform suffix)
never hits.

What an entry is found by also holds each operation's NAME (its
``jax.named_scope`` path, ``tpuserve/ops/scopes.py``) and not its source
line, in either case.  By default JAX leaves all metadata out of the key,
so a program compiled before a scope existed, or under its old name, is
found again and carries the old ``op_name``s into the trace, where the
scopes are what device time is read by; with the metadata in the key as
JAX writes it, the key would also hold file and line of every frame above
each operation, and an edit that moves a line would compile every program
of a cell again.  So the operations' locations carry no Python frames at
all: a renamed or added scope compiles again, a moved line does not
(tests/test_compile_cache.py).  The price: HLO dumps and profiles name an
operation by its scope path alone, with no ``source`` beside it.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")
# compiles quicker than this are not worth a file each
MIN_COMPILE_SECS = 1.0


def configure() -> str:
    """Place the persistent compile cache and return its directory."""
    import jax
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_traceback_in_locations_limit", 0)
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      MIN_COMPILE_SECS)
    return CHECKOUT_CACHE_DIR


def entries(cache_dir: str) -> int:
    """Number of cache entries on disk (0 for a directory not made yet)."""
    try:
        return len(os.listdir(cache_dir))
    except FileNotFoundError:
        return 0
