"""JAX's persistent compilation cache, placeable from outside.

Every run on a fresh machine starts with no compiled code and the GPT-2
step alone takes about half a minute to compile, so the entry-point
scripts (``chip_smoke.py``, the benchmark's cells, the examples, the
profiling tools) turn the cache on before their first compile. Tests do not.
"""

from __future__ import annotations

import os

import jax

# <checkout>/.jax_cache. Fixed on purpose: the directory is part of the
# cache key, so a tempfile/pid/timestamp path would never hit.
DEFAULT_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself, so
    no path is configured here and no other path is ever set in code.
    Unset: the cache goes to :data:`DEFAULT_DIR` inside the checkout.

    Either way the cache's key takes in each operation's metadata. The
    step is read by the names it carries (``op_name``: the phases, the
    models' parts, the kernels' names; ``docs/api.md``), and an executable
    from the cache carries the names of whoever compiled it first: with
    JAX's default key, which leaves the metadata out, a program whose
    scopes changed would be handed one that predates them, and a profile
    read by scope would find nothing. The price is one compile after an
    edit that moves a traced line.
    """
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
