"""Where JAX's persistent compilation cache lives.

Every process of this framework compiles the same programs (the fused
trusted step, the eval step, the serve prefill and decode programs), and
a chip run keeps nothing but its output directory, so a cold start is
minutes of compilation.  JAX's on-disk cache removes the repeats — if its
path is stable: the path is part of what a hit needs, so a temporary,
per-run or per-process directory never hits.

:func:`configure_compile_cache` is the one place that decides, and every
entry point (the CLI, ``benchmark/run.py``, ``chip_smoke.py``, the tests)
calls it before its first compile:

* ``JAX_COMPILATION_CACHE_DIR`` set — JAX's own handling of the variable
  stands, and nothing here touches ``jax_compilation_cache_dir``;
* unset — the cache goes to ``<checkout>/.jax_cache`` (git-ignored).

JAX's own thresholds stay as they are: a compile of one second or more
is written, whatever the size of the entry.
"""

from __future__ import annotations

import os

#: ``<checkout>/.jax_cache``: beside the package, so a copied tree finds
#: it at the same relative place.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".jax_cache",
)


def configure_compile_cache() -> str:
    """Place the persistent compilation cache; returns its directory."""
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    import jax

    if jax.config.jax_compilation_cache_dir != DEFAULT_CACHE_DIR:
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
