# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.

from __future__ import annotations

import functools
import os
from pathlib import Path

#: where :func:`use_compile_cache` keeps JAX's persistent compilation cache
#: when ``JAX_COMPILATION_CACHE_DIR`` is unset: a fixed directory of the
#: checkout (listed in ``.gitignore``)
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


@functools.lru_cache(maxsize=1)
def default_interpret() -> bool:
    """Whether Pallas kernels should run in interpret mode by default.

    Compiled on real accelerators (TPU / GPU), interpret-mode on CPU where
    Mosaic cannot lower.  Every kernel call site should route its default
    through this single helper so real hardware never silently runs the
    slow interpreter (and CPU CI never tries to compile).
    """
    import jax

    return jax.default_backend() not in ("tpu", "gpu", "cuda", "rocm")


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, places the cache: JAX reads it
    itself and no other directory is set here. Otherwise the cache goes to
    :data:`REPO_CACHE_DIR`, a fixed path: a temp or per-run directory would
    start empty every run and never hit. Every compile is kept (the default skips those under a second, which
    is every kernel of the query path). Call before the first compile.
    """
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
