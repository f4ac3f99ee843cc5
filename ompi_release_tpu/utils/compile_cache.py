"""Where compiled programs are kept between processes.

One jit of a collective or of the train step costs seconds to tens of
seconds on a TPU, and a job is many processes (every ``tpurun`` rank,
every leg of ``chip_smoke.py``) compiling the same programs. JAX's
persistent compilation cache makes the second process load what the
first one built — but only if both name the SAME directory, because
the path is the only thing that ties them together.

So there is exactly one rule, applied by :func:`ensure` before a
process's first compile (``Runtime.init`` and the model entry points
call it):

  - ``JAX_COMPILATION_CACHE_DIR`` set  -> jax reads it itself; this
    code sets nothing.
  - unset -> ``<checkout>/.jax_cache`` (git-ignored): a fixed path
    derived from where the package lives, never a temp name, a pid or
    a time, so every process of every run of this checkout agrees.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: the fixed in-checkout default: <repo>/.jax_cache
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".jax_cache",
)


def ensure() -> str:
    """Make sure this process compiles into the shared cache; returns
    the directory in effect. Idempotent and cheap (one env read, one
    config read) — call it wherever a first compile may happen."""
    env_dir = os.environ.get(ENV_VAR)
    if env_dir:
        return env_dir
    import jax

    if jax.config.jax_compilation_cache_dir is None:
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return jax.config.jax_compilation_cache_dir
