"""The one place an entry point chooses JAX's persistent compile cache.

``chip_smoke.py`` and the ``tools/*.py`` scripts that touch JAX call :func:`ensure_compile_cache` before their first compile.
``import paddle_tpu`` never does: a library import chooses no directory.
(It does put metadata into the cache's key, ``nn/layer/layers.py``: the
named scopes a profile is read by are metadata, and an executable cached
under other names must not be served for this tree.)

The cache directory is part of the cache key's environment, so it must
not move between runs:

- where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
  nothing is configured in code (a machine that comes with the variable
  set keeps what this repo caches there for the next call);
- where it is not, the cache is ``<checkout>/.jax_cache`` — the same
  directory ``tests/conftest.py`` points the variable at, already in
  ``.gitignore``.  Never a temporary name, a pid or a time.
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def ensure_compile_cache() -> str:
    """Make sure this process compiles through a persistent cache and
    return the directory in use."""
    env_dir = os.environ.get(ENV_VAR)
    if env_dir:
        return env_dir
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR


class CacheCounter:
    """Counts this process's persistent-cache hits and misses from JAX's
    own monitoring events, so a run can say whether it hit the cache."""

    _HIT = "/jax/compilation_cache/cache_hits"
    _MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax.monitoring

        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kwargs) -> None:
        if event == self._HIT:
            self.hits += 1
        elif event == self._MISS:
            self.misses += 1
