"""Where JAX's persistent compilation cache lives.

The place is chosen from outside: where ``JAX_COMPILATION_CACHE_DIR`` is
set, JAX reads it itself and nothing here sets a directory, so children
inherit the same place through the environment. Where it is not set the
cache goes to one fixed ``<checkout>/.jax_cache`` — the path is part of
the cache's key, so a directory that moves never hits.
"""

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def configure():
    """Place the persistent cache; every entry point calls this before
    its first compile. Returns the directory in force."""
    from_env = os.environ.get(ENV_VAR)
    if from_env:
        return from_env
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR


def active_dir():
    """The directory JAX will use (None: no persistent cache)."""
    import jax

    return jax.config.jax_compilation_cache_dir


_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
           "/jax/compilation_cache/cache_misses": "misses"}


def count_events():
    """Start counting the persistent cache's hits and misses; returns
    the live ``{"hits": n, "misses": n}`` dict."""
    import jax

    counts = {"hits": 0, "misses": 0}

    def _listen(event, **kwargs):
        key = _EVENTS.get(event)
        if key:
            counts[key] += 1

    jax.monitoring.register_event_listener(_listen)
    return counts
