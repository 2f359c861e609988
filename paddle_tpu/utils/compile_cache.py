"""JAX's persistent compilation cache, placed from outside.

``chip_smoke.py``, ``bench.py`` and ``__graft_entry__.py`` call
:func:`enable` before their first compile (JAX decides once per
process, at the first compile, whether the cache is in use). The
executor's and the generator's AOT ``lower().compile()`` keys are stable
across processes, so a second process finds what the first one built.

Placement: where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it
itself and this module sets no directory; where it is unset the cache
goes to one fixed path under the checkout (``.jax_compile_cache/``,
git-ignored). A cache that moves is never found again, so the path is
never a temporary name, a pid or a time.

The tests do not turn it on: on a CPU sandbox every hit makes the
XLA:CPU AOT loader print a machine-feature mismatch error (ROADMAP D9).
"""
import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_compile_cache")

_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileCache:
    """Where the persistent cache lives, and what this process has done
    with it since :func:`enable` returned this handle: executables found
    (``hits``) and built (``misses``), and ``compile_seconds`` spent in
    the backend either building an executable or reading it back."""

    def __init__(self, directory):
        self.directory = directory
        self.hits = 0
        self.misses = 0
        self.compile_seconds = 0.0

    def _on_event(self, event, **_kwargs):
        if event == _HIT:
            self.hits += 1
        elif event == _MISS:
            self.misses += 1

    def _on_duration(self, event, seconds, **_kwargs):
        if event == _BACKEND_COMPILE:
            self.compile_seconds += seconds


def enable():
    """Turn the persistent compilation cache on for this process and
    return its :class:`CompileCache` handle. Call before the first
    compile."""
    import jax
    directory = os.environ.get(ENV_VAR)
    if not directory:
        directory = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", directory)
    # JAX's default keeps only executables that took over a second to
    # build. Serving builds dozens that each take less (three samplers,
    # a prefill per length bucket and batch bucket, pool scatters and
    # copies), and together they are most of a warm start's compile
    # time; entries are small, so everything is kept.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cache = CompileCache(directory)
    jax.monitoring.register_event_listener(cache._on_event)
    jax.monitoring.register_event_duration_secs_listener(cache._on_duration)
    return cache
