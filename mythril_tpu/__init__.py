__version__ = "0.1.0"

_compile_cache_armed = False


def repo_compile_cache_dir() -> str:
    """The fixed in-checkout cache location: ``<checkout>/.jax_cache``.

    The path is part of every cache key, so it never moves between runs
    (no temp name, pid or time); ``.gitignore`` lists it."""
    import os

    return os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache",
    )


def arm_compile_cache() -> None:
    """Persist compiled XLA programs on disk across processes; called once
    by each entry point (CLI, service, ``chip_smoke.py``),
    never at import.

    The tape VM, the Pallas keccak kernel and the frontier's
    ``cached_segment`` programs compile once per shape bucket; the cache
    turns that into a once-per-checkout cost.  When the caller sets
    ``JAX_COMPILATION_CACHE_DIR``, JAX already reads it and no directory
    is set here; otherwise the cache lives at ``repo_compile_cache_dir()``.
    The min-compile-time floor drops to 0 so small programs (the
    opening-dispatch segment, CPU parity runs) are cached too.

    Cache hits/misses are mirrored into the ``compilecache.hits`` /
    ``compilecache.misses`` counters via ``jax.monitoring`` so
    ``--metrics-out`` snapshots and report meta show whether a warm start
    skipped the recompile.
    """
    global _compile_cache_armed
    import os

    if _compile_cache_armed:
        return
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        cache_dir = repo_compile_cache_dir()
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    _compile_cache_armed = True
    _arm_compile_cache_listener()


def _arm_compile_cache_listener() -> None:
    """Mirror jax's compilation-cache hit/miss events into the registry."""
    import jax.monitoring

    from mythril_tpu.observability.metrics import get_registry

    reg = get_registry()
    # persistent scope: hits accumulate across the per-contract metric
    # sweeps — warm-start evidence is process-wide, like the frontier's
    # slow/narrow-code verdicts.  Force-create so --metrics-out shows
    # the block even at 0.
    reg.counter("compilecache.hits", persistent=True)
    reg.counter("compilecache.misses", persistent=True)

    def _on_event(event, **kwargs):
        if event == "/jax/compilation_cache/cache_hits":
            reg.counter("compilecache.hits", persistent=True).inc()
        elif event == "/jax/compilation_cache/cache_misses":
            reg.counter("compilecache.misses", persistent=True).inc()

    jax.monitoring.register_event_listener(_on_event)
