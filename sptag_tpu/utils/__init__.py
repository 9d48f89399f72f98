import os


def round_up(n: int, m: int) -> int:
    """Round n up to the next multiple of m."""
    return ((n + m - 1) // m) * m


# Query-batch padding ladder shared by the device search paths: padding to a
# fixed bucket lets repeated searches reuse compiled programs instead of
# recompiling per shape.
QUERY_BUCKETS = (1, 8, 32, 128, 256, 1024)


def host_cores() -> int:
    """Cores THIS process may run on (its affinity mask) - what a pool of
    worker threads is sized by.  `os.cpu_count()` counts the machine's,
    and a pool that wide holds that many blocks' scratch at once on a
    host that gives the process a few of its cores (the chip's gives 13:
    PR 34)."""
    try:
        return max(1, len(os.sched_getaffinity(0)))
    except AttributeError:                  # not on this platform
        return os.cpu_count() or 1


def query_bucket(q: int, cap: int) -> int:
    """Pad q up to the smallest bucket, bounded by the caller's chunk cap."""
    for b in QUERY_BUCKETS:
        if q <= b:
            return min(b, cap)
    return min(round_up(q, QUERY_BUCKETS[-1]), cap)


_cache_enabled = False


def pin_platform(platform=None) -> None:
    """Pin jax's platform list before any backend initializes.

    JAX picks the accelerator when one is attached and fails at start-up
    if it cannot reach it; CLIs call this with their --platform flag
    (default: the SPTAG_TPU_PLATFORM env var) so an explicit
    `--platform cpu` runs a tool on the host instead.  Same effect as
    JAX_PLATFORMS, but settable after jax is imported.  No-op when
    nothing is requested."""
    import os

    p = platform or os.environ.get("SPTAG_TPU_PLATFORM")
    if p:
        import jax

        jax.config.update("jax_platforms", p)


#: default persistent compile cache: one fixed directory inside the
#: checkout (git-ignored).  The path is part of what a chip run can find
#: again, so it carries no salt, pid or time.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> None:
    """Turn on jax's persistent compilation cache (idempotent).

    A cold BKT build compiles dozens of programs; the persistent cache
    lets a repeat build, and the next process, reuse them.  Where
    JAX_COMPILATION_CACHE_DIR is set, jax already reads it and no
    directory is set here — whoever runs the program places the cache.
    Otherwise the cache is `COMPILE_CACHE_DIR`.  To turn the cache off
    use jax's own switch, JAX_ENABLE_COMPILATION_CACHE=false (the test
    suite does: child processes inherit it).  Called from the index
    build/search entry points rather than at import so importing the
    library never initializes a backend.
    """
    global _cache_enabled
    if _cache_enabled:
        return
    _cache_enabled = True
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)


def shape_bucket(x: int, lo: int = 32) -> int:
    """Quantize a padded array dimension to a small ladder: powers of 4
    below 2^15, powers of 2 above.  Every distinct padded shape compiles a
    fresh XLA kernel (seconds each, and a cold build has dozens); coarse
    buckets trade ≤4x padding compute — cheap on the MXU — for an
    order-of-magnitude fewer compiles across a build."""
    if x >= (1 << 15):
        return 1 << max(0, (x - 1).bit_length())
    b = max(1, lo)
    while b < x:
        b *= 4
    if b >= (1 << 15):
        # the pow4 ladder overshot the crossover (e.g. lo=1 ladder misses
        # 32768): fall back to pow2 so the function stays monotonic
        return 1 << max(0, (x - 1).bit_length())
    return b
