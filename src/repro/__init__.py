"""repro — Spindle (RDMA atomic multicast optimizations) as a multi-pod
JAX training/serving framework.  See README.md and DESIGN.md."""

import os

# The checkout's own persistent compile cache.  A later process finds the
# entries only at the same path, so it is fixed: never a temp name, pid or
# time.
CHECKOUT_COMPILATION_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def use_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point (the
    chip smoke, a benchmark, an example) and return its directory.

    One rule: where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
    reads it and nothing is set here; otherwise the cache lives at the
    fixed ``<checkout>/.jax_cache`` (git-ignored).  Library code and the
    tests never call this, so a test run leaves the checkout clean."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir",
                      CHECKOUT_COMPILATION_CACHE)
    return CHECKOUT_COMPILATION_CACHE
