"""Persistent-compile-cache misses during set-up (JAX's own events); 0 in
a warm run.  The window itself is asserted to compile nothing."""


def read(ctx):
    return float(ctx["counters"]["compile_cache_misses"])
