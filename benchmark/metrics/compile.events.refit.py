"""Compile-cache requests (loads and compiles) the program made inside the
window: cache_stats() after minus before. Should be 0."""


def read(ctx):
    return float(ctx.compile_events)
