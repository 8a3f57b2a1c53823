"""Mean seconds per job of persistent-cache retrievals, on any thread: the
window's ``compile.cache_load`` records (JAX's cache_retrieval_time_sec)."""

from benchmark import stages


def read(ctx):
    return stages.per_unit(ctx, "compile.cache_load")
