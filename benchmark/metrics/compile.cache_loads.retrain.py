"""Persistent-cache loads per job of the window (cache_stats() delta
around each job), mean over the jobs."""


def read(ctx):
    return ctx.job_mean(lambda j: j["cache_loads"])
