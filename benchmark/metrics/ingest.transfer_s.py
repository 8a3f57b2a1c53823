"""Mean per job of PIPELINE_STATS.report()["transfer_seconds"], read after each
job's fit. Summed over the planner's threads, so it can pass the span."""


def read(ctx):
    return ctx.job_mean(lambda j: j["pipeline"]["transfer_seconds"])
