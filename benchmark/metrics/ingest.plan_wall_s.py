"""Mean over the jobs of the wall seconds from the first start to the last
end of the job's ``plan`` stages (the planners run side by side, so
ingest.plan_s, their thread-seconds, can pass it)."""

from benchmark import stages


def read(ctx):
    return stages.wall_per_job(ctx, "plan")
