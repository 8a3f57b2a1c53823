"""Mean seconds per job of the benchmark's span around save_game_model."""


def read(ctx):
    return ctx.span_mean("bench.save")
