"""Mean seconds per job of the benchmark's span around make_game_dataset
to block_until_ready (raw shards onto the device)."""


def read(ctx):
    return ctx.span_mean("bench.dataset")
