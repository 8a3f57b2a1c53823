"""Mean seconds per job of the program's ``save.records`` stages, summed
over the coordinates: device -> host pull, per-entity GLMs, Avro datums
(save_game_model)."""

from benchmark import stages


def read(ctx):
    return stages.per_unit(ctx, "save.records")
