"""Mean seconds per job of the program's ``save.encode`` records, summed
over the coordinates: datum -> the block's bytes, deflate included
(avro.write_container, summed over the blocks of a file)."""

from benchmark import stages


def read(ctx):
    return stages.per_unit(ctx, "save.encode")
