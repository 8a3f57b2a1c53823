"""Mean seconds per job of the program's ``save.write`` records, summed
over the coordinates: open, the blocks' bytes -> file, close
(avro.write_container)."""

from benchmark import stages


def read(ctx):
    return stages.per_unit(ctx, "save.write")
