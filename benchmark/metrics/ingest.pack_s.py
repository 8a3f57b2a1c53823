"""Mean seconds per job of the program's ``pack`` stages (plan arrays into
the packed transfer buffer), summed over the threads that ran them."""

from benchmark import stages


def read(ctx):
    return stages.per_unit(ctx, "pack")
