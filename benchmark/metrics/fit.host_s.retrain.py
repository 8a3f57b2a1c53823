"""Mean seconds per job of the program's ``fit`` stage: FusedFit.run from
entry to the return of the dispatch; the host's part of the fit, no wait
for the device in it."""

from benchmark import stages


def read(ctx):
    return stages.per_unit(ctx, "fit")
