"""Largest share, over random-effect coordinates, of rows that train
nothing and are only scored (the rows an entity's reservoir cap leaves
out), in percent of all rows: the ``fit`` stage's ``passive_rows`` over
``active_rows + passive_rows``. A count of the planner's; repeats
exactly. No such attribute: no number."""

from benchmark import fitstage


def read(ctx):
    found = fitstage.coordinates(ctx)
    if found is None:
        return None
    return max(
        100.0 * c["passive_rows"] / (c["active_rows"] + c["passive_rows"])
        for c in found.values())
