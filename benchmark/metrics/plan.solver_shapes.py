"""Rungs of the bucket ladder a fit solves per CD iteration, all
random-effect coordinates together: each is one solver instance of a
shape of its own in the fused program (a kernel rung is seconds of trace
at set-up). A count from the ``fit`` stage's ``rungs``; no such
attribute: no number."""

from benchmark import fitstage


def read(ctx):
    found = fitstage.rungs(ctx)
    return None if found is None else float(len(found))
