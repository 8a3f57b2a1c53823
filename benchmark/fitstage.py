"""What the program wrote on the window's ``fit`` stages: the attributes
``FusedFit.run`` puts there per random-effect coordinate (``active_rows``,
``passive_rows``, ``capped_entities``, ``slab_rows`` and the ``rungs`` as
``[entities, row cap, route]``; OBSERVABILITY.md). A refit window holds
no ``plan`` stage, so these are the planner's counts as a fit sees them.
A program that records no such attribute gives every reader here nothing.
"""

from __future__ import annotations

from benchmark import stages


def coordinates(ctx):
    """coordinate -> attributes, of the window's last ``fit`` stage that
    carries any; None without one."""
    for record in reversed(stages.records(ctx, "fit")):
        found = (getattr(record, "attrs", None) or {}).get("coordinates")
        if found:
            return found
    return None


def rungs(ctx, route_prefix: str = ""):
    """``[entities, row cap, route]`` of every rung of every coordinate
    whose route starts with ``route_prefix``; None as above."""
    found = coordinates(ctx)
    if found is None:
        return None
    return [rung for attrs in found.values() for rung in attrs["rungs"]
            if rung[2].startswith(route_prefix)]
