"""Slab rows (entities x row cap) of the direct-route rungs that keep the
vmapped XLA CG, in percent of the slab rows of all direct-route rungs:
what the CG kernel's gate turns away. From the ``fit`` stage's ``rungs``
(routes ``direct`` / ``direct_kernel``); no direct rung or no such
attribute: no number."""

from benchmark import fitstage


def read(ctx):
    direct = fitstage.rungs(ctx, "direct")
    if not direct:
        return None
    xla = sum(b * r for b, r, route in direct if route == "direct")
    return 100.0 * xla / sum(b * r for b, r, _ in direct)
