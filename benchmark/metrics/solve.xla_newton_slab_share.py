"""Slab rows (entities x row cap) of the rungs that took the XLA Newton
step, in percent of the slab rows of all Newton rungs: what the Pallas
kernel's VMEM gate turns away. From the ``fit`` stage's ``rungs``
(routes ``newton_xla`` / ``newton_kernel``); no Newton rung or no such
attribute: no number."""

from benchmark import fitstage


def read(ctx):
    newton = fitstage.rungs(ctx, "newton_")
    if not newton:
        return None
    xla = sum(b * r for b, r, route in newton if route == "newton_xla")
    return 100.0 * xla / sum(b * r for b, r, _ in newton)
