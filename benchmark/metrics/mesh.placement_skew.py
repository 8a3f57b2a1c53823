"""The fullest device's bytes of the prepared data set over the mean of
all devices that hold any: the ``placed_bytes`` attribute of the window's
last ``fit`` stage that carries one (one entry a device, from shapes and
shardings). 1.0 is even; a whole copy on one device of four beside even
quarters reads 1.6. No such attribute: no number."""

from benchmark import stages


def read(ctx):
    for record in reversed(stages.records(ctx, "fit")):
        placed = (getattr(record, "attrs", None) or {}).get("placed_bytes")
        if placed and sum(placed) > 0:
            return max(placed) * len(placed) / sum(placed)
    return None
