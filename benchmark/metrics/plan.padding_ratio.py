"""Largest ratio, over random-effect coordinates, of slab rows (entities x
row cap, summed over the bucket ladder) to real rows. A count from the
prepared datasets' shapes; repeats exactly."""


def read(ctx):
    if not ctx.plan_shapes:
        return None
    rows = float(ctx.config["rows"])
    return max(sum(b * r for b, r in shapes) / rows
               for shapes in ctx.plan_shapes.values())
