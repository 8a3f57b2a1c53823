"""Share, in percent, of the traced window's idle seconds that fall in no
stage of the program's training thread (benchmark/stages.py idle_by_leaf:
the program's stage records moved to the trace's clock, each idle moment
given to the deepest stage open at it). What is left lies between the
program's calls: the harness's own waits and collections."""

from benchmark import stages


def read(ctx):
    by = stages.idle_by_leaf(ctx)
    if not by:
        return None
    idle = sum(by.values())
    return 100.0 * by.get("(outside spans)", 0.0) / idle if idle else None
