"""Optimizer iterations the window's last fit spent on the fixed effect,
summed over its coordinate-descent iterations (every fit of a window
solves the same problem, so it is every fit's count). A count the program
hands back with the fit. Why it stands here: the fused fit sums the fixed
effect's loss in float32 over millions of rows and its L-BFGS stops on a
tolerance below that sum's rounding, so a change that moves the order of
a sum can move this count, and with it ``train_rows_per_s`` by a fifth,
with no change to the speed of any step (PERF.md section 2, item 21). A
rate that moved with this count moved by the stop, not by the code. A
program that hands back no count: no number."""


def read(ctx):
    found = ctx.fixed_iterations
    return None if found is None else float(found)
