"""value_ratio: mean over the window's answers of f(S) / f(greedy) at the
same budget, both in float64 (bench/check.py)."""


def read(ctx):
    return ctx.value_ratio
