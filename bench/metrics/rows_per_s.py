"""rows_per_s: corpus rows selected over, summed over every selection
completed in the window, over the seconds from the window's start to the
last completion."""


def read(ctx):
    rows = getattr(ctx, "rows_done", None)
    if not rows:
        return None
    return rows / ctx.elapsed_s
