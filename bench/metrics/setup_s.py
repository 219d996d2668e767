"""setup_s: seconds from the process's start to the window's start --
imports, the corpus made on the device, the system built, and the window's
shapes compiled (or read from the compile cache) and run once."""


def read(ctx):
    return ctx.setup_s
