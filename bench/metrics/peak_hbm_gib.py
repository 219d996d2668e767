"""peak_hbm_gib: the fullest device's peak after the window, before the
reference runs: ``peak_bytes_in_use`` (arrays) plus ``peak_bytes_reserved``
(program temporaries, which the TPU runtime reserves apart), in GiB."""


def read(ctx):
    return ctx.peak_bytes / 2 ** 30 if ctx.peak_bytes else None
