"""peak_bytes_in_use of the fullest chip after the window, as the
device's allocator reports it."""


def read(ctx):
    return ctx.peak_bytes
