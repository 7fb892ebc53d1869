"""From process start to the window's start: engine build, program
tracing, compile-cache load or compile, warm-up checks (host clock)."""


def read(ctx):
    return ctx.setup_s
