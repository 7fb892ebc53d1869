"""Device seconds of the per-level chunk-step program, found in the
profiler trace by its jit name, per check of the traced part."""

PROGRAM = "_chunk_step_impl"


def read(ctx):
    if ctx.trace is None or not ctx.traced_checks:
        return None
    s = sum(v for k, v in ctx.trace.module_s.items() if PROGRAM in k)
    return s / ctx.traced_checks if s > 0 else None
