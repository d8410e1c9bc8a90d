"""Device time of one of the program's compiled programs in the traced
stretch, per unit of the work its counters say it did."""


def read(ctx, program: str, per: str, scale: float = 1e3):
    if ctx.trace is None or not ctx.window.traced:
        return None
    seconds, runs = ctx.trace.program_seconds(program)
    work = ctx.window.traced.get(per, 0)
    if not runs or not work:
        return None
    return scale * seconds / work
