"""Device-idle microseconds per EF solve while the program's own
`sppy.ef.solve` span is open (benchmark/program.py).  Nothing where the
run carries no program spans (`ctx.program`)."""

from benchmark import program as P


def read(ctx):
    prog = getattr(ctx, "program", None)
    if prog is None:
        return None
    return P.ef_idle_us(ctx.trace, prog, ctx.lo, ctx.hi)
