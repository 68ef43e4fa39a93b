"""Share of the leaf device time inside the program's `sppy.ef.solve`
spans spent in ops under the `pdhg_matvec` scope (benchmark/
program.py).  Nothing without program spans or scope paths."""

from benchmark import program as P


def read(ctx):
    prog = getattr(ctx, "program", None)
    if prog is None:
        return None
    shares = P.scope_shares(ctx.trace, prog, ctx.lo, ctx.hi)
    return None if shares is None else shares[P.MATVEC]
