"""Share of the leaf device time inside the program's `sppy.ef.solve`
spans spent in ops under the `pdhg_consensus` scope (benchmark/
program.py).  Logs the matvec and unscoped shares beside it, so the
three can be seen to add up.  Nothing without program spans or scope
paths."""

import sys

from benchmark import program as P


def read(ctx):
    prog = getattr(ctx, "program", None)
    if prog is None:
        return None
    shares = P.scope_shares(ctx.trace, prog, ctx.lo, ctx.hi)
    if shares is None:
        return None
    print(f"scope shares: {P.CONSENSUS} {shares[P.CONSENSUS]!r}%, "
          f"{P.MATVEC} {shares[P.MATVEC]!r}%, unscoped {shares[None]!r}%",
          file=sys.stderr)
    return shares[P.CONSENSUS]
