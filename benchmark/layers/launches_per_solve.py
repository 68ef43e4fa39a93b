"""Host program launches (`PJRT_LoadedExecutable_Execute`) inside the
program's `sppy.ef.solve` spans, per solve (benchmark/program.py): the
solve's kernel plus every eager op around it.  Nothing where the run
carries no program spans (`ctx.program`)."""

from benchmark import program as P


def read(ctx):
    prog = getattr(ctx, "program", None)
    if prog is None:
        return None
    return P.launches_per_solve(prog, ctx.lo, ctx.hi)
