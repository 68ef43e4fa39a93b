"""Pallas TPU kernel: fused PDHG iteration chunk.

The PDHG hot loop (ops/pdhg.py `steps`) does, per iteration, two
batched matvecs plus elementwise prox updates.  Under plain XLA each
iteration's intermediates round-trip through HBM; this kernel keeps a
TILE of scenarios' (A, x, y, bounds) resident in VMEM and runs the
whole `n_steps` chunk on-chip — matvecs on the MXU via dot_general,
prox math on the VPU — writing back only the chunk's final iterates
and running sums (which the restart logic consumes).

Grid: 1-D over scenario tiles; every ref is a (TILE_S, ...) VMEM
block.  Usable on CPU with interpret=True (that is how the unit tests
pin it against the jnp reference implementation).

See /opt/skills/guides/pallas_guide.md for the API conventions used.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# VMEM the kernel may ask Mosaic for (the scoped limit it is compiled
# with).  A v5e core has 128 MiB; the rest is Mosaic's own headroom.
VMEM_BUDGET = 100 * 2**20


def _chunk_kernel(n_steps, A_ref, cs_ref, qs_ref, lb_ref, ub_ref,
                  rlo_ref, rhi_ref, x_ref, y_ref, tau_ref, sig_ref,
                  xo_ref, yo_ref, xs_ref, ys_ref):
    # mixed-precision slabs (hot_dtype="bf16x") STORE A in bf16 — half
    # the VMEM per tile — but all arithmetic runs in the state dtype
    # (f32): the cast up happens once per chunk on the VMEM-resident
    # tile, so accumulation never drops below the compute precision
    A = A_ref[:].astype(cs_ref.dtype)
    cs = cs_ref[:]
    qs = qs_ref[:]
    lb = lb_ref[:]
    ub = ub_ref[:]
    rlo = rlo_ref[:]
    rhi = rhi_ref[:]
    tau = tau_ref[:]          # (T, 1)
    sigma = sig_ref[:]        # (T, 1)

    def body(_, carry):
        x, y, xs, ys = carry
        # per-scenario matvecs as VPU multiply-reduce over the VMEM-
        # resident A tile (Mosaic does not lower batched 3-D
        # dot_general; a matvec is bandwidth-bound so the VPU is the
        # right unit anyway)
        aty = jnp.sum(A * y[:, :, None], axis=1)      # (T, N)
        grad = cs + qs * x + aty
        xn = jnp.clip(x - tau * grad, lb, ub)
        xt = 2.0 * xn - x
        ax = jnp.sum(A * xt[:, None, :], axis=2)      # (T, M)
        v = y + sigma * ax
        zc = jnp.clip(v / sigma, rlo, rhi)
        yn = v - sigma * zc
        return xn, yn, xs + xn, ys + yn

    x0 = x_ref[:]
    y0 = y_ref[:]
    x, y, xs, ys = lax.fori_loop(
        0, n_steps, body,
        (x0, y0, jnp.zeros_like(x0), jnp.zeros_like(y0)))
    xo_ref[:] = x
    yo_ref[:] = y
    xs_ref[:] = xs
    ys_ref[:] = ys


def _block_bytes(shape, itemsize):
    """VMEM bytes of one block as Mosaic lays it out: the last dim
    padded to 128 lanes, the second-to-last to the sublane tile (8 rows
    of 32-bit, 16 of bf16)."""
    *lead, rows, cols = shape
    sub = 8 * max(1, 4 // itemsize)
    return (math.prod(lead) * (-(-rows // sub) * sub)
            * (-(-cols // 128) * 128) * itemsize)


def vmem_bytes(tile, M, N, a_itemsize, itemsize=4, steps=2):
    """Estimated VMEM of one grid step: every in/out block (twice when
    the grid has more than one step — Pallas double-buffers them), plus
    the kernel's (tile, M, N) temporaries in the state dtype (the
    broadcast product, and the cast copy of a narrower A).  Checked
    against compiles for a described v5e: it stays above what Mosaic
    reports."""
    vec = (6 * _block_bytes((tile, N), itemsize)
           + 4 * _block_bytes((tile, M), itemsize)
           + 2 * _block_bytes((tile, 1), itemsize))
    blocks = _block_bytes((tile, M, N), a_itemsize) + vec
    slab = _block_bytes((tile, M, N), itemsize)
    temps = 1.35 * slab + (slab if a_itemsize != itemsize else 0)
    return int((2 if steps > 1 else 1) * blocks + temps)


def choose_tile(S, M, N, tile_s, a_itemsize, itemsize=4):
    """(tile, padded S) for a batch of S scenarios.  Every tile obeys
    the TPU block rule — the scenario dim of a (tile, ·) block is a
    multiple of 8 or the whole batch — and fits VMEM_BUDGET; S is
    padded up to a multiple of 8.  Raises ValueError when even
    the smallest legal tile does not fit."""
    if tile_s < 1 or tile_s % 8:
        raise ValueError(
            f"pallas tile_s must be a positive multiple of 8 (the TPU "
            f"sublane tile), got {tile_s}")
    # pad to the next multiple of 8 at most, then take the largest
    # tile up to tile_s that divides it
    Sp = S if S <= 8 else -(-S // 8) * 8
    tiles = ([S] if S <= 8 else
             [t for t in range(min(tile_s, Sp), 0, -8) if Sp % t == 0])
    for t in tiles:
        need = vmem_bytes(t, M, N, a_itemsize, itemsize, steps=Sp // t)
        if need <= VMEM_BUDGET:
            return t, Sp
    raise ValueError(
        f"fused_chunk: a ({tiles[-1]}, {M}, {N}) scenario tile needs "
        f"~{need / 2**20:.0f} MiB of VMEM, over the "
        f"{VMEM_BUDGET / 2**20:.0f} MiB budget; this kernel holds whole "
        f"per-scenario matrices in VMEM, so M*N this large needs the "
        f"XLA path (use_pallas=False)")


@functools.partial(
    jax.jit,
    static_argnames=("n_steps", "tile_s", "interpret"))
def fused_chunk(A, cs, qs, lbs, ubs, rlo, rhi, x, y, tau, sigma,
                n_steps, tile_s=8, interpret=False):
    """Run `n_steps` PDHG iterations for the whole batch.

    All arrays are SOLVER-SPACE (already Ruiz-scaled) like the inner
    loop of PDHGSolver._solve_impl.  tau/sigma: (S,) per-scenario step
    sizes.  Returns (x, y, x_sum, y_sum) exactly matching the jnp
    `steps` implementation.  A batch over 8 is padded to a multiple
    of 8 (`choose_tile`) with inert scenarios — zero data, unit step
    sizes — that are sliced off the outputs.
    """
    S, M, N = A.shape
    tile, Sp = choose_tile(S, M, N, int(tile_s), A.dtype.itemsize,
                           x.dtype.itemsize)
    grid = (Sp // tile,)
    t2 = tau[:, None]
    s2 = sigma[:, None]
    args = [A, cs, qs, lbs, ubs, rlo, rhi, x, y, t2, s2]
    if Sp != S:
        fills = [0.0] * 9 + [1.0, 1.0]     # unit tau/sigma: no 0/0

        def pad(a, fill):
            return jnp.concatenate(
                [a, jnp.full((Sp - S,) + a.shape[1:], fill, a.dtype)])

        args = [pad(a, v) for a, v in zip(args, fills)]

    def tile_spec(*blk):
        # i * 0, not 0: block indices stay int32 under x64, where a
        # literal 0 is int64 and Mosaic cannot lower the index map
        return pl.BlockSpec(
            blk, lambda i: (i,) + (i * 0,) * (len(blk) - 1))

    kernel = functools.partial(_chunk_kernel, n_steps)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            tile_spec(tile, M, N),      # A
            tile_spec(tile, N),         # cs
            tile_spec(tile, N),         # qs
            tile_spec(tile, N),         # lb
            tile_spec(tile, N),         # ub
            tile_spec(tile, M),         # rlo
            tile_spec(tile, M),         # rhi
            tile_spec(tile, N),         # x
            tile_spec(tile, M),         # y
            tile_spec(tile, 1),         # tau
            tile_spec(tile, 1),         # sigma
        ],
        out_specs=[
            tile_spec(tile, N),
            tile_spec(tile, M),
            tile_spec(tile, N),
            tile_spec(tile, M),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Sp, N), x.dtype),
            jax.ShapeDtypeStruct((Sp, M), y.dtype),
            jax.ShapeDtypeStruct((Sp, N), x.dtype),
            jax.ShapeDtypeStruct((Sp, M), y.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_BUDGET),
        interpret=interpret,
    )(*args)
    return tuple(o[:S] for o in out)
