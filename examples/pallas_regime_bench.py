"""Pallas-vs-XLA kernel comparison in the Pallas kernel's CLAIMED
regime (VERDICT r3 item 3): large per-scenario problems where one
scenario's (A, x, y) tile approaches VMEM capacity and the fused chunk
kernel's VMEM residency should pay off — farmer with
crops_multiplier >= 100 (N ~ 1.2k, M ~ 0.4k per scenario at mult=100).

Runs the same solver-space PDHG chunk through BOTH paths and reports
sec/iter each way plus the ratio.  One JSON line per configuration.

    python examples/pallas_regime_bench.py            # on TPU
    PALLAS_BENCH_INTERPRET=1 ... (CPU, correctness only — timing
    meaningless in interpret mode)

On CPU without interpret mode the Pallas path is skipped (the kernel
is TPU-only); the XLA path still prints, so the artifact records the
comparison baseline either way.
"""

import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main():
    from mpisppy_tpu.utils.platform import ensure_cpu_backend
    ensure_cpu_backend()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mpisppy_tpu.models import farmer
    from mpisppy_tpu.ops import pdhg

    on_tpu = jax.devices()[0].platform != "cpu"
    interpret = bool(os.environ.get("PALLAS_BENCH_INTERPRET"))
    mult = int(os.environ.get("PALLAS_BENCH_MULT", 100))
    S = int(os.environ.get("PALLAS_BENCH_SCENS", 64))
    n_steps = int(os.environ.get("PALLAS_BENCH_STEPS", 200))
    tile_s = int(os.environ.get("PALLAS_BENCH_TILE", 8))   # multiple of 8

    b = farmer.build_batch(S, crops_multiplier=mult,
                           dtype=np.float32 if on_tpu else np.float64)
    prep = pdhg.prepare_batch(b.A, b.row_lo, b.row_hi)
    solver = pdhg.PDHGSolver(max_iters=n_steps, eps=1e-6)
    dt = b.c.dtype
    cs = jnp.asarray(b.c) * prep.d_col
    qs = jnp.asarray(b.qdiag) * prep.d_col * prep.d_col
    lbs = jnp.where(jnp.isfinite(b.lb), b.lb / prep.d_col, b.lb)
    ubs = jnp.where(jnp.isfinite(b.ub), b.ub / prep.d_col, b.ub)
    x = jnp.zeros_like(cs)
    y = jnp.zeros((S, b.num_rows), dt)
    omega = jnp.ones((S,), dt)
    sigma = 0.9 * omega / prep.anorm
    tau = 0.9 / (omega * prep.anorm + 0.9 * jnp.max(qs, axis=1))

    vmem_tile_mb = (b.num_rows * b.num_vars * tile_s
                    * np.dtype(dt).itemsize) / 1e6
    out = {"metric": "pallas_vs_xla_sec_per_iter",
           "scens": S, "crops_multiplier": mult,
           "rows": b.num_rows, "vars": b.num_vars,
           "tile_A_mb": round(vmem_tile_mb, 2),
           "device": jax.devices()[0].platform, "n_steps": n_steps}

    # XLA path: the solver's own fused while_loop chunk
    import functools

    @functools.partial(jax.jit, static_argnames=("n",))
    def xla_chunk(x, y, n):
        def body(_, carry):
            x, y = carry
            grad = cs + qs * x + pdhg._ATy(prep.A, y)
            xn = jnp.clip(x - tau[:, None] * grad, lbs, ubs)
            xt = 2.0 * xn - x
            v = y + sigma[:, None] * pdhg._Ax(prep.A, xt)
            zc = jnp.clip(v / sigma[:, None], prep.row_lo, prep.row_hi)
            return xn, v - sigma[:, None] * zc
        from jax import lax
        return lax.fori_loop(0, n, body, (x, y))

    r = xla_chunk(x, y, n_steps)
    jax.block_until_ready(r)
    t0 = time.time()
    r = xla_chunk(x, y, n_steps)
    jax.block_until_ready(r)
    out["xla_sec_per_iter"] = round((time.time() - t0) / n_steps, 7)

    if on_tpu or interpret:
        from mpisppy_tpu.ops.pallas_pdhg import fused_chunk
        r2 = fused_chunk(prep.A, cs, qs, lbs, ubs, prep.row_lo,
                         prep.row_hi, x, y, tau, sigma, n_steps,
                         tile_s=tile_s, interpret=interpret)
        jax.block_until_ready(r2)
        t0 = time.time()
        r2 = fused_chunk(prep.A, cs, qs, lbs, ubs, prep.row_lo,
                         prep.row_hi, x, y, tau, sigma, n_steps,
                         tile_s=tile_s, interpret=interpret)
        jax.block_until_ready(r2)
        out["pallas_sec_per_iter"] = round((time.time() - t0) / n_steps,
                                           7)
        out["pallas_speedup"] = round(
            out["xla_sec_per_iter"] / out["pallas_sec_per_iter"], 3)
        # agreement check on the final iterates
        out["max_dx"] = float(jnp.max(jnp.abs(r[0] - r2[0])))
    else:
        out["pallas_sec_per_iter"] = None
        out["note"] = "Pallas path skipped (TPU-only kernel; CPU host)"
    print(json.dumps(out))


if __name__ == "__main__":
    main()
