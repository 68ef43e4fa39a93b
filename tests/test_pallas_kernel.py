"""Pallas fused-chunk kernel tests (interpret mode on CPU): the kernel
must match the jnp reference loop bit-for-tolerance, and a full PH
golden run through the pallas path must land on the farmer optimum."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mpisppy_tpu.models import farmer
from mpisppy_tpu.ops.pallas_pdhg import fused_chunk
from mpisppy_tpu.ops.pdhg import PDHGSolver, prepare_batch


def _ref_steps(A, cs, qs, lbs, ubs, rlo, rhi, x, y, tau, sigma, n):
    xs = jnp.zeros_like(x)
    ys = jnp.zeros_like(y)
    for _ in range(n):
        grad = cs + qs * x + jnp.einsum("smn,sm->sn", A, y)
        xn = jnp.clip(x - tau[:, None] * grad, lbs, ubs)
        xt = 2.0 * xn - x
        v = y + sigma[:, None] * jnp.einsum("smn,sn->sm", A, xt)
        zc = jnp.clip(v / sigma[:, None], rlo, rhi)
        yn = v - sigma[:, None] * zc
        x, y = xn, yn
        xs = xs + xn
        ys = ys + yn
    return x, y, xs, ys


def test_fused_chunk_matches_reference():
    rng = np.random.RandomState(0)
    S, M, N = 16, 5, 7
    A = jnp.asarray(rng.randn(S, M, N))
    cs = jnp.asarray(rng.randn(S, N))
    qs = jnp.asarray(np.abs(rng.randn(S, N)) * 0.1)
    lbs = jnp.zeros((S, N))
    ubs = jnp.full((S, N), 10.0)
    rlo = jnp.asarray(np.where(rng.rand(S, M) < 0.5, -np.inf,
                               -rng.rand(S, M)))
    rhi = jnp.asarray(rng.rand(S, M) + 1.0)
    x = jnp.asarray(rng.rand(S, N))
    y = jnp.asarray(rng.randn(S, M) * 0.1)
    tau = jnp.asarray(0.1 + 0.05 * rng.rand(S))
    sigma = jnp.asarray(0.1 + 0.05 * rng.rand(S))

    ref = _ref_steps(A, cs, qs, lbs, ubs, rlo, rhi, x, y, tau, sigma, 7)
    got = fused_chunk(A, cs, qs, lbs, ubs, rlo, rhi, x, y, tau, sigma,
                      7, tile_s=8, interpret=True)
    for r, g in zip(ref, got):
        assert np.allclose(np.asarray(r), np.asarray(g), atol=1e-10)


@pytest.mark.parametrize("S", [3, 12])
def test_fused_chunk_odd_batch_pads_to_a_legal_tile(S):
    """S <= 8 runs as one whole-batch block; S = 12 pads to 16 and runs
    two tiles of 8 — both legal TPU block shapes."""
    rng = np.random.RandomState(1)
    M, N = 4, 5
    args = (jnp.asarray(rng.randn(S, M, N)), jnp.asarray(rng.randn(S, N)),
            jnp.zeros((S, N)), jnp.zeros((S, N)),
            jnp.full((S, N), 5.0), jnp.full((S, M), -1.0),
            jnp.ones((S, M)), jnp.asarray(rng.rand(S, N)),
            jnp.zeros((S, M)), jnp.full((S,), 0.1), jnp.full((S,), 0.1))
    out = fused_chunk(*args, 3, tile_s=8, interpret=True)
    ref = _ref_steps(*args, 3)
    for r, g in zip(ref, out):
        assert g.shape == r.shape
        assert np.allclose(np.asarray(g), np.asarray(r), atol=1e-10)


def test_choose_tile_obeys_block_rule_and_budget():
    from mpisppy_tpu.ops.pallas_pdhg import VMEM_BUDGET, choose_tile
    assert choose_tile(3, 61, 120, 8, 4) == (3, 3)
    assert choose_tile(12, 61, 120, 8, 4) == (8, 16)
    assert choose_tile(64, 61, 120, 16, 4) == (16, 64)
    assert choose_tile(40, 61, 120, 16, 4) == (8, 40)
    with pytest.raises(ValueError, match="multiple of 8"):
        choose_tile(64, 61, 120, 4, 4)
    with pytest.raises(ValueError, match="VMEM"):
        choose_tile(64, 1200, 1500, 8, 4)
    assert VMEM_BUDGET <= 128 * 2**20


def test_pallas_refuses_operators_it_cannot_tile():
    b = farmer.build_batch(8)
    from mpisppy_tpu.ops.pdhg import prepare_batch_split
    from mpisppy_tpu.ir import delta_idx
    rows, cols = delta_idx(b)
    prep = prepare_batch_split(b.A, rows, cols, b.row_lo, b.row_hi)
    solver = PDHGSolver(max_iters=200, eps=1e-5, use_pallas=True,
                        pallas_interpret=True)
    with pytest.raises(ValueError, match="use_pallas"):
        solver.solve(prep, b.c, b.qdiag, b.lb, b.ub)


def test_pdhg_solver_pallas_path_farmer():
    b = farmer.build_batch(8)
    prep = prepare_batch(b.A, b.row_lo, b.row_hi)
    solver = PDHGSolver(max_iters=20000, eps=1e-7, use_pallas=True,
                        pallas_tile=8, pallas_interpret=True)
    res = solver.solve(prep, b.c, b.qdiag, b.lb, b.ub,
                       obj_const=b.obj_const)
    assert bool(np.asarray(res.converged).all())
    # wait-and-see bound of 8-scenario farmer: E[obj] finite, below 0
    solver2 = PDHGSolver(max_iters=20000, eps=1e-7, use_pallas=False)
    res2 = solver2.solve(prep, b.c, b.qdiag, b.lb, b.ub,
                         obj_const=b.obj_const)
    assert np.allclose(np.asarray(res.obj), np.asarray(res2.obj),
                       rtol=1e-5, atol=1e-3)
