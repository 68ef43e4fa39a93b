"""ir.SplitA (shared + sparse-delta constraint matrices): operator
parity, prepared-batch parity, and end-to-end PH trajectory parity
against the dense representation.

Farmer is the motivating family (reference examples/farmer/farmer.py:
the yield coefficients are the ONLY scenario-varying matrix entries);
these tests pin that declaring model_meta["A_delta_idx"] changes no
numbers, only the kernel's memory traffic.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mpisppy_tpu import telemetry
from mpisppy_tpu.ir import (DELTA_RUNS_MAX, SplitA, bmatvec, bmatvec_t,
                            delta_form, delta_runs)
from mpisppy_tpu.models import farmer
from mpisppy_tpu.opt.ph import PH
from mpisppy_tpu.ops.pdhg import (PDHGSolver, prepare_batch,
                                  prepare_batch_split)


def _farmer_delta(b):
    rows, cols = b.model_meta["A_delta_idx"]
    return jnp.asarray(rows, jnp.int32), jnp.asarray(cols, jnp.int32)


def _split_of(b):
    rows, cols = _farmer_delta(b)
    A = jnp.asarray(b.A)
    vals = A[:, rows, cols]
    shared = A[0].at[rows, cols].set(0.0)
    return SplitA(shared=shared, rows=rows, cols=cols, vals=vals)


def test_farmer_declares_consistent_delta():
    """The model's declaration contract: outside the delta coordinate
    set, every scenario's matrix row equals scenario 0's."""
    b = farmer.build_batch(5, crops_multiplier=2)
    rows, cols = (np.asarray(v) for v in b.model_meta["A_delta_idx"])
    A = np.asarray(b.A).copy()
    A[:, rows, cols] = 0.0
    assert np.array_equal(A[1:], np.broadcast_to(A[0], A[1:].shape))


def test_bmatvec_matches_dense():
    b = farmer.build_batch(7, crops_multiplier=3)
    sp = _split_of(b)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(7, b.num_vars))
    y = jnp.asarray(rng.randn(7, b.num_rows))
    np.testing.assert_allclose(np.asarray(bmatvec(sp, x)),
                               np.asarray(bmatvec(jnp.asarray(b.A), x)),
                               rtol=1e-12, atol=1e-12)
    np.testing.assert_allclose(np.asarray(bmatvec_t(sp, y)),
                               np.asarray(bmatvec_t(jnp.asarray(b.A), y)),
                               rtol=1e-12, atol=1e-12)
    dense = np.asarray(sp.to_dense())
    np.testing.assert_allclose(dense, np.asarray(b.A), rtol=0, atol=0)


def test_prepare_split_scaled_operator_matches():
    """The split prep's scaled operator D_r A D_c must match a dense
    reconstruction of the same scalings."""
    b = farmer.build_batch(6, crops_multiplier=2)
    rows, cols = _farmer_delta(b)
    prep = prepare_batch_split(jnp.asarray(b.A), rows, cols,
                               jnp.asarray(b.row_lo),
                               jnp.asarray(b.row_hi))
    assert isinstance(prep.A, SplitA)
    dr = np.asarray(prep.d_row)[0]
    dc = np.asarray(prep.d_col)[0]
    want = dr[None, :, None] * np.asarray(b.A) * dc[None, None, :]
    got = np.asarray(prep.A.to_dense())
    np.testing.assert_allclose(got, want, rtol=1e-10, atol=1e-12)
    # equilibration actually helps: scaled row inf-norms near 1
    rmax = np.abs(got).max(axis=2)
    assert rmax[rmax > 0].max() < 4.0
    assert rmax[rmax > 0].min() > 0.1


def test_solver_split_vs_dense_parity():
    b = farmer.build_batch(8, crops_multiplier=2)
    rows, cols = _farmer_delta(b)
    sp_prep = prepare_batch_split(jnp.asarray(b.A), rows, cols,
                                  jnp.asarray(b.row_lo),
                                  jnp.asarray(b.row_hi))
    de_prep = prepare_batch(jnp.asarray(b.A), jnp.asarray(b.row_lo),
                            jnp.asarray(b.row_hi))
    solver = PDHGSolver(max_iters=60000, eps=1e-8)
    r_sp = solver.solve(sp_prep, jnp.asarray(b.c), jnp.asarray(b.qdiag),
                        jnp.asarray(b.lb), jnp.asarray(b.ub))
    r_de = solver.solve(de_prep, jnp.asarray(b.c), jnp.asarray(b.qdiag),
                        jnp.asarray(b.lb), jnp.asarray(b.ub))
    assert bool(np.all(np.asarray(r_sp.converged)))
    assert bool(np.all(np.asarray(r_de.converged)))
    np.testing.assert_allclose(np.asarray(r_sp.obj),
                               np.asarray(r_de.obj), rtol=5e-6)
    np.testing.assert_allclose(np.asarray(r_sp.dual_obj),
                               np.asarray(r_de.dual_obj), rtol=5e-5)


@pytest.fixture(scope="module")
def ph_pair():
    opts = {"defaultPHrho": 1.0, "PHIterLimit": 8, "convthresh": 0.0,
            "pdhg_eps": 1e-7}
    names = [f"scen{i}" for i in range(3)]
    ph_sp = PH(dict(opts), names, batch=farmer.build_batch(3))
    assert isinstance(ph_sp.prep.A, SplitA)   # meta took effect
    ph_de = PH(dict(opts, no_split_prep=True), names,
               batch=farmer.build_batch(3))
    assert not isinstance(ph_de.prep.A, SplitA)
    for p in (ph_sp, ph_de):
        p.Iter0()
        for _ in range(8):
            p.ph_iteration()
    return ph_sp, ph_de


def test_ph_trajectory_parity(ph_pair):
    ph_sp, ph_de = ph_pair
    assert abs(ph_sp.trivial_bound - ph_de.trivial_bound) < 1.0
    assert abs(ph_sp.conv - ph_de.conv) < 1e-4 * (1 + abs(ph_de.conv))
    np.testing.assert_allclose(np.asarray(ph_sp.root_xbar()),
                               np.asarray(ph_de.root_xbar()), atol=0.3)


def test_ph_bounds_parity(ph_pair):
    ph_sp, ph_de = ph_pair
    lag_sp = ph_sp.lagrangian_bound()
    lag_de = ph_de.lagrangian_bound()
    assert abs(lag_sp - lag_de) < 1.0 + 1e-4 * abs(lag_de)
    in_sp, f_sp = ph_sp.evaluate_xhat(ph_sp.root_xbar())
    in_de, f_de = ph_de.evaluate_xhat(ph_de.root_xbar())
    assert f_sp and f_de
    assert abs(in_sp - in_de) < 1.0 + 1e-4 * abs(in_de)


def test_xhat_reduced_system_is_shared():
    """Farmer's delta columns are all nonants, so the reduced xhat
    system must collapse to the (1, M, N) shared-A fast path."""
    b = farmer.build_batch(4)
    opts = {"defaultPHrho": 1.0, "PHIterLimit": 2, "convthresh": 0.0}
    ph = PH(opts, [f"scen{i}" for i in range(4)], batch=b)
    cache = ph._xhat_cache(None)
    assert cache["A_red"].shape[0] == 1
    # the no_split_prep escape hatch disables this fast path too (it
    # rests on the same A_delta_idx declaration contract)
    ph2 = PH(dict(opts, no_split_prep=True),
             [f"scen{i}" for i in range(4)], batch=farmer.build_batch(4))
    assert ph2._xhat_cache(None)["A_red"].shape[0] \
        == ph2.batch.num_scens


def test_bundled_delta_remap():
    from mpisppy_tpu.utils.bundles import bundle_batch
    b = farmer.build_batch(6)
    bb = bundle_batch(b, 3)
    rows, cols = (np.asarray(v) for v in bb.model_meta["A_delta_idx"])
    A = np.asarray(bb.A).copy()
    vals = A[:, rows, cols]
    A[:, rows, cols] = 0.0
    # shared outside deltas, and the deltas carry the member yields
    assert np.array_equal(A[1:], np.broadcast_to(A[0], A[1:].shape))
    assert vals.std() > 0
    # bundled PH still solves through the split path
    opts = {"defaultPHrho": 1.0, "PHIterLimit": 4, "convthresh": 0.0}
    ph = PH(opts, list(bb.tree.scen_names), batch=bb)
    assert isinstance(ph.prep.A, SplitA)
    ph.Iter0()
    assert np.isfinite(ph.trivial_bound)


def _coords(case):
    """(M, N, rows, cols) of a delta coordinate set."""
    if case.startswith("farmer"):
        b = farmer.build_batch(2, crops_multiplier=int(case[6:]))
        rows, cols = (np.asarray(v) for v in b.model_meta["A_delta_idx"])
        return b.num_rows, b.num_vars, rows, cols
    if case == "length1":
        return 9, 11, np.array([0, 5, 2, 7]), np.array([3, 1, 8, 0])
    if case == "same_rows":     # two runs add into rows 1..5
        return 9, 14, np.r_[1:6, 1:6], np.r_[0:5, 7:12]
    if case == "same_cols":     # two runs add into columns 2..6
        return 14, 9, np.r_[0:5, 7:12], np.r_[2:7, 2:7]
    rng = np.random.RandomState(1)     # "shuffled": past the cap
    k = rng.permutation(40)
    return 20, 30, np.r_[0:20, 0:20][k], np.r_[0:20, 10:30][k]


@pytest.mark.parametrize("case", ["farmer2", "farmer5", "length1",
                                  "same_rows", "same_cols", "shuffled",
                                  "traced"])
def test_delta_runs_match_dense_and_scatter(case):
    """The static-run form of the delta (ir.SplitA.with_runs) gives the
    products of the dense operator and of the gather / scatter-add form;
    coordinates past ir.DELTA_RUNS_MAX runs, or traced ones, keep the
    scatter form."""
    M, N, rows, cols = _coords("farmer2" if case == "traced" else case)
    S = 3
    rng = np.random.RandomState(0)
    shared = rng.randn(M, N)
    shared[rows, cols] = 0.0
    A = SplitA(shared=jnp.asarray(shared), rows=jnp.asarray(rows, jnp.int32),
               cols=jnp.asarray(cols, jnp.int32),
               vals=jnp.asarray(rng.randn(S, rows.size)))
    x = jnp.asarray(rng.randn(S, N))
    y = jnp.asarray(rng.randn(S, M))
    dense = np.asarray(A.to_dense())
    want = (np.einsum("smn,sn->sm", dense, np.asarray(x)),
            np.einsum("smn,sm->sn", dense, np.asarray(y)))
    scatter = (bmatvec(A, x), bmatvec_t(A, y))
    if case == "traced":
        @jax.jit
        def products(rows, cols):
            At = dataclasses.replace(A, rows=rows, cols=cols).with_runs()
            assert At.runs is None and delta_form(At) == "scatter"
            return bmatvec(At, x), bmatvec_t(At, y)
        got = products(A.rows, A.cols)
    else:
        Ar = A.with_runs()
        n_runs = len(set(zip(rows - np.arange(rows.size),
                             cols - np.arange(cols.size))))
        if case == "shuffled":
            assert n_runs > DELTA_RUNS_MAX
            assert Ar is A and delta_form(Ar) == "scatter"
        else:
            assert len(Ar.runs) == n_runs <= DELTA_RUNS_MAX
            assert delta_form(Ar) == "runs"
        got = (bmatvec(Ar, x), bmatvec_t(Ar, y))
    for g, w, s in zip(got, want, scatter):
        np.testing.assert_allclose(np.asarray(g), w, rtol=1e-12, atol=1e-12)
        np.testing.assert_allclose(np.asarray(g), np.asarray(s), rtol=1e-12,
                                   atol=1e-12)


def test_farmer_delta_is_two_runs():
    b = farmer.build_batch(4, crops_multiplier=3, split=True)
    nc = 9
    assert b.A.runs == ((0, 0, 0, nc), (nc, nc, 0, nc))
    rows, cols = b.model_meta["A_delta_idx"]
    assert delta_runs(rows, cols, b.num_rows, b.num_vars) == b.A.runs
    # out of the matrix: no run form
    assert delta_runs(rows, cols, int(np.max(rows)), b.num_vars) is None


@pytest.mark.parametrize("runs", [True, False], ids=["runs", "scatter"])
def test_solve_counts_its_delta_form(runs):
    """pdhg.split_delta_runs / pdhg.split_delta_scatter: the delta form
    each solve was traced with, once per trace; a PH on the dense
    farmer batch attaches the runs of its declared coordinates."""
    telemetry.configure({"enabled": True, "phase_timing": False})
    try:
        b = farmer.build_batch(9, crops_multiplier=1)
        ph = PH({"defaultPHrho": 1.0, "PHIterLimit": 1, "convthresh": 0.0,
                 "pdhg_max_iters": 123 if runs else 124},
                [f"scen{i}" for i in range(9)], batch=b)
        prep = ph.prep
        assert prep.A.runs is not None
        if not runs:
            prep = dataclasses.replace(
                prep, A=dataclasses.replace(prep.A, runs=None))
        pb = ph.batch
        ph.solver.solve(prep, pb.c, pb.qdiag, pb.lb, pb.ub)
        reg = telemetry.get().registry
        assert reg.counter("pdhg.split_delta_runs").value == int(runs)
        assert reg.counter("pdhg.split_delta_scatter").value == int(not runs)
    finally:
        telemetry.reset()
