"""The farmer extensive form solved whole by the consensus PDHG kernel
(opt/ef.py on ops/pdhg.py), in float32 on the CPU, split-native as at
full size: it converges where the consensus coupling used to stall it,
agrees with HiGHS on the whole EF, and the balanced prep
(ops/pdhg.balance_consensus) leaves the EFs that do not need it as they
were and slows none of the multistage trees that take it.  Also the
float64 fallback of a split-native EF, and the telemetry that names the
prep's branch and the matvec's form."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from scipy.optimize import linprog
from scipy.sparse import coo_matrix, vstack

from mpisppy_tpu import telemetry
from mpisppy_tpu.ir import SplitA, pad_scenarios
from mpisppy_tpu.models import aircond, farmer, hydro, sslp
from mpisppy_tpu.ops.pdhg import (_dense_row_factors, _gather_prep,
                                  prepare_split_native, shared_solve_jit)
from mpisppy_tpu.opt.ef import ExtensiveForm
from mpisppy_tpu.opt.ph import PH
from mpisppy_tpu.phbase import fused_superstep
from mpisppy_tpu.parallel.mesh import ScenarioMesh

F32 = np.float32


@pytest.fixture(autouse=True)
def _telemetry_on():
    telemetry.configure({"enabled": True, "phase_timing": False})
    yield
    telemetry.reset()


def _farmer(S, mult=10):
    return farmer.build_batch(S, crops_multiplier=mult, dtype=F32,
                              split=True)


def _names(b):
    return [f"scen{i}" for i in range(b.num_scens)]


def _ef(b, eps, names=None, max_iters=100000):
    # on one device, unpadded, as the benchmark's one-chip cells run
    return ExtensiveForm({"pdhg_eps": eps, "pdhg_max_iters": max_iters},
                         names or _names(b), batch=b,
                         mesh=ScenarioMesh(devices=jax.devices()[:1]))


def _solve(b, eps, names=None, max_iters=100000, certify=False):
    ef = _ef(b, eps, names, max_iters)
    return ef, ef.solve_extensive_form(certify=certify)


def _ef_highs(b):
    """HiGHS (scipy, float64) on the whole EF of a two-stage batch: one
    column per nonant, one per scenario for the rest."""
    A = b.A
    if isinstance(A, SplitA):
        A = np.asarray(A.to_dense(), np.float64)
    A = np.asarray(A, np.float64)
    S, M, N = A.shape
    na = np.asarray(b.nonant_idx)
    rest = np.setdiff1d(np.arange(N), na)
    K, R = len(na), len(rest)
    p = np.asarray(b.prob, np.float64)
    c = np.asarray(b.c, np.float64)
    lb, ub = np.asarray(b.lb, np.float64), np.asarray(b.ub, np.float64)
    nv = K + S * R
    cost = np.concatenate([(p[:, None] * c[:, na]).sum(0)]
                          + [p[s] * c[s, rest] for s in range(S)])
    blocks = []
    for s in range(S):
        sub = coo_matrix(A[s][:, np.concatenate([na, rest])])
        col = np.concatenate([np.arange(K), K + s * R + np.arange(R)])
        blocks.append(coo_matrix((sub.data, (sub.row, col[sub.col])),
                                 shape=(M, nv)))
    rows = vstack(blocks).tocsr()
    lo = np.asarray(b.row_lo, np.float64).reshape(-1)
    hi = np.asarray(b.row_hi, np.float64).reshape(-1)
    up, dn = np.isfinite(hi), np.isfinite(lo)
    bounds = np.stack([np.concatenate([lb[0, na]] + [lb[s, rest]
                                                     for s in range(S)]),
                       np.concatenate([ub[0, na]] + [ub[s, rest]
                                                     for s in range(S)])],
                      axis=1)
    r = linprog(cost, A_ub=vstack([rows[up], -rows[dn]]),
                b_ub=np.concatenate([hi[up], -lo[dn]]), bounds=bounds,
                method="highs")
    assert r.status == 0, r.message
    return float(r.fun + p @ np.asarray(b.obj_const, np.float64))


def _counter(name):
    return telemetry.get().registry.counter(name).value


def test_farmer_64x10_converges_to_highs():
    b = _farmer(64)
    opt = _ef_highs(b)
    np.testing.assert_allclose(opt, -1315246.07, rtol=1e-8)
    ef, res = _solve(b, 1e-4)
    assert bool(jnp.all(res.converged)) and int(res.iters) < 20000
    obj, dual = ef.get_objective_value(), ef.get_dual_bound()
    assert abs(obj - opt) <= 2e-4 * abs(opt)
    # the solver's own relative gap measure: at eps 1e-4 it lets
    # |obj - dual| reach ~2e-4 of |obj|
    assert abs(dual - opt) <= 2e-4 * (1 + abs(dual) + abs(opt))
    assert _counter("pdhg.ef_prep_balanced") == 1
    assert _counter("pdhg.ef_prep_ruiz") == 0


@pytest.mark.parametrize("S,ceiling", [(16, 19400), (64, 20000)])
def test_farmer_iterations_stay_under_the_ceiling(S, ceiling):
    # before the nonant columns were balanced: 19,400 iterations at
    # 16 x 10, and 64 x 10 was still unconverged at 100,000
    _, res = _solve(_farmer(S), 1e-4)
    assert bool(jnp.all(res.converged))
    assert int(res.iters) < ceiling


def test_sslp_ef_keeps_its_prep_and_its_work():
    b = sslp.build_batch(50, instance="sslp_5_25", dtype=F32)
    _, res = _solve(b, 1e-5, names=[f"Scenario{i + 1}" for i in range(50)])
    assert bool(jnp.all(res.converged))
    # 7,280 inner iterations before and after: the nonant block's norm
    # is 1.68 times the rest's, under the ratio that balances
    assert abs(int(res.iters) - 7280) <= 2 * 40
    assert _counter("pdhg.ef_prep_ruiz") == 1
    assert _counter("pdhg.ef_prep_balanced") == 0


@pytest.mark.parametrize("build,now,before,branch", [
    (lambda: hydro.build_batch((3, 3), dtype=F32), 2840, 2840, "ruiz"),
    (lambda: aircond.build_batch((10, 10), dtype=F32), 4040, 9520,
     "balanced")],
    ids=["hydro_3x3", "aircond_10x10"])
def test_multistage_ef_is_no_slower(build, now, before, branch):
    # inner iterations at pdhg_eps 1e-5, pinned, and before the
    # balancing: hydro's nonant block (3.03 times the rest's norm) keeps
    # Ruiz's prep; aircond's (11.6 times) is balanced and only faster
    b = build()
    _, res = _solve(b, 1e-5, names=list(b.tree.scen_names))
    assert bool(jnp.all(res.converged))
    assert abs(int(res.iters) - now) <= 2 * 40
    assert int(res.iters) <= before
    assert _counter(f"pdhg.ef_prep_{branch}") == 1


def test_balanced_prep_scales_only_the_dense_row():
    # farmer's acreage row (one entry per crop) is the only row far
    # denser than the median; the rest of the rows keep Ruiz's scaling
    b = _farmer(16)
    ruiz = prepare_split_native(b.A, b.row_lo, b.row_hi)
    r = np.asarray(_dense_row_factors(ruiz.A))
    np.testing.assert_array_equal(np.flatnonzero(r < 1.0), [b.num_rows - 1])
    assert 0.0 < r[-1] < 0.5
    # the EF's prep carries that row factor, and one factor below 1 on
    # every nonant column and on no other
    prep = _ef(b, 1e-4).prep
    assert isinstance(prep.A, SplitA)
    np.testing.assert_allclose(np.asarray(prep.d_row / ruiz.d_row)[0], r,
                               rtol=1e-6)
    f = np.asarray(prep.d_col / ruiz.d_col)[0]
    na = np.asarray(b.nonant_idx)
    np.testing.assert_allclose(f[na], f[na[0]], rtol=1e-6)
    assert 0.0 < f[na[0]] < 1.0
    np.testing.assert_array_equal(np.delete(f, na), 1.0)
    assert _counter("pdhg.ef_prep_balanced") == 1


def test_split_native_ef_falls_back_to_float64():
    # a solve left unconverged by its cap goes to the float64 re-solve,
    # which preps the split-native batch as split (it has no dense A)
    b = _farmer(16)
    assert isinstance(b.A, SplitA)
    ef, res = _solve(b, 1e-4, max_iters=400, certify=True)
    assert bool(jnp.all(res.converged))
    opt = _ef_highs(b)
    assert abs(ef.get_objective_value() - opt) <= 2e-4 * abs(opt)
    assert abs(ef.get_dual_bound() - opt) <= 2e-4 * (
        1 + abs(ef.get_dual_bound()) + abs(opt))


def _ops(op):
    for region in op.regions:
        for block in region.blocks:
            for o in block.operations:
                yield o.operation
                yield from _ops(o.operation)


def _split_delta_ops(module):
    """(name, location) of each op under the split operator's delta
    scope inside the lowered module's while body."""
    return [(op.name, str(op.location)) for op in _ops(module.operation)
            if re.search(r"\bwhile/body/(.*/)?pdhg_matvec/pdhg_split_delta/",
                         str(op.location))]


def _assert_delta_runs_as_slices(module):
    # the scope is still on the delta's ops, a multiply-add of static
    # slices: no scatter-add, and no gather (jnp.take's own function)
    delta = _split_delta_ops(module)
    assert any(name == "stablehlo.multiply" for name, _ in delta)
    assert any(name == "stablehlo.slice" for name, _ in delta)
    assert not [(name, loc) for name, loc in delta
                if name in ("stablehlo.scatter", "stablehlo.gather")
                or re.search(r"gather|scatter|_take\b", loc)]


def test_split_solve_names_its_delta_and_its_form():
    b = _farmer(4, mult=1)
    ef = _ef(b, 1e-4)
    (n_s, n), m = b.c.shape, ef.prep.A.shape[1]
    module = shared_solve_jit(ef.solver).lower(
        ef.prep, b.c, b.qdiag, b.lb, b.ub, jnp.zeros(n_s, F32),
        jnp.zeros((n_s, n), F32), jnp.zeros((n_s, m), F32), ef.consensus,
        None, None).compiler_ir("stablehlo")
    _assert_delta_runs_as_slices(module)
    assert _counter("pdhg.matvec_split") == 1
    assert _counter("pdhg.matvec_dense") == 0
    assert _counter("pdhg.split_delta_runs") == 1
    assert _counter("pdhg.split_delta_scatter") == 0


def test_ph_superstep_delta_keeps_its_runs_when_padded_and_compacted():
    # a PH superstep on a padded farmer batch, with its prep gathered
    # as compaction gathers it: each rebuilt SplitA carries the runs
    b = pad_scenarios(_farmer(3, mult=1), 4)
    ph = PH({"defaultPHrho": 1.0, "PHIterLimit": 1, "convthresh": 0.0,
             "pdhg_eps": 1e-4, "pdhg_max_iters": 400},
            list(b.tree.scen_names), batch=b,
            mesh=ScenarioMesh(devices=jax.devices()[:1]))
    ph.Iter0()
    prep = _gather_prep(ph.prep, jnp.asarray([2, 0, 3, 1], jnp.int32))
    assert b.A.runs is not None and prep.A.runs == b.A.runs
    module = fused_superstep(ph.solver).lower(
        ph.state, ph.rho, ph.W_on, ph.prox_on, ph.lb_eff, ph.ub_eff,
        ph.superstep_eps, prep, ph.batch).compiler_ir("stablehlo")
    _assert_delta_runs_as_slices(module)
    assert _counter("pdhg.split_delta_runs") >= 1
    assert _counter("pdhg.split_delta_scatter") == 0
