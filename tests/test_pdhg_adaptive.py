"""Adaptive-work PDHG tests: the KKT-triggered restart policy, the
converged-scenario compaction driver, the option plumbing for the new
knobs, and the AST trace-safety guard on the solver's hot loop.

Measured headline (f64 model corpus, eps=1e-6): adaptive restarts cut
total inner iterations 33% vs the fixed cadence (farmer 0.50x, netdes
0.37x, uc 0.44x, apl1p 0.55x; sizes/sslp within noise) — the tier-1
subset below asserts the >=20% aggregate on its three fastest members.
"""

import ast
import inspect
import os

import numpy as np
import pytest

import jax.numpy as jnp

from mpisppy_tpu.models import apl1p, farmer, netdes
from mpisppy_tpu.ops.pdhg import PDHGSolver, _gather_prep, prepare_batch
from mpisppy_tpu.serve.compile_cache import width_bucket

pytestmark = pytest.mark.pdhg


# --------------------------------------------------------------------------
# knob plumbing
# --------------------------------------------------------------------------

def test_width_bucket():
    assert [width_bucket(n) for n in (1, 2, 3, 4, 5, 8, 9, 1000)] \
        == [1, 2, 4, 4, 8, 8, 16, 1024]
    assert width_bucket(3, floor=8) == 8
    assert width_bucket(0) == 1


def test_from_options_maps_adaptive_knobs():
    s = PDHGSolver.from_options({
        "pdhg_restart_mode": "fixed",
        "pdhg_restart_beta_sufficient": 0.1,
        "pdhg_restart_beta_necessary": 0.9,
        "pdhg_compact_threshold": 0.25})
    assert s.restart_mode == "fixed"
    assert s.restart_beta_sufficient == 0.1
    assert s.restart_beta_necessary == 0.9
    assert s.compact_threshold == 0.25
    # defaults: adaptive on, compaction off
    d = PDHGSolver.from_options({})
    assert d.restart_mode == "adaptive"
    assert d.compact_threshold == 0.0


def test_env_overlay_wins(monkeypatch):
    monkeypatch.setenv(
        "MPISPPY_TPU_PDHG",
        "restart_mode=fixed pdhg_compact_threshold=0.5")
    s = PDHGSolver.from_options({"pdhg_restart_mode": "adaptive",
                                 "pdhg_compact_threshold": 0.0})
    assert s.restart_mode == "fixed"       # env wins over the dict
    assert s.compact_threshold == 0.5      # prefixed key accepted too


def test_bad_restart_mode_rejected():
    with pytest.raises(ValueError):
        PDHGSolver(restart_mode="sometimes")


def test_clone_and_config_key():
    s = PDHGSolver(eps=1e-7, restart_beta_sufficient=0.3,
                   compact_threshold=0.5)
    c = s.clone(max_iters=123)
    assert c.max_iters == 123
    assert c.restart_beta_sufficient == 0.3
    assert c.compact_threshold == 0.5
    # config_key covers every knob: only the overridden field differs
    ka, kb = s.config_key(), c.config_key()
    assert ka != kb
    assert [a for a, b in zip(ka, kb) if a != b] == [s.max_iters]
    assert len(ka) == len(inspect.signature(PDHGSolver).parameters)
    # the new knobs are IN the key (configs must never alias in caches)
    assert s.config_key() != s.clone(restart_mode="fixed").config_key()
    assert s.config_key() != \
        s.clone(compact_threshold=0.25).config_key()


# --------------------------------------------------------------------------
# adaptive vs fixed on the model corpus
# --------------------------------------------------------------------------

def _corpus():
    return [farmer.build_batch(8), netdes.build_batch(4),
            apl1p.build_batch()]


def test_adaptive_and_fixed_reach_reference_verdicts():
    """Both restart policies must reach the SAME certified KKT verdicts
    (all-converged) and the same objectives on the corpus, and the
    adaptive policy must spend >=20% fewer total inner iterations (the
    measured aggregate on the full corpus is 33%)."""
    tot = {"adaptive": 0, "fixed": 0}
    for b in _corpus():
        prep = prepare_batch(b.A, b.row_lo, b.row_hi)
        objs = {}
        for mode in ("adaptive", "fixed"):
            s = PDHGSolver(max_iters=100000, eps=1e-6, restart_mode=mode)
            res = s.solve(prep, b.c, b.qdiag, b.lb, b.ub,
                          obj_const=b.obj_const)
            assert bool(np.all(np.asarray(res.converged))), mode
            assert np.all(np.asarray(res.pres) < 1e-6)
            tot[mode] += int(res.iters)
            objs[mode] = np.asarray(res.obj)
            # restart accounting: per-scenario counts ride in the result
            assert np.asarray(res.restarts).shape == (b.num_scens,)
        assert np.allclose(objs["adaptive"], objs["fixed"], rtol=1e-4,
                           atol=1e-4)
    assert tot["adaptive"] <= 0.8 * tot["fixed"], tot


def test_adaptive_restarts_before_forced_cap():
    """On farmer the trigger must fire well before the every-16 forced
    cap (that is where the iteration savings come from): more restart
    events than the fixed cadence takes in the same iteration count."""
    b = farmer.build_batch(8)
    prep = prepare_batch(b.A, b.row_lo, b.row_hi)
    s = PDHGSolver(max_iters=100000, eps=1e-6)
    res = s.solve(prep, b.c, b.qdiag, b.lb, b.ub, obj_const=b.obj_const)
    n_checks = int(res.iters) // s.check_every
    forced_cadence_events = (n_checks // s.restart_every) * b.num_scens
    assert int(np.sum(np.asarray(res.restarts))) > forced_cadence_events


# --------------------------------------------------------------------------
# compaction
# --------------------------------------------------------------------------

def _split_difficulty_batch():
    """farmer-8 with a strong prox term (center far from the LP
    optimum) on scenarios 0-3: the inflated objective scale makes those
    need ~4x the iterations of the plain LPs 4-7 (measured: LPs
    converge by 2560 inner iterations, prox scenarios by 9120) — a
    clean early/late split, the shape compaction exists for."""
    b = farmer.build_batch(8)
    q = np.array(b.qdiag)
    c = np.array(b.c)
    q[:4] += 100.0
    c[:4, :3] -= 100.0 * 150.0   # prox center at 150 acres
    return b, jnp.asarray(c), jnp.asarray(q)


def test_compaction_parity_frozen_bit_identical():
    b, c, q = _split_difficulty_batch()
    prep = prepare_batch(b.A, b.row_lo, b.row_hi)
    seg = 2560
    sc = PDHGSolver(max_iters=20000, eps=1e-7, compact_threshold=0.9)
    su = sc.clone(compact_threshold=0.0)

    traj = []
    res_c = sc.solve_compacted(prep, c, q, b.lb, b.ub,
                               obj_const=b.obj_const,
                               segment_iters=seg, on_segment=traj.append)
    res_u = su.solve(prep, c, q, b.lb, b.ub, obj_const=b.obj_const)
    assert bool(np.all(np.asarray(res_c.converged)))
    assert bool(np.all(np.asarray(res_u.converged)))

    # compaction must actually have happened and widths never grow
    widths = [t["width"] for t in traj]
    assert widths[-1] < b.num_scens
    assert widths == sorted(widths, reverse=True)
    assert all(w == width_bucket(w) for w in widths)

    # scenarios frozen in segment 1 (before any compaction) are
    # BIT-identical to the uncompacted solve: they converged at the
    # same KKT check, with x_best pinned from the same iterate
    probe = su.solve(prep, c, q, b.lb, b.ub, obj_const=b.obj_const,
                     iters_cap=jnp.asarray(seg, jnp.int32))
    frozen = np.asarray(probe.converged)
    assert frozen[4:].all()      # the plain-LP half converges early
    assert not frozen.all()      # ...and the prox-heavy half survives
    for f in ("x", "y", "obj", "pres", "dres", "gap"):
        a = np.asarray(getattr(res_c, f))[frozen]
        u = np.asarray(getattr(res_u, f))[frozen]
        assert np.array_equal(a, u), f
    # survivors agree within the KKT tolerance (restart average and
    # omega re-seed each segment, so bitwise equality is not expected)
    assert np.allclose(np.asarray(res_c.obj), np.asarray(res_u.obj),
                       rtol=1e-5, atol=1e-5)


def test_compaction_disabled_is_plain_solve():
    b = farmer.build_batch(4)
    prep = prepare_batch(b.A, b.row_lo, b.row_hi)
    s = PDHGSolver(max_iters=20000, eps=1e-7)   # compact_threshold=0
    ra = s.solve_compacted(prep, b.c, b.qdiag, b.lb, b.ub,
                           obj_const=b.obj_const)
    rb = s.solve(prep, b.c, b.qdiag, b.lb, b.ub, obj_const=b.obj_const)
    assert np.array_equal(np.asarray(ra.x), np.asarray(rb.x))


def test_compaction_skips_padding_scenarios():
    """prob=0 padding rows (ir.pad_scenarios) never count as active:
    a batch whose real rows all converge ends without spinning on the
    padding."""
    b, c, q = _split_difficulty_batch()
    prep = prepare_batch(b.A, b.row_lo, b.row_hi)
    probs = np.array([0.25, 0.25, 0.25, 0.25, 0.0, 0.0, 0.0, 0.0])
    s = PDHGSolver(max_iters=20000, eps=1e-7, compact_threshold=0.9)
    traj = []
    res = s.solve_compacted(prep, c, q, b.lb, b.ub, obj_const=b.obj_const,
                            probs=probs, segment_iters=640,
                            on_segment=traj.append)
    # real rows (the prox-heavy half) all converged...
    assert bool(np.all(np.asarray(res.converged)[:4]))
    # ...and the driver stopped on active==0 without burning max_iters
    # on the prob-0 LPs
    assert traj[-1]["active"] == 0
    assert int(res.iters) < s.max_iters


def test_gather_prep_keeps_shared_leaves():
    """Shared-A preps broadcast with leading dim 1; _gather_prep must
    gather only per-scenario leaves (the take() rule)."""
    b = farmer.build_batch(6)
    prep = prepare_batch(b.A, b.row_lo, b.row_hi)
    ii = jnp.asarray([1, 4], jnp.int32)
    g = _gather_prep(prep, ii)
    assert g.A.shape[0] == 2 and g.anorm.shape == (2,)
    shared = prep.__class__(
        A=prep.A, row_lo=prep.row_lo, row_hi=prep.row_hi,
        d_row=prep.d_row[:1], d_col=prep.d_col[:1], anorm=prep.anorm)
    g2 = _gather_prep(shared, ii)
    assert g2.d_row.shape[0] == 1        # untouched broadcast leaf
    assert g2.row_lo.shape[0] == 2


# --------------------------------------------------------------------------
# AST trace-safety guard
# --------------------------------------------------------------------------

def _is_static_expr(node):
    """Expression whose value is fixed at TRACE time: constants,
    self.* config attributes, isinstance() checks, and .shape reads."""
    if isinstance(node, ast.Constant):
        return True
    if isinstance(node, ast.Name):
        return node.id in ("self", "SplitA")
    if isinstance(node, ast.Attribute):
        return node.attr == "shape" or _is_static_expr(node.value)
    if isinstance(node, ast.Subscript):
        return _is_static_expr(node.value)
    if isinstance(node, ast.Call):
        return (isinstance(node.func, ast.Name)
                and node.func.id in ("isinstance", "len", "getattr",
                                     "int", "max"))
    return False


def _is_static_test(node):
    if isinstance(node, ast.BoolOp):
        return all(_is_static_test(v) for v in node.values)
    if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.Not):
        return _is_static_test(node.operand)
    if isinstance(node, ast.Compare):
        # identity tests (x is None) are Python-level, never traced
        if all(isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops):
            return True
        return (_is_static_expr(node.left)
                and all(_is_static_expr(c) for c in node.comparators))
    return _is_static_expr(node)


def test_solve_impl_loop_body_is_trace_safe():
    """Guard: every Python `if` inside PDHGSolver._solve_impl branches
    on trace-time-static state only (config attributes, None-ness of
    optional args, shapes/types) — a Python `if` on a traced value
    would raise TracerBoolConversionError at best and silently bake in
    one branch at worst.  Traced branching must use jnp.where /
    lax.cond / lax.switch."""
    import mpisppy_tpu.ops.pdhg as mod

    src = open(mod.__file__).read()
    tree = ast.parse(src)
    impl = None
    for node in ast.walk(tree):
        if isinstance(node, ast.ClassDef) and node.name == "PDHGSolver":
            for f in node.body:
                if isinstance(f, ast.FunctionDef) \
                        and f.name == "_solve_impl":
                    impl = f
    assert impl is not None, "PDHGSolver._solve_impl not found"
    bad = [n.lineno for n in ast.walk(impl)
           if isinstance(n, ast.If) and not _is_static_test(n.test)]
    assert not bad, (
        f"Python `if` on possibly-traced values in _solve_impl at "
        f"lines {bad} of {mod.__file__}; use jnp.where/lax.cond")
    # the checker itself must reject a traced-value branch
    neg = ast.parse("if score_cand > 1.0:\n    pass").body[0]
    assert not _is_static_test(neg.test)
