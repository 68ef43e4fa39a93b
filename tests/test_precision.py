"""Mixed-precision PDHG tests (hot_dtype / promotion / SparseSplitA).

Covers: knob plumbing (from_options, MPISPPY_TPU_PDHG overlay, clone /
config_key non-aliasing), the eps-floor promotion rule and its
monotonicity, f32-vs-f64 verdict parity on the model corpus, bf16
storage with f32 accumulation on every operator form, BCOO matvec
parity against the dense SplitA path at several densities, the
SPOpt/PH promotion driver (accounting, prep dtypes, checkpointed
`promoted` flag with pre-PR-6 back-compat), the solver statistics,
the AST guard that pins every certified/EF/MIP-dive solver clone to
hot_dtype=None, and serve bucket-key non-aliasing.

Timing waiver: the ISSUE-6 >=1.5x hot-loop speedup is asserted on
accelerators only.  On CPU, f32 storage does not reliably beat the
x64 pipeline (XLA:CPU vectorizes both; memory traffic, not flops,
dominates at corpus sizes), so the CPU measurement is informational —
see doc/src/pdhg.md "Mixed-precision hot loop".
"""

import ast

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mpisppy_tpu.ir import (SparseSplitA, SplitA, bmatvec, bmatvec_t,
                            matvec_form, shared_density, sparsify_split)
from mpisppy_tpu.models import apl1p, farmer, netdes, uc
from mpisppy_tpu.ops.pdhg import PDHGSolver, eps_floor, prepare_batch, \
    prepare_split_native
from mpisppy_tpu.opt.ph import PH
from mpisppy_tpu.resilience.checkpoint import (load_run_checkpoint,
                                               save_run_checkpoint)

pytestmark = pytest.mark.precision

F32_FLOOR = 100.0 * float(jnp.finfo(jnp.float32).eps)


# --------------------------------------------------------------------------
# knob plumbing
# --------------------------------------------------------------------------

def test_from_options_maps_precision_knobs():
    s = PDHGSolver.from_options({"pdhg_hot_dtype": "f32",
                                 "pdhg_sparse_threshold": 0.3})
    assert s.hot_dtype == "f32"
    assert s.sparse_threshold == 0.3
    # defaults: full precision, always-dense
    d = PDHGSolver.from_options({})
    assert d.hot_dtype is None
    assert d.sparse_threshold == 0.0


def test_hot_dtype_normalization_and_rejection():
    # every "off" spelling lands on None (the historical behavior)
    for off in (None, "", "none", "off", "f64", "float64"):
        assert PDHGSolver(hot_dtype=off).hot_dtype is None
    assert PDHGSolver(hot_dtype="bf16x").hot_dtype == "bf16x"
    with pytest.raises(ValueError, match="hot_dtype"):
        PDHGSolver(hot_dtype="f16")


def test_env_overlay_wins_precision(monkeypatch):
    monkeypatch.setenv("MPISPPY_TPU_PDHG",
                       "hot_dtype=f32 pdhg_sparse_threshold=0.25")
    s = PDHGSolver.from_options({"pdhg_hot_dtype": "off",
                                 "pdhg_sparse_threshold": 0.0})
    assert s.hot_dtype == "f32"          # env wins over the dict
    assert s.sparse_threshold == 0.25    # prefixed key accepted too


def test_clone_and_config_key_cover_precision_knobs():
    s = PDHGSolver(hot_dtype="f32", sparse_threshold=0.3)
    c = s.clone(max_iters=77)
    assert c.hot_dtype == "f32" and c.sparse_threshold == 0.3
    # the new knobs are IN the key (configs must never alias in caches)
    assert s.config_key() != s.clone(hot_dtype=None).config_key()
    assert s.config_key() != s.clone(hot_dtype="bf16x").config_key()
    assert s.config_key() != s.clone(sparse_threshold=0.0).config_key()
    # the certified/dive clone idiom drops ONLY the hot dtype
    f = s.clone(hot_dtype=None)
    assert f.hot_dtype is None and f.sparse_threshold == 0.3


# --------------------------------------------------------------------------
# eps floor + promotion rule
# --------------------------------------------------------------------------

def test_eps_floor_and_promotion_monotone():
    s = PDHGSolver(hot_dtype="f32")
    assert s.hot_eps_floor() == pytest.approx(F32_FLOOR)
    assert eps_floor("float32") == pytest.approx(F32_FLOOR)
    assert not s.wants_promotion(1e-4)
    assert s.wants_promotion(1e-6)
    # bf16x ACCUMULATES in f32, so its floor is f32's, not bf16's
    assert PDHGSolver(hot_dtype="bf16x").hot_eps_floor() \
        == pytest.approx(F32_FLOOR)
    full = PDHGSolver()
    assert full.hot_eps_floor() == 0.0
    assert not full.wants_promotion(1e-12)
    # monotone along the eps ladder: once True, tighter eps stays True
    wants = [s.wants_promotion(e)
             for e in (1e-3, 1e-4, 1e-5, 1e-6, 1e-8)]
    assert wants == sorted(wants)
    assert wants[-1]


def test_hot_pair_never_upcasts():
    s = PDHGSolver(hot_dtype="f32")
    assert s._hot_pair(jnp.float64) == (jnp.dtype("float32"),) * 2
    assert s._hot_pair(jnp.float32) is None      # no-op downcast
    b = PDHGSolver(hot_dtype="bf16x")
    assert b._hot_pair(jnp.float32) \
        == (jnp.dtype(jnp.bfloat16), jnp.dtype("float32"))
    assert PDHGSolver()._hot_pair(jnp.float64) is None


# --------------------------------------------------------------------------
# f32-vs-f64 verdict parity on the model corpus
# --------------------------------------------------------------------------

def _corpus():
    return [farmer.build_batch(8), netdes.build_batch(4),
            apl1p.build_batch()]


def test_hot_f32_matches_f64_verdicts_on_corpus():
    """At a tolerance above the f32 floor the hot loop must reach the
    SAME convergence verdicts as the f64 loop, matching objectives,
    with the result still in the caller's dtype."""
    for b in _corpus():
        prep = prepare_batch(b.A, b.row_lo, b.row_hi)
        base = PDHGSolver(max_iters=100000, eps=1e-4)
        hot = base.clone(hot_dtype="f32")
        r64 = base.solve(prep, b.c, b.qdiag, b.lb, b.ub,
                         obj_const=b.obj_const)
        r32 = hot.solve(prep, b.c, b.qdiag, b.lb, b.ub,
                        obj_const=b.obj_const)
        v64 = np.asarray(r64.converged)
        v32 = np.asarray(r32.converged)
        assert bool(np.all(v64)) and bool(np.all(v32))
        np.testing.assert_array_equal(v32, v64)
        # residuals certified against FULL-precision data in the
        # caller's dtype (the final KKT recheck in _solve_impl)
        assert np.all(np.asarray(r32.pres) < 1e-4)
        assert np.asarray(r32.x).dtype == np.asarray(r64.x).dtype
        np.testing.assert_allclose(np.asarray(r32.obj),
                                   np.asarray(r64.obj),
                                   rtol=1e-3, atol=1e-3)


def test_hot_loop_speedup_or_cpu_waiver():
    """ISSUE-6 acceptance: >=1.5x fewer hot-loop seconds under hot f32,
    asserted on accelerators.  CPU runs measure but do not assert (see
    module docstring + doc/src/pdhg.md for the documented waiver)."""
    import time

    b = farmer.build_batch(64)
    prep = prepare_batch(b.A, b.row_lo, b.row_hi)
    args = (prep, b.c, b.qdiag, b.lb, b.ub)
    kw = {"obj_const": b.obj_const}
    secs = {}
    for tag, s in (("f64", PDHGSolver(max_iters=100000, eps=1e-4)),
                   ("f32", PDHGSolver(max_iters=100000, eps=1e-4,
                                      hot_dtype="f32"))):
        r = s.solve(*args, **kw)               # compile warmup
        jax.block_until_ready(r.x)
        t0 = time.perf_counter()
        r = s.solve(*args, **kw)
        jax.block_until_ready(r.x)
        secs[tag] = time.perf_counter() - t0
        assert bool(np.all(np.asarray(r.converged))), tag
    if jax.default_backend() != "cpu":
        assert secs["f64"] / secs["f32"] >= 1.5, secs


def _operator(form):
    """A small batch and its prep with the operator in `form`."""
    if form == "dense":
        b = farmer.build_batch(8, split=False)
        return b, prepare_batch(b.A, b.row_lo, b.row_hi)
    if form == "split":
        b = farmer.build_batch(8, split=True)
        return b, prepare_split_native(b.A, b.row_lo, b.row_hi)
    b = uc.build_batch(4, H=4)
    return b, prepare_batch(b.A, b.row_lo, b.row_hi)


@pytest.mark.parametrize("form", ["dense", "split", "shared"])
def test_bf16x_stores_bf16_accumulates_f32(form):
    """hot_dtype="bf16x" on the XLA loop: the operator enters the
    while_loop in bfloat16 (_cast_A), every other float the loop reads
    or carries is float32, the SolveResult comes back in the caller's
    dtype, and the verdicts match the f32 run on the same batch."""
    b, prep = _operator(form)
    assert matvec_form(prep.A) == form
    base = PDHGSolver(max_iters=100000, eps=1e-4)
    hot = base.clone(hot_dtype="bf16x")
    x0, y0 = jnp.zeros_like(b.c), jnp.zeros_like(b.row_lo)
    jx = jax.make_jaxpr(hot._solve_impl)(
        prep, b.c, b.qdiag, b.lb, b.ub, b.obj_const, x0, y0)
    loops = [e for e in jx.jaxpr.eqns if e.primitive.name == "while"]
    assert len(loops) == 1
    e = loops[0]
    n = e.params["cond_nconsts"] + e.params["body_nconsts"]
    consts = [v.aval for v in e.invars[:n]]
    carry = [v.aval for v in e.invars[n:]]
    op_shapes = sorted(a.shape for a in jax.tree.leaves(prep.A)
                       if jnp.issubdtype(a.dtype, jnp.floating))
    assert sorted(a.shape for a in consts
                  if a.dtype == jnp.bfloat16) == op_shapes
    floats = [a for a in consts + carry
              if jnp.issubdtype(a.dtype, jnp.floating)
              and a.dtype != jnp.bfloat16]
    assert floats and all(a.dtype == jnp.float32 for a in floats)
    assert all(a.dtype != jnp.bfloat16 for a in carry)

    r16 = hot.solve(prep, b.c, b.qdiag, b.lb, b.ub, obj_const=b.obj_const)
    r32 = base.clone(hot_dtype="f32").solve(
        prep, b.c, b.qdiag, b.lb, b.ub, obj_const=b.obj_const)
    for f in ("x", "y", "obj", "dual_obj", "pres"):
        assert getattr(r16, f).dtype == b.c.dtype, f
    v16 = np.asarray(r16.converged)
    assert bool(np.all(v16))
    np.testing.assert_array_equal(v16, np.asarray(r32.converged))
    # bf16 holds A to ~4e-3 relative: the loop solves a perturbed
    # operator, whose optimum the final check accepts at eps
    np.testing.assert_allclose(np.asarray(r16.obj), np.asarray(r32.obj),
                               rtol=2e-2)


# --------------------------------------------------------------------------
# SparseSplitA parity vs the dense SplitA path
# --------------------------------------------------------------------------

def _random_split(S=3, M=24, N=16, density=0.1, seed=0):
    rng = np.random.default_rng(seed)
    sh = rng.normal(size=(M, N)) * (rng.random((M, N)) < density)
    nnz = 5
    rows = rng.integers(0, M, nnz).astype(np.int32)
    cols = rng.integers(0, N, nnz).astype(np.int32)
    sh[rows, cols] = 0.0        # SplitA contract: shared 0 at deltas
    vals = rng.normal(size=(S, nnz))
    return SplitA(shared=jnp.asarray(sh), rows=jnp.asarray(rows),
                  cols=jnp.asarray(cols), vals=jnp.asarray(vals))


@pytest.mark.parametrize("density", [0.01, 0.1, 0.3])
def test_sparse_split_matvec_parity(density):
    Ad = _random_split(density=density)
    As = sparsify_split(Ad, threshold=0.99)
    assert isinstance(As, SparseSplitA)
    S, M, N = Ad.shape
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(S, N)))
    y = jnp.asarray(rng.normal(size=(S, M)))
    np.testing.assert_allclose(np.asarray(bmatvec(As, x)),
                               np.asarray(bmatvec(Ad, x)),
                               rtol=1e-12, atol=1e-12)
    np.testing.assert_allclose(np.asarray(bmatvec_t(As, y)),
                               np.asarray(bmatvec_t(Ad, y)),
                               rtol=1e-12, atol=1e-12)
    np.testing.assert_allclose(np.asarray(As.to_dense()),
                               np.asarray(Ad.to_dense()),
                               rtol=0, atol=0)
    assert As.shared_nnz_frac == pytest.approx(shared_density(Ad))


def test_sparsify_split_gating_and_astype():
    Ad = _random_split(density=0.5)
    assert sparsify_split(Ad, 0.0) is Ad       # knob off
    assert sparsify_split(Ad, None) is Ad
    assert sparsify_split(Ad, 0.2) is Ad       # density above threshold
    dense = jnp.ones((2, 3, 4))
    assert sparsify_split(dense, 0.9) is dense  # not a SplitA
    As = sparsify_split(_random_split(density=0.1), 0.99)
    assert sparsify_split(As, 0.99) is As      # already sparse
    # astype preserves the subclass AND the coordinate structure (this
    # is what lets the mixed-precision storage cast ride through)
    A32 = As.astype(jnp.float32)
    assert isinstance(A32, SparseSplitA)
    assert A32.shared.data.dtype == jnp.float32
    assert A32.vals.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(A32.to_dense()),
                               np.asarray(As.to_dense()), rtol=1e-6)


# --------------------------------------------------------------------------
# SPOpt/PH promotion driver
# --------------------------------------------------------------------------

OPTS = {"defaultPHrho": 1.0, "PHIterLimit": 6, "convthresh": 1e-6}


def _ph(extra):
    return PH(dict(OPTS, **extra), [f"s{i}" for i in range(4)],
              batch=farmer.build_batch(4))


def test_active_solver_prep_promotes_below_floor():
    ph = _ph({"pdhg_hot_dtype": "f32", "pdhg_eps": 1e-4})
    # hot prep carries low-precision data; farmer's is split
    assert str(ph.prep.A.dtype) == "float32"
    s0, p0 = ph.active_solver_prep(1e-4)
    assert s0 is ph.solver and p0 is ph.prep
    assert ph.pdhg_stats()["promotions_total"] == 0
    s1, p1 = ph.active_solver_prep(1e-6)
    assert s1 is not ph.solver and s1.hot_dtype is None
    assert str(p1.A.dtype) == "float64"
    assert ph.pdhg_stats()["promotions_total"] == 1
    # the pair is cached; each promoted SOLVE is counted
    s2, p2 = ph.active_solver_prep(1e-6)
    assert s2 is s1 and p2 is p1
    assert ph.pdhg_stats()["promotions_total"] == 2
    # probes (count=False) never skew the accounting
    ph.active_solver_prep(1e-6, count=False)
    assert ph.pdhg_stats()["promotions_total"] == 2
    ph.reset_solve_stats()
    assert ph.pdhg_stats()["promotions_total"] == 0


def test_ph_hot_run_stays_hot_above_floor():
    """Supersteps at eps above the f32 floor never promote and the
    objective matches the f64 run."""
    ph_h = _ph({"pdhg_hot_dtype": "f32", "pdhg_eps": 1e-4})
    conv_h, eobj_h, _ = ph_h.ph_main()
    ph_f = _ph({"pdhg_eps": 1e-4})
    conv_f, eobj_f, _ = ph_f.ph_main()
    assert eobj_h == pytest.approx(eobj_f, rel=1e-3)
    st = ph_h.pdhg_stats()
    assert st["hot_dtype"] == "f32"
    assert st["promotions_total"] == 0
    assert int(ph_h.state.promoted) == 0


def test_ph_hot_run_promotes_below_floor():
    """A superstep tolerance below the f32 floor routes every solve to
    the promoted full-precision pair: the state records it, the
    accounting counts it, and the objective matches full precision."""
    ph_h = _ph({"pdhg_hot_dtype": "f32", "superstep_eps": 1e-6,
                "pdhg_eps": 1e-6, "PHIterLimit": 4})
    conv_h, eobj_h, _ = ph_h.ph_main()
    assert int(ph_h.state.promoted) == 1
    assert ph_h.pdhg_stats()["promotions_total"] >= 1
    ph_f = _ph({"superstep_eps": 1e-6, "pdhg_eps": 1e-6,
                "PHIterLimit": 4})
    conv_f, eobj_f, _ = ph_f.ph_main()
    assert eobj_h == pytest.approx(eobj_f, rel=1e-9)


def test_spopt_sparse_prep_counts_matvecs():
    # farmer's shared block density (~0.21) sits under the threshold
    ph = _ph({"pdhg_sparse_threshold": 0.3, "pdhg_eps": 1e-5})
    assert isinstance(ph.prep.A, SparseSplitA)
    st = ph.pdhg_stats()
    assert st["shared_nnz_frac"] == pytest.approx(
        float(ph.prep.A.shared_nnz_frac))
    conv, eobj, _ = ph.ph_main()
    assert ph.pdhg_stats()["sparse_matvecs"] > 0
    # dense reference: same objective, zero sparse matvecs
    ph_d = _ph({"pdhg_eps": 1e-5})
    conv_d, eobj_d, _ = ph_d.ph_main()
    assert eobj == pytest.approx(eobj_d, rel=1e-6)
    assert ph_d.pdhg_stats()["sparse_matvecs"] == 0


# --------------------------------------------------------------------------
# checkpoint: promoted flag + pre-PR-6 back-compat
# --------------------------------------------------------------------------

def test_checkpoint_promoted_roundtrip_and_pre_pr6_backcompat(tmp_path):
    ph = _ph({"pdhg_hot_dtype": "f32", "superstep_eps": 1e-6,
              "pdhg_eps": 1e-6, "PHIterLimit": 2})
    ph.ph_main(finalize=False)
    assert int(ph.state.promoted) == 1
    real = save_run_checkpoint(str(tmp_path / "prec.ckpt"), ph)
    fresh = _ph({"pdhg_hot_dtype": "f32", "superstep_eps": 1e-6,
                 "pdhg_eps": 1e-6, "PHIterLimit": 2})
    fresh.Iter0()
    load_run_checkpoint(real, fresh)
    assert int(fresh.state.promoted) == 1
    # pre-PR-6 checkpoint: strip the precision fields entirely — loads
    # must default to the f64-era values (promoted=0), not KeyError
    z = dict(np.load(real, allow_pickle=True))
    for k in ("promoted", "ladder_eps"):
        z.pop(k)
    old = str(tmp_path / "old_format.npz")
    with open(old, "wb") as f:
        np.savez(f, **z)
    older = _ph({"pdhg_hot_dtype": "f32", "superstep_eps": 1e-6,
                 "pdhg_eps": 1e-6, "PHIterLimit": 2})
    older.Iter0()
    load_run_checkpoint(old, older)
    assert int(older.state.promoted) == 0
    # the rest of the state restored identically either way
    np.testing.assert_allclose(np.asarray(older.state.W),
                               np.asarray(fresh.state.W))


# --------------------------------------------------------------------------
# AST guard: certified/EF/MIP-dive paths pin hot_dtype=None
# --------------------------------------------------------------------------

def _clone_calls(modname, funcname):
    import importlib
    mod = importlib.import_module(modname)
    tree = ast.parse(open(mod.__file__).read())
    fn = None
    for node in ast.walk(tree):
        if isinstance(node, ast.FunctionDef) and node.name == funcname:
            fn = node
    assert fn is not None, f"{funcname} not found in {modname}"
    calls = [n for n in ast.walk(fn) if isinstance(n, ast.Call)
             and isinstance(n.func, ast.Attribute)
             and n.func.attr == "clone"]
    assert calls, f"no solver.clone() call in {modname}.{funcname}"
    return calls


@pytest.mark.parametrize("modname,funcname", [
    ("mpisppy_tpu.spopt", "_certified_resolve"),
    ("mpisppy_tpu.spopt", "_promoted_pair"),
    ("mpisppy_tpu.opt.ef", "_certified_ef_resolve"),
    ("mpisppy_tpu.opt.mip", "_dive_solver"),
])
def test_certified_paths_pin_full_precision(modname, funcname):
    """Guard: every solver clone on a bound-certifying path (certified
    KKT re-solve, EF authority solve, MIP dive probes) carries an
    explicit hot_dtype=None keyword — these solves feed verdicts and
    bound decisions and must NEVER run sub-f64, no matter what hot
    dtype the parent solver was configured with."""
    for call in _clone_calls(modname, funcname):
        kw = {k.arg: k.value for k in call.keywords}
        assert "hot_dtype" in kw, (
            f"{modname}.{funcname}: clone() without explicit "
            f"hot_dtype at line {call.lineno}")
        node = kw["hot_dtype"]
        assert isinstance(node, ast.Constant) and node.value is None, (
            f"{modname}.{funcname}: clone(hot_dtype=...) must be the "
            f"literal None at line {call.lineno}")


# --------------------------------------------------------------------------
# serve: precision knobs must split compile-cache buckets
# --------------------------------------------------------------------------

def test_bucket_key_distinguishes_precision_configs():
    """serve builds ONE canonical solver per bucket from the request
    options and never routes through active_solver_prep, so promotion
    cannot thrash buckets — but two configs that differ only in the
    precision knobs must land in different buckets."""
    from mpisppy_tpu.serve.compile_cache import bucket_key

    b = farmer.build_batch(4)
    k0 = bucket_key(b, options={})
    kh = bucket_key(b, options={"pdhg_hot_dtype": "f32"})
    kb = bucket_key(b, options={"pdhg_hot_dtype": "bf16x"})
    ks = bucket_key(b, options={"pdhg_sparse_threshold": 0.3})
    assert len({k0, kh, kb, ks}) == 4


# --------------------------------------------------------------------------
# solver statistics
# --------------------------------------------------------------------------

@pytest.mark.parametrize("path", ["solve_loop", "superstep"])
def test_solve_stats_after_accounting_cut(path):
    """pdhg_stats counts every inner iteration the solves report, and
    solve_stats carries only the wall-clock times."""
    ph = _ph({"pdhg_eps": 1e-4})
    iters = 0
    if path == "solve_loop":
        for _ in range(3):
            iters += int(ph.solve_loop().iters)
    else:
        ph.Iter0()
        ph.reset_solve_stats()
        for _ in range(3):
            ph.ph_iteration()
            iters += int(ph.state.solve_iters)
    assert iters > 0
    assert ph.pdhg_stats()["inner_iters"] == iters
    stats = ph.solve_stats()
    assert set(stats) == {"solve_wall_s", "certify_wall_s"}
    assert stats["solve_wall_s"] > 0.0
    assert stats["certify_wall_s"] >= 0.0
