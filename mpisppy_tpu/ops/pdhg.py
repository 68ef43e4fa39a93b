"""Batched first-order LP/QP solver (restarted PDHG, PDLP/MPAX family).

This kernel is the TPU-native replacement for the reference's
out-of-process Gurobi/CPLEX/Xpress calls (reference: mpisppy/spopt.py:85
`solve_one`, :839 `_create_solvers`) — SURVEY.md §2.9.  One scenario =
one batch element; all matvecs are batched (S, M, N) x (S, N) einsums
that land on the MXU; the whole solve is one `lax.while_loop` under
`jit`, so PH's solve_loop becomes a single fused XLA computation instead
of N sequential solver processes.

Problem form (per scenario):

    minimize    c @ x + 0.5 * qdiag @ (x*x)
    subject to  row_lo <= A @ x <= row_hi
                lb <= x <= ub

qdiag >= 0 (diagonal QP — exactly what PH's proximal term produces,
reference phbase.py:617 attach_PH_to_objective).

Method: Chambolle-Pock / Condat-Vu primal-dual iterations with
  * Ruiz equilibration of A (done once per batch in `prepare_batch`),
  * step sizes from a power-iteration estimate of ||A||_2,
  * KKT-progress-triggered ADAPTIVE restarts to the better of
    {current, running average} (the PDLP/MPAX trigger: restart on
    sufficient KKT-score decay or on necessary-decay-plus-stagnation,
    per scenario, with the fixed cadence kept as both the forced
    cycle-length cap and a documented fallback mode —
    `restart_mode="fixed"`),
  * primal-weight (omega) rebalancing at restarts,
  * per-scenario convergence freezing, and (opt-in) host-driven
    COMPACTION of the surviving scenarios into smaller power-of-two
    width buckets once most of the batch has converged
    (`solve_compacted`), so late iterations stop paying matvec FLOPs
    and HBM bandwidth for frozen scenarios.

Termination mirrors PDLP's relative KKT criterion.  Duals: `y` are the
row multipliers; `reduced costs` follow from c + qdiag*x + A^T y, giving
the Lagrangian-bound machinery its inputs (reference
cylinders/lagrangian_bounder.py) for free — see `dual_objective`.
"""

from __future__ import annotations

import dataclasses
import os as _os
import threading as _threading
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

# one shared-A broadcast dispatch rule for the whole package: the
# SA == 1 fast path turns the batched matvec into a real matmul
from ..ir import SplitA
from ..ir import bmatvec as _Ax
from ..ir import bmatvec_t as _ATy

# hot_dtype knob -> (storage dtype, compute dtype) for the inner loop.
#   f32:   everything in float32 — the MPAX/PDLP trade: the hot loop
#          runs ~2x+ faster (CPU SIMD width / MXU rate / HBM traffic)
#          while the certified bound paths stay f64;
#   bf16x: A stored in bfloat16 (halves the constraint tensor's HBM
#          traffic — the dominant bandwidth term), iterates and
#          accumulation in float32 (bf16 @ f32 dot_generals accumulate
#          in f32).
# The knob NEVER upcasts: an f32 batch under hot_dtype="f32" is a
# no-op, and the final KKT verdict + unscaled SolveResult are always
# produced in the CALLER's dtype (see _solve_impl).
HOT_DTYPES = {
    "f32": ("float32", "float32"),
    "bf16x": ("bfloat16", "float32"),
}


def eps_floor(dtype):
    """Tightest tolerance `dtype` arithmetic can express: below
    ~100 ulp the KKT residuals are rounding noise and the loop would
    spin to max_iters (the clamp `_solve_impl` has always applied,
    exposed for the promotion rule)."""
    return 100.0 * float(jnp.finfo(jnp.dtype(dtype)).eps)


def _register(cls, data_fields, meta_fields=()):
    jax.tree_util.register_dataclass(
        cls, data_fields=data_fields, meta_fields=meta_fields)
    return cls


@dataclasses.dataclass(frozen=True)
class PreparedBatch:
    """Scaled constraint data, computed once per ScenarioBatch."""
    A: Any        # (S, M, N) scaled: D_r @ A @ D_c
    row_lo: Any   # (S, M) scaled: D_r * row_lo
    row_hi: Any   # (S, M)
    d_row: Any    # (S, M) row scaling D_r
    d_col: Any    # (S, N) col scaling D_c
    anorm: Any    # (S,) ||A_scaled||_2 estimate


_register(PreparedBatch,
          ("A", "row_lo", "row_hi", "d_row", "d_col", "anorm"))


@dataclasses.dataclass(frozen=True)
class ConsensusSpec:
    """Nonanticipativity structure for EXACT extensive-form solves.

    With a spec, the solver treats each (tree node, nonant slot) as ONE
    shared variable broadcast to its member scenarios: the primal
    gradient is segment-summed over node members before the update (the
    adjoint of the broadcast), so the batched iteration solves the
    monolithic EF — the TPU-native analog of the reference's
    `_create_EF_from_scen_dict` nonant equality constraints
    (reference sputils.py:308-336) without ever materializing the big
    matrix.  Requires prepare_batch(shared_cols=True).
    """
    node_of: Any      # (S, K) node id per scenario per nonant slot
    nonant_idx: Any   # (K,) column indices of nonant slots
    num_nodes: int = 1
    # number of INDEPENDENT stacked EF copies along the scenario axis
    # (opt/mip._lp_multi probes k bound-variants in one launch): every
    # batch-global reduction — power-iteration norm, step sizes, the
    # one-problem KKT verdict, the restart omega — is taken PER COPY,
    # so a degenerate/infeasible variant cannot pollute its siblings'
    # step sizes or convergence verdicts
    num_copies: int = 1


_register(ConsensusSpec, ("node_of", "nonant_idx"),
          ("num_nodes", "num_copies"))


@dataclasses.dataclass(frozen=True)
class SolveResult:
    x: Any          # (S, N) primal solution (unscaled)
    y: Any          # (S, M) row duals (unscaled)
    obj: Any        # (S,) primal objective (incl. obj_const)
    dual_obj: Any   # (S,) dual objective estimate (incl. obj_const)
    pres: Any       # (S,) relative primal residual (inf-norm)
    dres: Any       # (S,) relative dual residual (inf-norm)
    gap: Any        # (S,) relative primal-dual gap
    converged: Any  # (S,) bool
    iters: Any      # () int - iterations used (max across batch)
    restarts: Any = 0  # (S,) int - restart events per scenario


_register(SolveResult,
          ("x", "y", "obj", "dual_obj", "pres", "dres", "gap",
           "converged", "iters", "restarts"))


# --------------------------------------------------------------------------
# scaling
# --------------------------------------------------------------------------

def _ruiz(A, n_iter=10, eps=1e-12, shared_cols=False):
    """Ruiz equilibration: returns (A_scaled, d_row, d_col) with
    A_scaled = diag(d_row) @ A @ diag(d_col), rows/cols ~unit inf-norm.

    shared_cols: use ONE column scaling across all scenarios (the EF
    matrix's column space) — required by consensus solves, where a
    shared variable must see one consistent scaling."""
    S, M, N = A.shape
    d_row = jnp.ones((S, M), A.dtype)
    d_col = jnp.ones((N,) if shared_cols else (S, N), A.dtype)

    def body(_, carry):
        As, dr, dc = carry
        rmax = jnp.max(jnp.abs(As), axis=2)            # (S, M)
        cmax = jnp.max(jnp.abs(As), axis=(0, 1) if shared_cols else 1)
        sr = jnp.where(rmax <= eps, 1.0,
                       1.0 / jnp.sqrt(jnp.maximum(rmax, eps)))
        sc = jnp.where(cmax <= eps, 1.0,
                       1.0 / jnp.sqrt(jnp.maximum(cmax, eps)))
        sc_b = sc[None, None, :] if shared_cols else sc[:, None, :]
        As = As * sr[:, :, None] * sc_b
        return As, dr * sr, dc * sc

    A, d_row, d_col = lax.fori_loop(0, n_iter, body, (A, d_row, d_col))
    if shared_cols:
        d_col = jnp.broadcast_to(d_col[None, :], (S, N))
    return A, d_row, d_col


def _power_iteration(A, iters=40, seed=0):
    """||A||_2 per scenario via power iteration on A^T A."""
    S, M, N = A.shape
    key = jax.random.PRNGKey(seed)
    v = jax.random.normal(key, (S, N), A.dtype)

    def body(_, v):
        v = v / (jnp.linalg.norm(v, axis=1, keepdims=True) + 1e-30)
        av = _Ax(A, v)
        v = _ATy(A, av)
        return v

    v = lax.fori_loop(0, iters, body, v)
    av = _Ax(A, v / (
        jnp.linalg.norm(v, axis=1, keepdims=True) + 1e-30))
    return jnp.linalg.norm(av, axis=1)


@partial(jax.jit, static_argnames=("ruiz_iters", "shared_cols"))
def prepare_batch(A, row_lo, row_hi, ruiz_iters=10, shared_cols=False):
    """One-time per-batch preprocessing (scale + norm estimate)."""
    As, d_row, d_col = _ruiz(A, n_iter=ruiz_iters, shared_cols=shared_cols)
    anorm = _power_iteration(As)
    return PreparedBatch(
        A=As,
        row_lo=jnp.where(jnp.isfinite(row_lo), row_lo * d_row, row_lo),
        row_hi=jnp.where(jnp.isfinite(row_hi), row_hi * d_row, row_hi),
        d_row=d_row,
        d_col=d_col,
        # floor at 1: after Ruiz scaling a real A has ||A|| >= ~1; an
        # all-zero A (zero-probability padding scenario, ir.pad_scenarios)
        # would otherwise yield ~0 and blow up the step sizes
        anorm=jnp.maximum(anorm, 1.0),
    )


@partial(jax.jit, static_argnames=("ruiz_iters",))
def prepare_batch_split(A, rows, cols, row_lo, row_hi, ruiz_iters=10):
    """prepare_batch for a batch whose matrix uncertainty is confined
    to the (rows, cols) coordinate set (ir.SplitA): A is the DENSE
    (S, M, N) tensor the model built; every entry OUTSIDE the delta set
    must be scenario-independent (the model's declaration via
    model_meta["A_delta_idx"] is the contract — tests pin it).

    Ruiz equilibration here uses ONE row/col scaling shared across
    scenarios (norms taken as the max over scenarios), because a
    per-scenario scaling would destroy the shared+sparse structure:
    D_r(s) (A0 + d(s)) D_c(s) splits only when D_r/D_c are shared.
    Shared scalings also satisfy the consensus solver's shared-column
    requirement (prepare_batch(shared_cols=True)) for free.

    Returns a PreparedBatch whose A is a SplitA and whose d_row/d_col
    are (1, M)/(1, N) — the shared-A broadcasting convention.
    """
    rows = jnp.asarray(rows, jnp.int32)
    cols = jnp.asarray(cols, jnp.int32)
    vals = A[:, rows, cols]                          # (S, nnz)
    A0 = A[0].at[rows, cols].set(0.0)                # (M, N) shared part
    return _prepare_split_core(A0, rows, cols, vals, row_lo, row_hi,
                               ruiz_iters=ruiz_iters)


def prepare_split_native(A: "SplitA", row_lo, row_hi, ruiz_iters=10):
    """prepare_batch_split for a batch born split (ir.ScenarioBatch.A
    IS a SplitA — the only representation at sizes where the dense
    (S, M, N) tensor cannot exist, e.g. true-size farmer)."""
    return _prepare_split_core(
        A.shared, jnp.asarray(A.rows, jnp.int32),
        jnp.asarray(A.cols, jnp.int32), A.vals, row_lo, row_hi,
        ruiz_iters=ruiz_iters)


@partial(jax.jit, static_argnames=("ruiz_iters",))
def _prepare_split_core(A0, rows, cols, vals, row_lo, row_hi,
                        ruiz_iters=10):
    M, N = A0.shape
    A0 = A0.at[rows, cols].set(0.0)   # enforce the zeros-at-delta contract
    eps = 1e-12

    def body(_, carry):
        A0s, vs, dr, dc = carry
        vmax = jnp.max(jnp.abs(vs), axis=0)          # (nnz,) over scens
        rmax = jnp.max(jnp.abs(A0s), axis=1).at[rows].max(vmax)
        cmax = jnp.max(jnp.abs(A0s), axis=0).at[cols].max(vmax)
        sr = jnp.where(rmax <= eps, 1.0,
                       1.0 / jnp.sqrt(jnp.maximum(rmax, eps)))
        sc = jnp.where(cmax <= eps, 1.0,
                       1.0 / jnp.sqrt(jnp.maximum(cmax, eps)))
        A0s = A0s * sr[:, None] * sc[None, :]
        vs = vs * sr[rows] * sc[cols]
        return A0s, vs, dr * sr, dc * sc

    A0s, vs, dr, dc = lax.fori_loop(
        0, ruiz_iters, body,
        (A0, vals, jnp.ones((M,), A0.dtype), jnp.ones((N,), A0.dtype)))
    As = SplitA(shared=A0s, rows=rows, cols=cols, vals=vs)
    anorm = _power_iteration(As)
    d_row = dr[None, :]
    d_col = dc[None, :]
    return PreparedBatch(
        A=As,
        row_lo=jnp.where(jnp.isfinite(row_lo), row_lo * d_row, row_lo),
        row_hi=jnp.where(jnp.isfinite(row_hi), row_hi * d_row, row_hi),
        d_row=d_row,
        d_col=d_col,
        anorm=jnp.maximum(anorm, 1.0),
    )


def _gather_prep(prep: PreparedBatch, ii) -> PreparedBatch:
    """Gather a PreparedBatch down to the scenario rows `ii`.

    Shared-A leaves (leading dim 1, the broadcasting convention of
    prepare_batch_split / shared prep) are NOT gathered — they apply
    to every scenario already; a SplitA gathers only its per-scenario
    delta values.  Used by `PDHGSolver.solve_compacted`."""
    def take(a):
        return a if a.shape[0] == 1 else a[ii]

    A = prep.A
    if isinstance(A, SplitA):
        # replace (not the constructor) so a SparseSplitA stays sparse
        A = dataclasses.replace(A, vals=A.vals[ii])
    else:
        A = take(A)
    return PreparedBatch(
        A=A, row_lo=take(prep.row_lo), row_hi=take(prep.row_hi),
        d_row=take(prep.d_row), d_col=take(prep.d_col),
        anorm=take(prep.anorm))


def reprep_row_bounds(prep: PreparedBatch, row_lo, row_hi) -> PreparedBatch:
    """Rebuild a PreparedBatch's scaled row bounds from new RAW bounds.

    Valid exactly when the constraint operator is UNCHANGED — the Ruiz
    scaling and the norm estimate depend only on A, so a batch whose
    uncertainty lives entirely in the row bounds (shared-A families:
    UC wind) can reuse one prep for every scenario block and pay only
    this O(S*M) rescale per block.  The streaming layer's shared-A
    block path is built on this; `_shift_and_widen_rows` (spopt xhat)
    is the same identity for shifted bounds."""
    return dataclasses.replace(
        prep,
        row_lo=jnp.where(jnp.isfinite(row_lo),
                         row_lo * prep.d_row, row_lo),
        row_hi=jnp.where(jnp.isfinite(row_hi),
                         row_hi * prep.d_row, row_hi))


def _unscale_A(A, dr, dc):
    """User-space view of a scaled constraint operator: A / dr / dc,
    dispatching on representation (dense batched / shared / SplitA /
    SparseSplitA — scale_shared keeps BCOO data in coordinate form)."""
    if isinstance(A, SplitA):
        return dataclasses.replace(
            A,
            shared=A.scale_shared(1.0 / dr[0], 1.0 / dc[0]),
            vals=A.vals / (dr[:, A.rows] * dc[:, A.cols]))
    return A / dr[:, :, None] / dc[:, None, :]


def _cast_A(A, dt):
    """Storage-dtype cast of a constraint operator (SplitA.astype is
    subclass-preserving; dense arrays cast directly)."""
    return A.astype(dt)


# --------------------------------------------------------------------------
# core iteration pieces (all batched over leading S axis)
# --------------------------------------------------------------------------


def _proj_box(x, lb, ub):
    return jnp.clip(x, lb, ub)


def _dual_prox(v, sigma, lo, hi):
    """prox of the support function of [lo, hi]:
    v - sigma * proj_[lo,hi](v / sigma), safe with +-inf bounds."""
    z = v / sigma[..., None]
    zc = jnp.clip(z, lo, hi)
    return v - sigma[..., None] * zc


def _residuals(x, y, c, qdiag, A, row_lo, row_hi, lb, ub, cavg=None):
    """KKT residuals + gap, all relative, inf-norms. Batched.

    Follows the PDLP convention: reduced-cost terms whose matching bound
    is infinite are projected out of the dual objective and charged to
    the dual residual instead.

    cavg: optional consensus averaging fn — replaces each nonant slot's
    reduced cost by (segment sum / member count) so per-scenario sums of
    rc terms equal the shared-variable (EF) dual-objective terms.
    """
    Ax = _Ax(A, x)
    # primal violation of row bounds (box is enforced by projection)
    pviol = jnp.maximum(jnp.maximum(row_lo - Ax, Ax - row_hi), 0.0)
    pviol = jnp.where(jnp.isfinite(pviol), pviol, 0.0)
    rhs_scale = 1.0 + jnp.max(
        jnp.where(jnp.isfinite(row_hi), jnp.abs(row_hi), 0.0)
        + jnp.where(jnp.isfinite(row_lo), jnp.abs(row_lo), 0.0), axis=1)
    pres = jnp.max(pviol, axis=1) / rhs_scale

    # dual: r = grad f + A^T y ; must live in normal cone of the box
    grad = c + qdiag * x
    aty = _ATy(A, y)
    r = grad + aty
    if cavg is not None:
        r = cavg(r)
    # split reduced cost by sign; valid part pairs with a finite bound
    rpos = jnp.maximum(r, 0.0)
    rneg = jnp.minimum(r, 0.0)
    lb_fin = jnp.isfinite(lb)
    ub_fin = jnp.isfinite(ub)
    # dual residual: the part of r that cannot be explained by an active
    # finite bound
    dviol = jnp.where(lb_fin, 0.0, rpos) + jnp.where(ub_fin, 0.0, -rneg)
    # plus stationarity leftover for strictly-interior coords:
    at_lb = x <= lb + 1e-9 * (1 + jnp.abs(lb))
    at_ub = x >= ub - 1e-9 * (1 + jnp.abs(ub))
    interior = ~(at_lb | at_ub)
    dviol = jnp.maximum(dviol, jnp.where(interior, jnp.abs(r), 0.0))
    obj_scale = 1.0 + jnp.max(jnp.abs(c), axis=1)
    dres = jnp.max(dviol, axis=1) / obj_scale

    # objectives
    pobj = jnp.sum(c * x, axis=1) + 0.5 * jnp.sum(qdiag * x * x, axis=1)
    # dual objective (PDLP-style estimate):
    #   g(y) = -0.5 x'Qx - sup_{s in [lo,hi]} y's + sum_j rc_j * (lb or ub)
    # with L = f(x) + y'(Ax) - sup_{s in [lo,hi]} y's, the support term
    # is y_i*hi if y_i>0 else y_i*lo.
    ysup = jnp.where(y > 0,
                     jnp.where(jnp.isfinite(row_hi), y * row_hi, 0.0),
                     jnp.where(jnp.isfinite(row_lo), y * row_lo, 0.0))
    rc = jnp.where(lb_fin, rpos * lb, 0.0) + jnp.where(ub_fin, rneg * ub, 0.0)
    dobj = (-0.5 * jnp.sum(qdiag * x * x, axis=1)
            - jnp.sum(ysup, axis=1)
            + jnp.sum(rc, axis=1))
    gap = jnp.abs(pobj - dobj) / (1.0 + jnp.abs(pobj) + jnp.abs(dobj))
    return pres, dres, gap, pobj, dobj


@dataclasses.dataclass(frozen=True)
class _Carry:
    x: Any
    y: Any
    x_sum: Any           # running sums for the restart average
    y_sum: Any
    nsum: Any            # (S,) count in current restart cycle
    x_last: Any          # iterate at last restart (for omega update)
    y_last: Any
    omega: Any           # (S,) primal weight
    k: Any               # iteration counter (outer checks)
    converged: Any       # (S,) bool
    x_best: Any          # frozen solution for converged scenarios
    y_best: Any
    cycle: Any           # (S,) checks since last restart
    score_restart: Any   # (S,) KKT score of the last restart point
    score_cand_prev: Any  # (S,) candidate score at previous check
    restarts: Any        # (S,) restart events taken


_register(_Carry, tuple(f.name for f in dataclasses.fields(_Carry)))


# Per-THREAD solve-jit registry.  Every cylinder of a wheel (hub +
# spokes) and every serve-layer request builds its own PDHGSolver from
# the same options; a per-instance `jax.jit(self._solve_impl)` would
# give each instance a private trace cache and re-compile the identical
# computation.  `_solve_impl` depends only on the construction-time
# scalars in `config_key()`, so instances with equal config share ONE
# wrapper — jit's own cache then buckets on argument shapes/dtypes
# exactly as before.  The registry is thread-local, NOT process-global,
# and `PDHGSolver._solve_jit` resolves through it at CALL time (a
# property), not at construction: threaded cylinder wheels construct
# every cylinder on the main thread and then dispatch hub and spoke
# solves concurrently from worker threads, and concurrent calls into
# one shared jit wrapper deadlock the dispatch path (observed: all
# threads futex-parked under test_cylinders threaded mode).  Call-time
# per-thread scoping keeps the dedup win inside each thread while
# preserving the pre-registry invariant that no two threads ever race
# one wrapper — whichever thread DRIVES a solver gets (and reuses) its
# own wrapper, regardless of which thread built the solver.
_SOLVE_JIT_TLS = _threading.local()


def shared_solve_jit(solver):
    """The thread-shared jitted `_solve_impl` for `solver`'s config."""
    reg = getattr(_SOLVE_JIT_TLS, "registry", None)
    if reg is None:
        reg = _SOLVE_JIT_TLS.registry = {}
    key = solver.config_key()
    fn = reg.get(key)
    if fn is None:
        fn = jax.jit(solver._solve_impl)
        reg[key] = fn
    return fn


class PDHGSolver:
    """Restarted PDHG solver over a ScenarioBatch.

    Stateless/functional: `solve` is jit-compiled; typical use is through
    SPOpt.solve_loop (opt/spopt.py) which supplies PH-modified
    objectives as plain arrays.
    """

    def __init__(self, max_iters=20000, eps=1e-6, check_every=40,
                 restart_every=16, omega0=1.0, use_pallas="auto",
                 pallas_tile=8, pallas_interpret=False,
                 restart_mode="adaptive", restart_beta_sufficient=0.2,
                 restart_beta_necessary=0.8, compact_threshold=0.0,
                 hot_dtype=None, sparse_threshold=0.0):
        # restart_every is in units of `check_every` inner iterations.
        # Under restart_mode="adaptive" it is the FORCED cycle-length
        # cap (a restart fires at the latest every restart_every
        # checks); under restart_mode="fixed" it is the whole policy.
        # Default 16 (=640 inner iterations per restart cycle):
        # measured on the model corpus, every-4 FIXED restarts CYCLE on
        # degenerate duals (unit commitment: 24/40 scenarios stuck at
        # gap ~1 after 300k iters; at 16 all converge in 12k) and are
        # ~2x slower on farmer; sizes/sslp/netdes/battery are
        # insensitive (within ~2x of their small iteration counts).
        # The adaptive trigger restarts EARLIER than the cap only on
        # evidence of sufficient KKT decay, so it cannot reintroduce
        # that cycling.
        self.max_iters = int(max_iters)
        self.eps = float(eps)
        self.check_every = int(check_every)
        self.restart_every = int(restart_every)
        self.omega0 = float(omega0)
        if restart_mode not in ("adaptive", "fixed"):
            raise ValueError(
                f"restart_mode must be 'adaptive' or 'fixed', "
                f"got {restart_mode!r}")
        self.restart_mode = str(restart_mode)
        self.restart_beta_sufficient = float(restart_beta_sufficient)
        self.restart_beta_necessary = float(restart_beta_necessary)
        # active fraction below which solve_compacted gathers the
        # unconverged survivors into a smaller pow2 width bucket;
        # 0.0 disables compaction (solve_compacted == solve)
        self.compact_threshold = float(compact_threshold)
        if use_pallas == "auto":
            # measured on TPU v5e (farmer-64, crops_mult 4): XLA's
            # fused while_loop beats the Pallas chunk kernel ~100x at
            # these batched-small-matvec shapes — Pallas grid programs
            # serialize over scenario tiles while XLA vectorizes the
            # whole batch.  The kernel stays available (explicitly
            # pass use_pallas=True) for very large per-scenario
            # problems where one scenario fills VMEM.
            use_pallas = False
        self.use_pallas = bool(use_pallas)
        self.pallas_tile = int(pallas_tile)
        self.pallas_interpret = bool(pallas_interpret)
        # mixed-precision hot loop (see HOT_DTYPES): None/f64/off keep
        # the historical behavior — the loop runs in the caller's dtype
        if hot_dtype in (None, "", "none", "off", "f64", "float64"):
            hot_dtype = None
        elif hot_dtype not in HOT_DTYPES:
            raise ValueError(
                f"hot_dtype must be one of {sorted(HOT_DTYPES)} (or "
                f"None/'f64' for full precision), got {hot_dtype!r}")
        self.hot_dtype = hot_dtype
        # shared-block density below which a SplitA prep is stored /
        # multiplied as BCOO (ir.SparseSplitA); 0.0 = always dense
        self.sparse_threshold = float(sparse_threshold)

    @property
    def _solve_jit(self):
        # resolved per CALLING thread (see _SOLVE_JIT_TLS above): the
        # thread that runs the solve owns the wrapper, never a thread
        # that merely constructed the solver
        return shared_solve_jit(self)

    @classmethod
    def from_options(cls, options):
        """Build a solver from an SPBase-style options dict (the pdhg_*
        keys).  The one place the option names/defaults are mapped —
        SPOpt and the serve layer's compile cache both route through
        here so a request's bucket is keyed on the exact solver config
        the in-process optimizer would use.

        The MPISPPY_TPU_PDHG environment variable overlays the dict
        (env wins, matching the chaos/telemetry layering): a
        space-separated key=value string of pdhg knobs with or without
        the pdhg_ prefix, e.g.
        ``MPISPPY_TPU_PDHG="restart_mode=fixed compact_threshold=0.25"``.
        """
        o = dict(options or {})
        env = _os.environ.get("MPISPPY_TPU_PDHG")
        if env:
            from ..utils.solver_spec import option_string_to_dict
            for k, v in (option_string_to_dict(env) or {}).items():
                o[k if k.startswith("pdhg_") else f"pdhg_{k}"] = v
        return cls(
            max_iters=int(o.get("pdhg_max_iters", 20000)),
            eps=float(o.get("pdhg_eps", 1e-6)),
            check_every=int(o.get("pdhg_check_every", 40)),
            restart_every=int(o.get("pdhg_restart_every", 16)),
            use_pallas=o.get("pdhg_use_pallas", "auto"),
            pallas_tile=int(o.get("pdhg_pallas_tile", 8)),
            pallas_interpret=bool(o.get("pdhg_pallas_interpret", False)),
            restart_mode=str(o.get("pdhg_restart_mode", "adaptive")),
            restart_beta_sufficient=float(
                o.get("pdhg_restart_beta_sufficient", 0.2)),
            restart_beta_necessary=float(
                o.get("pdhg_restart_beta_necessary", 0.8)),
            compact_threshold=float(o.get("pdhg_compact_threshold", 0.0)),
            hot_dtype=o.get("pdhg_hot_dtype"),
            sparse_threshold=float(o.get("pdhg_sparse_threshold", 0.0)))

    def config_key(self):
        """Hashable construction-time config.  `_solve_impl` reads ONLY
        these attributes, so two solvers with equal keys trace to the
        same computation and may share one jit wrapper.
        (compact_threshold does not enter the trace — solve_compacted
        is a host-side driver — but it is part of the key so configs
        with different compaction policies never alias in caches keyed
        on it, e.g. serve.compile_cache.bucket_key.)"""
        return (self.max_iters, self.eps, self.check_every,
                self.restart_every, self.omega0, self.use_pallas,
                self.pallas_tile, self.pallas_interpret,
                self.restart_mode, self.restart_beta_sufficient,
                self.restart_beta_necessary, self.compact_threshold,
                self.hot_dtype, self.sparse_threshold)

    def clone(self, **overrides):
        """A new solver with this one's full config, selected fields
        overridden — the safe way for callers that re-solve under a
        different budget/precision (spopt._certified_resolve,
        opt.mip._dive_solver) to keep every OTHER knob (restart policy,
        betas, pallas config) in sync with the parent solver."""
        cfg = dict(
            max_iters=self.max_iters, eps=self.eps,
            check_every=self.check_every,
            restart_every=self.restart_every, omega0=self.omega0,
            use_pallas=self.use_pallas, pallas_tile=self.pallas_tile,
            pallas_interpret=self.pallas_interpret,
            restart_mode=self.restart_mode,
            restart_beta_sufficient=self.restart_beta_sufficient,
            restart_beta_necessary=self.restart_beta_necessary,
            compact_threshold=self.compact_threshold,
            hot_dtype=self.hot_dtype,
            sparse_threshold=self.sparse_threshold)
        cfg.update(overrides)
        return type(self)(**cfg)

    # -- mixed precision ---------------------------------------------------
    def hot_eps_floor(self):
        """Tolerance floor of the configured hot dtype's COMPUTE
        precision (0.0 when the hot loop runs full precision — nothing
        to promote from)."""
        if self.hot_dtype is None:
            return 0.0
        return eps_floor(HOT_DTYPES[self.hot_dtype][1])

    def wants_promotion(self, eps=None):
        """True when a solve at tolerance `eps` (default: the
        construction-time eps) needs MORE precision than the hot dtype
        can express — the eps-ladder/KKT promotion rule: drivers
        (spopt.solve_loop, phbase supersteps) switch to the
        full-precision solver + prep instead of letting the hot loop
        clamp eps to its floor and certify at a looser tolerance than
        requested.  Monotone under the PH eps ladder: the ladder only
        tightens, so promotion never reverts within a run."""
        if self.hot_dtype is None:
            return False
        e = self.eps if eps is None else float(eps)
        return e < self.hot_eps_floor()

    def _hot_pair(self, caller_dtype):
        """(storage, compute) jnp dtypes for the hot loop given the
        caller's array dtype, or None when no downcast applies (knob
        off, caller already at/below the hot precision)."""
        if self.hot_dtype is None:
            return None
        store, compute = (jnp.dtype(s)
                          for s in HOT_DTYPES[self.hot_dtype])
        dt = jnp.dtype(caller_dtype)
        if dt == store and dt == compute:
            return None
        if jnp.finfo(dt).bits < jnp.finfo(compute).bits:
            return None     # never upcast the caller's data
        return store, compute

    # -- public ----------------------------------------------------------
    def solve(self, prep: PreparedBatch, c, qdiag, lb, ub,
              obj_const=None, x0=None, y0=None,
              consensus: ConsensusSpec | None = None,
              eps=None, iters_cap=None) -> SolveResult:
        """Solve the batch.  c/qdiag/lb/ub are UNSCALED user-space arrays
        (S, N); x0/y0 optional warm starts in user space.  With a
        ConsensusSpec, solves the monolithic EF (prep must come from
        prepare_batch(shared_cols=True)).  `eps` (a jnp scalar) overrides
        the construction-time tolerance without recompiling — the analog
        of per-iteration solver mipgap schedules (reference
        extensions/mipgapper.py)."""
        S, N = c.shape
        M = prep.A.shape[1]
        if obj_const is None:
            obj_const = jnp.zeros((S,), c.dtype)
        if x0 is None:
            x0 = jnp.zeros((S, N), c.dtype)
        if y0 is None:
            y0 = jnp.zeros((S, M), c.dtype)
        return self._solve_jit(prep, c, qdiag, lb, ub, obj_const, x0, y0,
                               consensus, eps, iters_cap)

    def solve_compacted(self, prep: PreparedBatch, c, qdiag, lb, ub,
                        obj_const=None, x0=None, y0=None,
                        consensus: ConsensusSpec | None = None,
                        eps=None, probs=None, segment_iters=None,
                        on_segment=None) -> SolveResult:
        """`solve`, segmented on the host so converged scenarios stop
        paying matvec FLOPs: run `segment_iters` inner iterations via
        the traced `iters_cap` (no recompile per segment), read the
        converged mask back, and once the active (unconverged, prob>0)
        fraction drops below `compact_threshold`, GATHER the survivors
        into the next smaller power-of-two width bucket
        (serve.compile_cache.width_bucket — pow2 quantization bounds
        the number of distinct compiled widths at log2(S)) and continue
        the hot loop on the compacted slab, scattering results back
        over the frozen full-width buffers.

        Scenarios that converge are NEVER re-entered into a later
        segment, so anything frozen before the first compaction is
        bit-identical to the uncompacted solve (same jit, same shapes,
        same inputs up to its convergence check).  Survivors restart
        each segment from their own warm iterate; their restart average
        and omega re-seed, so they agree with the uncompacted solve
        only up to the KKT tolerance — the compaction parity contract.

        Falls back to plain `solve` when compaction is disabled
        (compact_threshold == 0) or under a ConsensusSpec (consensus
        couples the whole batch; dropping scenarios would change the
        problem).  `probs`: optional (S,) scenario probabilities —
        zero-probability padding rows never count as active.
        `on_segment`: optional callback receiving a dict
        (width/active/iters/seg_iters) after each segment — the
        telemetry hook for the active-fraction trajectory.
        """
        if self.compact_threshold <= 0.0 or consensus is not None:
            return self.solve(prep, c, qdiag, lb, ub,
                              obj_const=obj_const, x0=x0, y0=y0,
                              consensus=consensus, eps=eps)
        import numpy as np

        from ..serve.compile_cache import width_bucket

        S, N = c.shape
        M = prep.A.shape[1]
        if obj_const is None:
            obj_const = jnp.zeros((S,), c.dtype)
        if x0 is None:
            x0 = jnp.zeros((S, N), c.dtype)
        if y0 is None:
            y0 = jnp.zeros((S, M), c.dtype)
        seg = (int(segment_iters) if segment_iters
               else self.check_every * self.restart_every)
        seg = max(seg, self.check_every)

        real = np.arange(S)
        if probs is not None:
            p = np.asarray(probs).reshape(-1)
            real = real[p > 0]

        bufs = None          # full-width result buffers (set by seg 1)
        restarts_f = jnp.zeros((S,), jnp.int32)
        iters_done = 0
        width = S
        cur = None           # None = full width, else gathered indices
        cur_n = S            # how many leading rows of `cur` are real
        while True:
            cap = min(seg, self.max_iters - iters_done)
            if cap < self.check_every and bufs is not None:
                break
            if cur is None:
                res = self.solve(prep, c, qdiag, lb, ub,
                                 obj_const=obj_const, x0=x0, y0=y0,
                                 eps=eps, iters_cap=cap)
            else:
                ii = jnp.asarray(cur, jnp.int32)
                res = self.solve(
                    _gather_prep(prep, ii), c[ii], qdiag[ii], lb[ii],
                    ub[ii], obj_const=obj_const[ii],
                    x0=bufs["x"][ii], y0=bufs["y"][ii],
                    eps=eps, iters_cap=cap)
            iters_done += int(res.iters)
            if bufs is None:
                bufs = {f: getattr(res, f) for f in
                        ("x", "y", "obj", "dual_obj", "pres", "dres",
                         "gap", "converged")}
                restarts_f = res.restarts
            else:
                ri = jnp.asarray(cur[:cur_n], jnp.int32)
                for f in bufs:
                    bufs[f] = bufs[f].at[ri].set(
                        getattr(res, f)[:cur_n])
                restarts_f = restarts_f.at[ri].add(res.restarts[:cur_n])

            conv = np.asarray(bufs["converged"])
            act = real[~conv[real]]
            if on_segment is not None:
                on_segment({"width": int(width),
                            "active": int(act.size),
                            "iters": iters_done,
                            "seg_iters": int(res.iters)})
            if act.size == 0 or iters_done >= self.max_iters:
                break
            target = width_bucket(act.size)
            if target < width and act.size <= self.compact_threshold * width:
                width = target
            # survivors only — converged rows are frozen in `bufs` and
            # must never re-enter a segment (bit-stability contract);
            # pad to the bucket width by repeating survivors (padded
            # duplicates converge with their twins and are dropped at
            # scatter time)
            cur = np.resize(act, width)
            cur[:act.size] = act
            cur_n = int(act.size)

        return SolveResult(
            x=bufs["x"], y=bufs["y"], obj=bufs["obj"],
            dual_obj=bufs["dual_obj"], pres=bufs["pres"],
            dres=bufs["dres"], gap=bufs["gap"],
            converged=bufs["converged"],
            iters=jnp.asarray(iters_done, jnp.int32),
            restarts=restarts_f)

    # -- impl --------------------------------------------------------
    def _solve_impl(self, prep, c, qdiag, lb, ub, obj_const, x0, y0,
                    consensus=None, eps=None, iters_cap=None):
        dc, dr = prep.d_col, prep.d_row
        # scale into solver space (in the caller's precision — the
        # promotion rules of c * dc fix the OUTPUT dtype below)
        cs = c * dc
        qs = qdiag * dc * dc
        lbs = jnp.where(jnp.isfinite(lb), lb / dc, lb)
        ubs = jnp.where(jnp.isfinite(ub), ub / dc, ub)
        xs0 = jnp.clip(jnp.where(jnp.isfinite(x0 / dc), x0 / dc, 0.0),
                       lbs, ubs)
        ys0 = y0 / dr
        A, rlo, rhi = prep.A, prep.row_lo, prep.row_hi
        # mixed precision (hot_dtype): the while_loop below runs in the
        # hot COMPUTE dtype with A held in the hot STORAGE dtype; the
        # final KKT verdict and the returned SolveResult are produced
        # back in the caller's dtype (out_dt), so warm starts, PH state
        # and checkpoints never silently narrow.  The *_f views feed
        # that final verdict — aliases when no downcast applies.
        out_dt = cs.dtype
        hot = self._hot_pair(out_dt)
        cs_f, qs_f, lbs_f, ubs_f = cs, qs, lbs, ubs
        A_f, rlo_f, rhi_f = A, rlo, rhi
        if hot is not None:
            store, compute = hot
            cs, qs = cs.astype(compute), qs.astype(compute)
            lbs, ubs = lbs.astype(compute), ubs.astype(compute)
            xs0, ys0 = xs0.astype(compute), ys0.astype(compute)
            rlo, rhi = rlo.astype(compute), rhi.astype(compute)
            A = _cast_A(A, store)
        S, N = cs.shape
        # clamp the tolerance to what the LOOP dtype can express: in
        # float32 an eps below ~1e-5 can never be met and every solve
        # would spin to max_iters.  (Callers needing a tighter eps than
        # the hot floor promote to full precision instead —
        # wants_promotion.)  The final verdict reuses the same clamped
        # value in the caller's dtype (eps_out).
        floor = 100.0 * float(jnp.finfo(cs.dtype).eps)
        if eps is None:
            eps = max(self.eps, floor)
            eps_out = eps
        else:
            eps_out = jnp.maximum(jnp.asarray(eps, out_dt), floor)
            eps = eps_out.astype(cs.dtype)

        if consensus is not None:
            from ..ir import node_segment_sum
            na = consensus.nonant_idx
            _, segsum = node_segment_sum(consensus.node_of,
                                         consensus.num_nodes)
            counts = segsum(jnp.ones_like(cs[:, na]))

            def csum(g):
                """Adjoint of the shared-variable broadcast: nonant
                slots <- sum over node members, broadcast back."""
                return g.at[:, na].set(segsum(g[:, na]))

            def cavg(g):
                g2 = csum(g)
                return g2.at[:, na].set(g2[:, na] / counts)

            # z-space norm weights: shared coords counted once
            wz = jnp.ones_like(cs).at[:, na].set(1.0 / counts)

            # per-copy reductions over the scenario axis: with
            # num_copies stacked independent EFs (opt/mip._lp_multi),
            # each copy is its own problem and must get its own norm /
            # step size / verdict (nc == 1 degenerates to the plain
            # batch-global reductions)
            nc = max(int(getattr(consensus, "num_copies", 1) or 1), 1)
            S0 = S // nc

            def scen_sum(a):
                """(S,) -> per-copy sum, broadcast back to (S,)."""
                return jnp.repeat(
                    jnp.sum(a.reshape(nc, S0), axis=1), S0)

            def scen_max(a):
                return jnp.repeat(
                    jnp.max(a.reshape(nc, S0), axis=1), S0)

            def znorm(g):
                """(S, ...) -> per-copy z-norm, (S,) broadcast."""
                return jnp.sqrt(scen_sum(
                    jnp.sum(wz * g * g, axis=1))) + 1e-30

            # power iteration for the EF operator  M = blockdiag(A) . B
            key = jax.random.PRNGKey(0)
            v = cavg(jax.random.normal(key, (S, N), cs.dtype))

            def pbody(_, v):
                v = v / znorm(v)[:, None]
                u = _Ax(A, v)
                return csum(_ATy(A, u))

            v = lax.fori_loop(0, 40, pbody, v)
            av = _Ax(A, v / znorm(v)[:, None])
            anorm_c = jnp.sqrt(scen_sum(jnp.sum(av * av, axis=1)))
            anorm = jnp.maximum(anorm_c, 1.0).astype(cs.dtype)
            qmax = scen_max(jnp.max(csum(qs), axis=1)).astype(cs.dtype)
            xs0 = jnp.clip(cavg(xs0), lbs, ubs)  # consistent warm start
        else:
            csum = cavg = None
            # cast, not recompute: the norm estimate from the (possibly
            # low-precision) prep is accurate far beyond step-size needs
            anorm = prep.anorm.astype(cs.dtype)
            qmax = jnp.max(qs, axis=1)

        def steps(x, y, omega, n):
            """n PDHG iterations; returns final + running sums."""
            sigma = 0.9 * omega / anorm
            tau = 0.9 / (omega * anorm + 0.9 * qmax)

            if self.use_pallas:
                # the Pallas chunk kernel tiles per-scenario A slabs; a
                # caller who asked for it gets it compiled or an error,
                # never a silent XLA or interpret-mode substitute
                if csum is not None or isinstance(A, SplitA) \
                        or A.shape[0] != x.shape[0]:
                    raise ValueError(
                        "use_pallas=True needs a dense per-scenario "
                        "(S, M, N) operator and no consensus; this "
                        "solve's operator is split, shared or "
                        "consensus — use use_pallas=False")
                from .pallas_pdhg import fused_chunk
                return fused_chunk(
                    A, cs, qs, lbs, ubs, rlo, rhi, x, y,
                    tau, sigma, n, tile_s=self.pallas_tile,
                    interpret=self.pallas_interpret)

            def body(_, carry):
                x, y, xs, ys = carry
                grad = cs + qs * x + _ATy(A, y)
                if csum is not None:
                    grad = csum(grad)
                xn = _proj_box(x - tau[:, None] * grad, lbs, ubs)
                xt = 2.0 * xn - x
                v = y + sigma[:, None] * _Ax(A, xt)
                yn = _dual_prox(v, sigma, rlo, rhi)
                return xn, yn, xs + xn, ys + yn

            zx = jnp.zeros_like(x)
            zy = jnp.zeros_like(y)
            x, y, xs, ys = lax.fori_loop(0, n, body, (x, y, zx, zy))
            return x, y, xs, ys

        def kkt_score(x, y, data=None):
            # data: optional (cs, qs, A, rlo, rhi, lbs, ubs) override —
            # the final verdict passes the caller-precision views
            if data is None:
                data = (cs, qs, A, rlo, rhi, lbs, ubs)
            csk, qsk, Ak, rlok, rhik, lbsk, ubsk = data
            pres, dres, gap, pobj, dobj = _residuals(
                x, y, csk, qsk, Ak, rlok, rhik, lbsk, ubsk, cavg=cavg)
            if consensus is not None:
                # each EF COPY is one problem: its scenarios share one
                # verdict, and only the SUMS of its per-scenario
                # objective pieces are meaningful for the duality gap
                pres = scen_max(pres)
                dres = scen_max(dres)
                ps, ds = scen_sum(pobj), scen_sum(dobj)
                gap = jnp.abs(ps - ds) / (1.0 + jnp.abs(ps)
                                          + jnp.abs(ds))
            return pres + dres + gap, pres, dres, gap

        ne = self.check_every
        n_outer = self.max_iters // ne
        # traced SCREENING cap: callers ranking many speculative
        # candidates (uc.one_opt_commitment sweeps, mip refine) bound
        # the spend per launch without a second solver instance or a
        # recompile per cap value
        if iters_cap is None:
            cap_outer = n_outer
        else:
            cap_outer = jnp.minimum(
                jnp.asarray(n_outer, jnp.int32),
                (jnp.asarray(iters_cap, jnp.int32) + ne - 1) // ne)

        def cond(carry):
            return (carry.k < cap_outer) & (~jnp.all(carry.converged))

        def body(carry):
            x, y, xs, ys = steps(carry.x, carry.y, carry.omega, ne)
            x_sum = carry.x_sum + xs
            y_sum = carry.y_sum + ys
            nsum = carry.nsum + ne
            score_cur, pres, dres, gap = kkt_score(x, y)
            newly = (pres < eps) & (dres < eps) & (gap < eps)
            conv = carry.converged | newly
            x_best = jnp.where(
                (newly & ~carry.converged)[:, None], x, carry.x_best)
            y_best = jnp.where(
                (newly & ~carry.converged)[:, None], y, carry.y_best)

            k = carry.k + 1
            cycle = carry.cycle + 1

            # restart CANDIDATE: the better of {current, cycle average}
            # (PDLP's restart-to-the-best rule).  Computed every check
            # — one extra kkt_score per check_every inner iterations,
            # ~2.5% at the default cadence — so the adaptive trigger
            # can observe the candidate's score.
            xa = x_sum / nsum[:, None]
            ya = y_sum / nsum[:, None]
            score_avg, *_ = kkt_score(xa, ya)
            take_avg = score_avg < score_cur
            xr = jnp.where(take_avg[:, None], xa, x)
            yr = jnp.where(take_avg[:, None], ya, y)
            score_cand = jnp.minimum(score_avg, score_cur)

            if self.restart_mode == "adaptive":
                # PDLP trigger, per scenario: sufficient decay fires
                # immediately; necessary decay fires only once progress
                # WITHIN the cycle stalls (candidate score no longer
                # improving check-over-check); the fixed cadence
                # remains as a forced cap so no cycle runs unbounded.
                # Under consensus every input here is per-copy uniform
                # (kkt_score reduces with scen_max/scen_sum), so the
                # mask is per-copy uniform too and the shared-variable
                # invariant holds.
                suff = (score_cand
                        <= self.restart_beta_sufficient
                        * carry.score_restart)
                nec = ((score_cand
                        <= self.restart_beta_necessary
                        * carry.score_restart)
                       & (score_cand > carry.score_cand_prev))
                do_restart = suff | nec | (cycle >= self.restart_every)
            else:
                do_restart = jnp.broadcast_to(
                    cycle >= self.restart_every, cycle.shape)
            # frozen scenarios take no further restarts (their state is
            # pinned below anyway; keeps the restarts counter honest)
            do_restart = do_restart & ~conv

            # primal weight update (PDLP eq. (10)-style smoothing)
            if consensus is not None:
                # one shared problem PER COPY -> one shared omega
                # per copy (per-scenario omegas would give
                # inconsistent step sizes and break the
                # shared-variable invariant)
                dxv = xr - carry.x_last
                dyv = yr - carry.y_last
                dx = jnp.sqrt(scen_sum(jnp.sum(dxv * dxv, axis=1)))
                dy = jnp.sqrt(scen_sum(jnp.sum(dyv * dyv, axis=1)))
            else:
                dx = jnp.linalg.norm(xr - carry.x_last, axis=1)
                dy = jnp.linalg.norm(yr - carry.y_last, axis=1)
            ok = (dx > 1e-12) & (dy > 1e-12)
            ratio = jnp.where(ok, dy / jnp.maximum(dx, 1e-12), 1.0)
            om_new = jnp.where(
                ok,
                jnp.exp(0.5 * jnp.log(ratio)
                        + 0.5 * jnp.log(carry.omega)),
                carry.omega)
            om_new = jnp.clip(om_new, 1e-4, 1e4)

            # apply the restart per scenario via masks (no batch-global
            # lax.cond: scenarios restart independently)
            m = do_restart
            m2 = m[:, None]
            zx = jnp.zeros_like(x)
            zy = jnp.zeros_like(y)
            xr_ = jnp.where(m2, xr, x)
            yr_ = jnp.where(m2, yr, y)

            # freeze converged scenarios
            cm = carry.converged[:, None]
            return _Carry(
                x=jnp.where(cm, carry.x, xr_),
                y=jnp.where(cm, carry.y, yr_),
                x_sum=jnp.where(m2, zx, x_sum),
                y_sum=jnp.where(m2, zy, y_sum),
                nsum=jnp.where(m, 0.0, nsum),
                x_last=jnp.where(m2, xr, carry.x_last),
                y_last=jnp.where(m2, yr, carry.y_last),
                omega=jnp.where(m, om_new, carry.omega), k=k,
                converged=conv, x_best=x_best, y_best=y_best,
                cycle=jnp.where(m, 0, cycle),
                score_restart=jnp.where(m, score_cand,
                                        carry.score_restart),
                # reset to +inf at a restart so the stagnation test
                # cannot fire on the new cycle's first check
                score_cand_prev=jnp.where(m, jnp.inf, score_cand),
                restarts=carry.restarts + m.astype(jnp.int32))

        S, N = cs.shape
        M = rlo.shape[1]
        inf = jnp.full((S,), jnp.inf, cs.dtype)
        # seed the decay reference with the WARM-START's own KKT score
        # (not +inf, which would read any first check as "sufficient
        # decay" and fire a spurious immediate restart)
        score0, *_ = kkt_score(xs0, ys0)
        init = _Carry(
            x=xs0, y=ys0,
            x_sum=jnp.zeros_like(xs0), y_sum=jnp.zeros_like(ys0),
            nsum=jnp.zeros((S,), cs.dtype),
            x_last=xs0, y_last=ys0,
            omega=jnp.full((S,), self.omega0, cs.dtype),
            k=jnp.asarray(0, jnp.int32),
            converged=jnp.zeros((S,), bool),
            x_best=xs0, y_best=ys0,
            cycle=jnp.zeros((S,), jnp.int32),
            score_restart=score0.astype(cs.dtype),
            score_cand_prev=inf,
            restarts=jnp.zeros((S,), jnp.int32))
        fin = lax.while_loop(cond, body, init)

        x = jnp.where(fin.converged[:, None], fin.x_best, fin.x)
        y = jnp.where(fin.converged[:, None], fin.y_best, fin.y)
        if hot is not None:
            # promote the final iterate to the caller's dtype and
            # recheck the verdict there: frozen scenarios keep their
            # hot-precision certificate (the semantics a native-f32
            # run has always had) and the full-precision recheck can
            # only ADD conversions
            x = x.astype(out_dt)
            y = y.astype(out_dt)
        _, pres, dres, gap = kkt_score(
            x, y, data=(cs_f, qs_f, A_f, rlo_f, rhi_f, lbs_f, ubs_f))
        # unscale
        xu = x * dc
        yu = y * dr
        pobj = (jnp.sum(c * xu, axis=1)
                + 0.5 * jnp.sum(qdiag * xu * xu, axis=1) + obj_const)
        # dual objective in user space (recompute residual pieces unscaled)
        _, _, _, _, dobj = _residuals(
            xu, yu, c, qdiag,
            _unscale_A(prep.A, dr, dc),
            jnp.where(jnp.isfinite(prep.row_lo), prep.row_lo / dr,
                      prep.row_lo),
            jnp.where(jnp.isfinite(prep.row_hi), prep.row_hi / dr,
                      prep.row_hi),
            lb, ub, cavg=cavg)
        return SolveResult(
            x=xu, y=yu, obj=pobj, dual_obj=dobj + obj_const,
            pres=pres, dres=dres, gap=gap,
            converged=fin.converged | ((pres < eps_out)
                                       & (dres < eps_out)
                                       & (gap < eps_out)),
            iters=fin.k * ne, restarts=fin.restarts)
