"""PHBase — Progressive Hedging machinery (reference: mpisppy/phbase.py).

The reference's per-iteration work is: pack [xbar||xsqbar] vectors
var-by-var into Pyomo Params, one MPI Allreduce per tree node
(phbase.py:27-107 _Compute_Xbar), a Python loop for the dual update
(:293-318 Update_W), mutation of every scenario's Pyomo objective, and
N sequential solver calls.  Here ALL of it is one jitted superstep:

    x  <- argmin_x  c@x + (W - rho*xbar)@x_na + rho/2 ||x_na||^2 + ...
    xbar <- per-node probability-weighted average (segment-sum + psum)
    W  <- W + rho * (x_na - xbar)
    conv <- prob-weighted scaled ||x - xbar||_1

The per-tree-node communicators of the reference (spbase.py:333-375)
become a segment-sum over node ids (ir.TreeInfo.node_of) — identical
code for 2-stage (1 node) and multistage.
"""

from __future__ import annotations

import dataclasses
import functools
import threading
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from . import global_toc
from .ir import ScenarioBatch, SparseSplitA, node_segment_sum
from .resilience.chaos import ChaosInjector
from .spopt import SPOpt


def _register(cls, data_fields, meta_fields=()):
    jax.tree_util.register_dataclass(
        cls, data_fields=data_fields, meta_fields=meta_fields)
    return cls


@dataclasses.dataclass(frozen=True)
class PHState:
    """Per-iteration PH state (pytree; scenario-leading arrays sharded)."""
    x: Any        # (S, N) last primal solutions
    y: Any        # (S, M) last duals (warm start + Lagrangian bounds)
    W: Any        # (S, K) dual weights on nonants
    xbar: Any     # (S, K) per-slot consensus values (node-averaged)
    xsqbar: Any   # (S, K) consensus of squares (for Fixer-style variance)
    obj: Any      # (S,) per-scenario objective at x
    dual_obj: Any  # (S,)
    conv: Any     # () convergence metric
    it: Any       # () int iteration count
    solve_iters: Any = 0  # () int kernel iterations of the last solve
    active_frac: Any = 1.0  # () unconverged fraction (prob>0) last solve
    solve_restarts: Any = 0  # () int restart events of the last solve
    # () int 1 when the last solve ran on the promoted full-precision
    # pair (hot_dtype runs only; stays 0 otherwise) — checkpointed so a
    # resumed run knows its precision history (resilience/checkpoint.py)
    promoted: Any = 0


_register(PHState, tuple(f.name for f in dataclasses.fields(PHState)))


@dataclasses.dataclass(frozen=True)
class ScenarioView:
    """One scenario's slice of the solution state — what denouements
    and user callbacks receive in place of the reference's Pyomo
    scenario instance (reference spbase.py:505-522)."""
    index: int
    name: str
    x: Any         # (N,) full primal solution of this scenario
    nonants: Any   # (K,) nonanticipative values
    obj: float     # true objective at x
    prob: float    # scenario probability
    W: Any         # (K,) dual weights
    xbar: Any      # (K,) consensus values seen by this scenario


# ---- pure functional core (all jit-friendly) -----------------------------

def compute_xbar(batch: ScenarioBatch, x_na, extra=None):
    """Per-node probability-weighted averages of nonant values.

    Mirror of _Compute_Xbar (reference phbase.py:27-107): the reference
    packs [xbar||xsqbar] and Allreduces per node comm; here it's a
    segment-sum over node ids, reduced across devices by XLA.

    When the batch carries per-(scenario, slot) probabilities
    (batch.var_prob — the reference's variable_probability feature,
    spbase.py:394), those weights replace the scenario probabilities in
    the average, exactly as the reference's Compute_Xbar consumes
    `_mpisppy_variable_probability` (phbase.py:71-88).

    x_na: (S, K) nonant values.  Returns (xbar, xsqbar), each (S, K),
    gathered back to scenario-slot layout.
    """
    tree = batch.tree
    if batch.var_prob is not None:
        p = jnp.asarray(batch.var_prob, x_na.dtype)      # (S, K)
    else:
        p = jnp.broadcast_to(tree.prob[:, None], x_na.shape)
    _, segsum = node_segment_sum(tree.node_of, tree.num_nodes)
    wsum = segsum(p)
    denom = jnp.maximum(wsum, 1e-30)
    xbar = segsum(p * x_na) / denom
    xsqbar = segsum(p * x_na * x_na) / denom
    return xbar, xsqbar


def ph_objective_arrays(batch: ScenarioBatch, W, rho, xbar,
                        W_on=1.0, prox_on=1.0):
    """Fold PH's W and prox terms into (c_eff, qdiag_eff).

    Replaces attach_Ws_and_prox / attach_PH_to_objective (reference
    phbase.py:585-699): W@x + prox_on * rho/2 (x^2 - 2 xbar x + xbar^2).
    The xbar^2 constant is dropped (doesn't move the argmin; objective
    values reported from c, not c_eff).  W_on/prox_on mirror the
    reference's gate scalars.
    """
    na = batch.nonant_idx
    lin = W_on * W - prox_on * rho * xbar
    c_eff = batch.c.at[:, na].add(lin)
    q_eff = batch.qdiag.at[:, na].add(
        jnp.broadcast_to(prox_on * rho, W.shape))
    return c_eff, q_eff


def update_W(W, rho, x_na, xbar):
    """Dual update (reference phbase.py:293-318 Update_W)."""
    return W + rho * (x_na - xbar)


def _active_fraction(batch, converged):
    """Fraction of prob>0 scenarios the solve left unconverged — the
    adaptive-work observability signal (pdhg.active_fraction)."""
    live = batch.prob > 0
    n = jnp.maximum(jnp.sum(live), 1)
    return jnp.sum((~converged) & live) / n


def convergence_metric(batch: ScenarioBatch, x_na, xbar):
    """Scaled prob-weighted ||x - xbar||_1 (reference phbase.py:321-343
    convergence_diff)."""
    K = max(x_na.shape[1], 1)
    per_scen = jnp.sum(jnp.abs(x_na - xbar), axis=1) / K
    return jnp.sum(batch.prob * per_scen)


def ph_superstep(solver, state: PHState, rho, W_on, prox_on,
                 lb, ub, eps, prep, batch):
    """One fused PH iteration as a pure function of its inputs:
    solve -> xbar consensus -> W update -> convergence metric.

    Everything that varies per run — scenario data, rho, bounds,
    tolerance, prepared matrices — is a traced ARGUMENT, so one lowered
    computation serves every PH instance (and every serve-layer
    request) with the same shapes: the executable is keyed only on the
    solver config (via `fused_superstep`) plus jit's own shape bucket.
    This is also what lets the serve layer vmap the whole superstep
    over a leading request axis."""
    c_eff, q_eff = ph_objective_arrays(
        batch, state.W, rho, state.xbar, W_on=W_on, prox_on=prox_on)
    res = solver._solve_jit(
        prep, c_eff, q_eff, lb, ub, batch.obj_const,
        state.x, state.y, None, eps)
    x_na = batch.nonants(res.x)
    xbar, xsqbar = compute_xbar(batch, x_na)
    W = update_W(state.W, rho, x_na, xbar)
    conv = convergence_metric(batch, x_na, xbar)
    # report the TRUE objective at x (c, not c_eff)
    obj = batch.objective(res.x)
    return PHState(
        x=res.x, y=res.y, W=W, xbar=xbar, xsqbar=xsqbar,
        obj=obj, dual_obj=res.dual_obj, conv=conv, it=state.it + 1,
        solve_iters=res.iters,
        active_frac=_active_fraction(batch, res.converged),
        solve_restarts=jnp.sum(res.restarts))


# Per-THREAD fused-superstep registry, mirroring
# ops.pdhg._SOLVE_JIT_TLS: `ph_superstep` depends on the solver only
# through its config, so every PHBase whose solver shares a config_key
# (within one thread) shares ONE jitted wrapper.  Before this registry
# each instance jitted a bound method and re-traced/re-compiled the
# identical superstep.  Thread-local, not process-global, and resolved
# at CALL time (`PHBase._superstep` is a property), for the same reason
# as the solve-jit registry: threaded cylinder wheels construct every
# cylinder on the main thread but dispatch concurrently from worker
# threads, and concurrent calls into one jit wrapper deadlock —
# call-time per-thread scoping preserves the invariant that no two
# threads race one wrapper.  The serve layer's batch=1 path runs this identical
# lowered computation (same function, same config, same shapes), which
# is what makes its result bitwise equal to a standalone `PH.ph_main`
# (asserted in tests/test_serve.py).
_SUPERSTEP_TLS = threading.local()


def fused_superstep(solver):
    """The thread-shared jitted PH superstep for `solver`'s config."""
    reg = getattr(_SUPERSTEP_TLS, "registry", None)
    if reg is None:
        reg = _SUPERSTEP_TLS.registry = {}
    key = solver.config_key()
    fn = reg.get(key)
    if fn is None:
        fn = jax.jit(functools.partial(ph_superstep, solver))
        reg[key] = fn
    return fn


class PHBase(SPOpt):
    """Shared PH machinery; algorithm drivers (opt/ph.py, opt/aph.py)
    subclass this."""

    def __init__(self, options, all_scenario_names, scenario_creator=None,
                 scenario_denouement=None, all_nodenames=None,
                 extensions=None, extension_kwargs=None,
                 rho_setter=None, variable_probability=None,
                 scenario_creator_kwargs=None, batch=None, mesh=None,
                 prep=None):
        super().__init__(
            options, all_scenario_names,
            scenario_creator=scenario_creator,
            scenario_denouement=scenario_denouement,
            all_nodenames=all_nodenames,
            scenario_creator_kwargs=scenario_creator_kwargs,
            variable_probability=variable_probability,
            batch=batch, mesh=mesh, prep=prep)
        self.rho_setter = rho_setter
        self.extobject = None
        if extensions is not None:
            self.extobject = extensions(self, **(extension_kwargs or {}))
        self.spcomm = None  # set by cylinders.hub when running as hub
        self._iter0_solver_options = self.options.get(
            "iter0_solver_options")
        self.W_on = 1.0
        self.prox_on = 1.0

        # rho: scalar option -> (S, K) array; rho_setter may override
        # per-variable (reference phbase.py:387-406 _use_rho_setter)
        K = self.batch.num_nonants
        S = self.batch.num_scens
        rho_default = float(self.options.get("defaultPHrho", 1.0))
        rho = jnp.full((S, K), rho_default, self.batch.c.dtype)
        if rho_setter is not None:
            vals = np.asarray(rho_setter(self.batch), dtype=float)
            rho = jnp.broadcast_to(jnp.asarray(vals), (S, K)).astype(
                self.batch.c.dtype)
        self.rho = rho

        self.state: PHState | None = None
        self.trivial_bound = None
        self.best_bound = None
        # per-phase jitted pieces of the superstep, built lazily the
        # first time telemetry phase timing runs (telemetry/; the fused
        # _superstep property stays the only path when telemetry is off)
        self._phase_jits = None
        self.conv = None

        # effective bounds: extensions (Fixer, slamming) pin nonants by
        # tightening these; the jitted superstep takes them as ARGS so a
        # fix never triggers recompilation (the reference mutates Pyomo
        # var.fix() instead, spopt.py:592-740)
        self.lb_eff = self.batch.lb
        self.ub_eff = self.batch.ub
        # (solver_eps lives on SPOpt so solve_loop callers — Iter0,
        # spokes, xhat evaluation — honor the Gapper schedule too)
        # superstep tolerance: PH subproblem solves tolerate loose
        # accuracy (PH is itself an approximation until the bounds
        # certify), so the hot loop may run at a looser eps than the
        # certified bound solves — the analog of the reference's
        # iterk mipgap vs bound-solve gap split (extensions/mipgapper.py)
        self._superstep_eps_opt = self.options.get("superstep_eps")
        # inexactness LADDER (options["eps_ladder"]): start the hot-loop
        # solves LOOSE and tighten as PH's own convergence metric
        # shrinks — early PH iterations over-solve subproblems the next
        # W update will invalidate anyway (the adaptive-sampling-PH
        # observation, PAPERS.md).  Config (truthy enables; a dict
        # overrides fields):
        #   start  — iteration-1 tolerance (default max(100*eps, 1e-3))
        #   min    — tightest tolerance (default the solver eps)
        #   couple — eps target = couple * conv (default 0.1): the
        #       tolerance tracks the consensus error geometrically,
        #       clamped to [min, start] and monotone non-increasing
        # When enabled, the ladder REPLACES a static superstep_eps (it
        # IS the dynamic schedule feeding the same traced-eps path, so
        # tightening never recompiles).
        lad = self.options.get("eps_ladder")
        self._ladder = None
        if lad:
            lad = dict(lad) if isinstance(lad, dict) else {}
            self._ladder = {
                "start": float(lad.get(
                    "start", max(100.0 * self.solver.eps, 1e-3))),
                "min": float(lad.get("min", self.solver.eps)),
                "couple": float(lad.get("couple", 0.1)),
            }
            self._ladder_eps = self._ladder["start"]

        # optional converger (reference phbase.py:726-755 PH_Prep wires
        # options["ph_converger"]; convergers/converger.py API)
        self.convobject = None
        conv_cls = self.options.get("ph_converger")
        if conv_cls is not None:
            self.convobject = conv_cls(self)

        # crash-resume + fault injection (resilience/):
        #   options["run_checkpoint"]   — atomic full-state checkpoint
        #       path, written every options["checkpoint_every"] iters
        #   options["resume_from"]      — checkpoint to restore instead
        #       of running Iter0 (missing file => fresh start)
        #   options["chaos"]            — deterministic fault injectors
        self._chaos = ChaosInjector.from_options(self.options.get("chaos"))

    # -- hook plumbing (reference extensions/extension.py API) ------------
    def _ext(self, hook, *args):
        if self.extobject is not None:
            getattr(self.extobject, hook, lambda *a: None)(*args)

    # -- nonant fixing for extensions (reference spopt.py:592-740) --------
    def fix_nonants(self, mask, values):
        """Pin nonant slots where mask (S, K) is True to `values` (S, K)
        by tightening the effective bounds.  Idempotent; unfix_nonants
        reverses."""
        b = self.batch
        na = b.nonant_idx
        vals = jnp.asarray(values, b.c.dtype)
        m = jnp.asarray(mask, bool)
        self.lb_eff = self.lb_eff.at[:, na].set(
            jnp.where(m, vals, self.lb_eff[:, na]))
        self.ub_eff = self.ub_eff.at[:, na].set(
            jnp.where(m, vals, self.ub_eff[:, na]))

    def unfix_nonants(self, mask):
        """Restore original batch bounds where mask (S, K) is True."""
        b = self.batch
        na = b.nonant_idx
        m = jnp.asarray(mask, bool)
        self.lb_eff = self.lb_eff.at[:, na].set(
            jnp.where(m, b.lb[:, na], self.lb_eff[:, na]))
        self.ub_eff = self.ub_eff.at[:, na].set(
            jnp.where(m, b.ub[:, na], self.ub_eff[:, na]))

    def count_fixed(self):
        na = self.batch.nonant_idx
        return int(jnp.sum(self.lb_eff[:, na] == self.ub_eff[:, na]))

    # -- elastic re-slicing (mpmd/reslice.py; doc/src/mpmd.md) ------------
    def reshard(self, mesh, pad_multiple=1):
        """Move this optimizer onto a NEW ScenarioMesh mid-run — the
        hub side of a dynamic reslice: the current batch is re-padded
        to the new plan's pad_multiple (pads always APPEND, so existing
        scenario rows keep their indices and window row semantics),
        every scenario-leading state array is zero-extended onto the
        new rows, all device state is re-placed on the new mesh, and
        the solver prep is rebuilt there.  The hub never restarts:
        PHState — duals, consensus, the iteration counter — carries
        over row-for-row.  Returns the new padded scenario count."""
        from .ir import SplitA, pad_scenarios, shared_density

        S_old = self.batch.num_scens
        q = max(int(pad_multiple), 1)
        Spad = ((S_old + q - 1) // q) * q
        self.mesh = mesh
        self.batch = mesh.shard_batch(pad_scenarios(self.batch, Spad))
        S_new = self.batch.num_scens
        dS = S_new - S_old

        def grow(a, fill=0.0):
            # zero-extend an (S_old, ...) array to (S_new, ...) and
            # commit it to the new mesh (arrays committed to the OLD
            # mesh cannot feed a jit over the new one)
            a = np.asarray(a)
            if dS > 0:
                pad = np.full((dS,) + a.shape[1:], fill, a.dtype)
                a = np.concatenate([a, pad])
            return mesh.shard_like_batch(a)

        def scal(a):
            return mesh.replicate(np.asarray(a))

        st = self.state
        if st is not None:
            self.state = PHState(
                x=grow(st.x), y=grow(st.y), W=grow(st.W),
                xbar=grow(st.xbar), xsqbar=grow(st.xsqbar),
                obj=grow(st.obj), dual_obj=grow(st.dual_obj),
                conv=scal(st.conv), it=scal(st.it),
                solve_iters=scal(st.solve_iters),
                active_frac=scal(st.active_frac),
                solve_restarts=scal(st.solve_restarts),
                promoted=scal(st.promoted))
        self.rho = grow(self.rho,
                        float(self.options.get("defaultPHrho", 1.0)))
        # effective bounds keep their (possibly extension-pinned) rows;
        # the fresh batch supplies the new pad rows
        lb = np.concatenate([np.asarray(self.lb_eff),
                             np.asarray(self.batch.lb)[S_old:]])
        ub = np.concatenate([np.asarray(self.ub_eff),
                             np.asarray(self.batch.ub)[S_old:]])
        self.lb_eff = mesh.shard_like_batch(lb)
        self.ub_eff = mesh.shard_like_batch(ub)
        # every shape/placement-keyed cache is stale now; the next
        # superstep retraces on the new (S, ...) shapes
        self.prep = self._build_prep(hot=self.solver.hot_dtype)
        self._shared_nnz_frac = (float(shared_density(self.prep.A))
                                 if isinstance(self.prep.A, SplitA)
                                 else None)
        self.solver_eps = jnp.asarray(np.asarray(self.solver_eps),
                                      self.batch.c.dtype)
        self._promoted_cache = None
        self._np_cache = {}
        self._phase_jits = None
        self.clear_warmstart()
        global_toc(f"reshard: {S_old} -> {S_new} padded scenarios on "
                   f"{mesh.size} device(s)")
        return S_new

    # -- Iter0 (reference phbase.py:758-872) ------------------------------
    def Iter0(self):
        self._ext("pre_iter0")
        global_toc("Iter0: no-penalty solves")
        # certify="feas": refine (f64) only primal-infeasible scenarios
        # — matching the reference's infeasibility-only iter0 gate; a
        # solve legitimately riding to a big artificial box (epigraph
        # variables pre-cuts) is dual-unconverged but NOT refined.
        # options["iter0_certify"]=False skips the refine entirely —
        # for batches that are feasible by construction (UC load shed)
        # where an f32 stall is solver noise, a large straggler set
        # would route through the CPU-f64 fallback and dominate
        # accelerator wall-clock (the r4 UC-on-TPU timeout); Ebound's
        # mask keeps the published bound valid either way
        res = self.solve_loop(
            lb=self.lb_eff, ub=self.ub_eff, warm=False,
            dtiming=self.options.get("display_timing"),
            certify=("feas" if self.options.get("iter0_certify", True)
                     else False))
        feas = self.feas_prob(res)
        self.iter0_feas_mass = float(feas)   # benchmarks report this
        if feas < 1.0 - 1e-6:
            # reference hard-quits on infeasible iter0 (phbase.py:817
            # "quitting after iter 0 because of infeasibility");
            # set options["iter0_infeasibility_ok"] to downgrade to a
            # warning (and accept -inf bounds from Ebound's mask)
            if self.options.get("iter0_certify", True):
                msg = (f"iter0 feasible mass only {feas} after "
                       f"certified re-solve: infeasible or unsolvable "
                       f"scenario(s)")
            else:
                # no certification ran — an f32 stall is
                # indistinguishable from true infeasibility here
                msg = (f"iter0 feasible mass only {feas} on the "
                       f"UNCERTIFIED fast solve (iter0_certify=False): "
                       f"enable iter0_certify for an f64 re-solve, or "
                       f"set iter0_infeasibility_ok to continue with "
                       f"masked bounds")
            if self.options.get("iter0_infeasibility_ok", False):
                global_toc("WARNING: " + msg)
            else:
                raise RuntimeError(msg)
        x_na = self.batch.nonants(res.x)
        xbar, xsqbar = compute_xbar(self.batch, x_na)
        W = update_W(jnp.zeros_like(x_na), self.rho, x_na, xbar)
        conv = convergence_metric(self.batch, x_na, xbar)
        self.trivial_bound = float(self.valid_Ebound(res))
        self.best_bound = self.trivial_bound
        self.state = PHState(
            x=res.x, y=res.y, W=W, xbar=xbar, xsqbar=xsqbar,
            obj=res.obj, dual_obj=res.dual_obj, conv=conv,
            it=jnp.asarray(0, jnp.int32), solve_iters=res.iters,
            active_frac=_active_fraction(self.batch, res.converged),
            solve_restarts=jnp.sum(res.restarts))
        self.conv = float(conv)
        global_toc(f"Iter0 trivial bound = {self.trivial_bound:.6g}, "
                   f"conv = {float(conv):.6g}")
        if self._tel.enabled:
            self._tel.event("ph.iter0",
                            trivial_bound=self.trivial_bound,
                            feas_mass=self.iter0_feas_mass,
                            conv=self.conv)
        self._ext("post_iter0")
        return self.trivial_bound

    # -- one PH iteration, fully fused ------------------------------------
    # The body lives in the module-level `ph_superstep`: everything
    # that varies per run is a traced ARG (not a closure constant) —
    # multihost meshes forbid closing over arrays that span
    # non-addressable devices, bound-rewriting extensions swap
    # batches/preps without recompiling, and the serve layer executes
    # the same function with swapped-in scenario arrays.  This method
    # stays as the un-jitted entry for callers holding a PH instance.
    def _superstep_impl(self, state: PHState, rho, W_on, prox_on,
                        lb=None, ub=None, eps=None, prep=None,
                        batch=None):
        b = self.batch if batch is None else batch
        return ph_superstep(
            self.solver, state, rho, W_on, prox_on,
            b.lb if lb is None else lb,
            b.ub if ub is None else ub,
            eps, self.prep if prep is None else prep, b)

    @property
    def _superstep(self):
        # resolved per CALLING thread (see _SUPERSTEP_TLS above): in the
        # threaded wheel the hub's driving thread is not the thread
        # that constructed it, and the wrapper must belong to the driver
        return fused_superstep(self.solver)

    @property
    def superstep_eps(self):
        """Tolerance of the hot-loop subproblem solves: the eps-ladder
        schedule when enabled (options["eps_ladder"], updated each
        ph_iteration), else the superstep_eps option when given, else
        the DYNAMIC solver_eps (so the Gapper schedule keeps reaching
        the PH loop)."""
        if self._ladder is not None:
            return jnp.asarray(self._ladder_eps, self.batch.c.dtype)
        if self._superstep_eps_opt is None:
            return self.solver_eps
        return jnp.asarray(self._superstep_eps_opt, self.batch.c.dtype)

    def _run_superstep(self):
        """Advance self.state by one superstep and sync.  Telemetry
        phase timing (when ON) routes through the unfused per-phase
        path; otherwise this is byte-for-byte the pre-telemetry fused
        call — the zero-cost-when-off contract of telemetry/.

        A hot-dtype run promotes here too: once the superstep tolerance
        (ladder or static) crosses the hot dtype's eps floor, the
        promoted full-precision (solver, prep) pair takes over —
        monotone under the ladder, so at most one extra superstep
        compile per run."""
        solver, prep = self.active_solver_prep(self.superstep_eps)
        if self._tel.phase_timing:
            self._superstep_phased(solver, prep)
        else:
            self.state = fused_superstep(solver)(
                self.state, self.rho, self.W_on, self.prox_on,
                self.lb_eff, self.ub_eff, self.superstep_eps, prep,
                self.batch)
            jax.block_until_ready(self.state.x)
        if solver is not self.solver:
            self.state = dataclasses.replace(
                self.state, promoted=jnp.asarray(1, jnp.int32))

    def _phase_impls(self, solver=None):
        """Jitted per-phase cuts of _superstep_impl (solve / xbar-psum
        / W-update / conv), functionally identical to the fused body —
        only the phase boundaries differ, so the phase-timed iteration
        produces the same PHState.  `solver` defaults to the configured
        one; the promoted full-precision solver gets its own cache
        entry (config_key differs)."""
        solver = self.solver if solver is None else solver
        key = solver.config_key()
        cache = self._phase_jits
        if cache is None:
            cache = self._phase_jits = {}
        fns = cache.get(key)
        if fns is not None:
            return fns

        def solve(state, rho, W_on, prox_on, lb, ub, eps, prep, batch):
            c_eff, q_eff = ph_objective_arrays(
                batch, state.W, rho, state.xbar,
                W_on=W_on, prox_on=prox_on)
            return solver._solve_jit(
                prep, c_eff, q_eff, lb, ub, batch.obj_const,
                state.x, state.y, None, eps)

        def xbar(batch, x):
            x_na = batch.nonants(x)
            return (x_na,) + compute_xbar(batch, x_na)

        def w_up(W, rho, x_na, xbar_):
            return update_W(W, rho, x_na, xbar_)

        def conv(batch, x_na, xbar_, x):
            return convergence_metric(batch, x_na, xbar_), \
                batch.objective(x)

        fns = {"solve": jax.jit(solve), "xbar": jax.jit(xbar),
               "w_update": jax.jit(w_up), "conv": jax.jit(conv)}
        cache[key] = fns
        return fns

    def _superstep_phased(self, solver=None, prep=None):
        """One PH iteration with per-phase spans + timing histograms
        (ph.phase.{solve,psum,w_update,conv}_seconds).  Each phase runs
        as its own jitted call with a device sync between phases — the
        observability/fusion trade the telemetry docs call out, which
        is why this path exists ONLY behind tel.phase_timing."""
        tel = self._tel
        st, b = self.state, self.batch
        prep = self.prep if prep is None else prep
        fns = self._phase_impls(solver)
        t0 = time.monotonic()
        with tel.span("ph.phase.solve"):
            res = fns["solve"](st, self.rho, self.W_on, self.prox_on,
                               self.lb_eff, self.ub_eff,
                               self.superstep_eps, prep, b)
            jax.block_until_ready(res.x)
        t1 = time.monotonic()
        with tel.span("ph.phase.psum"):
            x_na, xbar, xsqbar = fns["xbar"](b, res.x)
            jax.block_until_ready(xbar)
        t2 = time.monotonic()
        with tel.span("ph.phase.w_update"):
            W = fns["w_update"](st.W, self.rho, x_na, xbar)
            jax.block_until_ready(W)
        t3 = time.monotonic()
        with tel.span("ph.phase.conv"):
            conv, obj = fns["conv"](b, x_na, xbar, res.x)
            jax.block_until_ready(conv)
        t4 = time.monotonic()
        hist = tel.registry.histogram
        hist("ph.phase.solve_seconds").observe(t1 - t0)
        hist("ph.phase.psum_seconds").observe(t2 - t1)
        hist("ph.phase.w_update_seconds").observe(t3 - t2)
        hist("ph.phase.conv_seconds").observe(t4 - t3)
        self.state = PHState(
            x=res.x, y=res.y, W=W, xbar=xbar, xsqbar=xsqbar,
            obj=obj, dual_obj=res.dual_obj, conv=conv, it=st.it + 1,
            solve_iters=res.iters,
            active_frac=_active_fraction(b, res.converged),
            solve_restarts=jnp.sum(res.restarts))

    def ph_iteration(self):
        self._ext("pre_solve_loop")
        t0 = time.time()
        tel = self._tel
        if tel.enabled:
            with tel.span("ph.iteration"):
                self._run_superstep()
        else:
            self._run_superstep()
        # the superstep's kernel work: iters ride along in the state so
        # no extra device sync is needed beyond the conv readback below
        it_n = int(self.state.solve_iters)
        rst_n = int(self.state.solve_restarts)
        self._kernel_iters += it_n
        self._restarts_total += rst_n
        if isinstance(self.prep.A, SparseSplitA):
            self._sparse_matvecs += 2 * it_n
        self._active_fraction = float(self.state.active_frac)
        wall = time.time() - t0
        self._solve_wall += wall
        self._ext("post_solve_loop")
        self.conv = float(self.state.conv)
        if self._ladder is not None:
            # tighten (never loosen) toward couple*conv, floored at min
            self._ladder_eps = min(
                self._ladder_eps,
                max(self._ladder["min"],
                    self._ladder["couple"] * self.conv))
        if tel.enabled:
            r = tel.registry
            r.counter("ph.iterations").inc()
            r.histogram("ph.iteration_seconds").observe(wall)
            r.gauge("ph.conv").set(self.conv)
            r.counter("pdhg.inner_iters_total").inc(it_n)
            r.counter("pdhg.restarts_total").inc(rst_n)
            r.gauge("pdhg.active_fraction").set(self._active_fraction)
            if isinstance(self.prep.A, SparseSplitA):
                r.counter("pdhg.sparse_matvecs").inc(2 * it_n)
            if self._ladder is not None:
                r.gauge("ph.superstep_eps").set(self._ladder_eps)
        return self.conv

    # -- crash-resume (resilience/checkpoint.py) --------------------------
    def _save_checkpoint(self, path):
        """Write the run checkpoint — the subclass override point:
        StreamingPH routes to the stream checkpoint format (host-
        resident W + sampler RNG state instead of device PHState)."""
        from .resilience.checkpoint import save_run_checkpoint
        save_run_checkpoint(path, self)

    def _maybe_checkpoint(self, k):
        path = self.options.get("run_checkpoint")
        if not path:
            return
        if k % int(self.options.get("checkpoint_every", 1)) == 0:
            self._save_checkpoint(path)

    def restore_run_checkpoint(self, path):
        """Install a full run checkpoint (state, bounds, iter) — the
        Iter0 replacement on a `resume_from=` run."""
        from .resilience.checkpoint import load_run_checkpoint
        load_run_checkpoint(path, self)
        global_toc(f"PH resumed from checkpoint {path} at iter "
                   f"{int(self.state.it)} "
                   f"(trivial_bound={self.trivial_bound})")
        return self.trivial_bound

    # -- main loop (reference phbase.py:875-979 iterk_loop) ---------------
    def iterk_loop(self):
        max_iters = int(self.options.get("PHIterLimit", 100))
        convthresh = float(self.options.get("convthresh", 1e-4))
        verbose = self.options.get("verbose", False)
        # a resumed run continues from the checkpointed iteration so
        # the total iteration budget matches the uninterrupted run
        start = int(self.state.it) if self.state is not None else 0
        for k in range(start + 1, max_iters + 1):
            conv = self.ph_iteration()
            self._ext("miditer")
            if verbose or k % 10 == 0 or k == 1:
                eobj = float(self.Eobjective(self.state.obj))
                global_toc(f"PH iter {k:4d} conv={conv:.6e} "
                           f"E[obj]={eobj:.6g}")
            self._ext("enditer")
            self._maybe_checkpoint(k)
            # chaos crash-at-iter fires AFTER the checkpoint: the test
            # contract is "killed at iter k, resumable from iter k"
            self._chaos.hub_iter_tick(k)
            if self.spcomm is not None:
                self.spcomm.sync()
                if self.spcomm.is_converged():
                    global_toc(f"PH terminated by hub at iter {k}")
                    break
            if self.convobject is not None and self.convobject.is_converged():
                global_toc(f"PH terminated by converger "
                           f"{type(self.convobject).__name__} at iter {k}")
                break
            if conv < convthresh:
                global_toc(f"PH converged (conv={conv:.3e} < "
                           f"{convthresh}) at iter {k}")
                break
            self._ext("enditer_after_sync")
        self._ext("post_everything")
        return self.conv

    def post_loops(self):
        """Final expected objective (reference phbase.py:982).

        The denouement contract is the reference's
        (rank, scenario_name, scenario): each callback receives THAT
        scenario's data — a ScenarioView of its solution row — not the
        global state (reference spbase.py:505-522 usage)."""
        eobj = float(self.Eobjective(self.state.obj))
        if self.scenario_denouement is not None:
            for i, name in enumerate(self.all_scenario_names):
                self.scenario_denouement(0, name, self.scenario_view(i))
        return eobj

    def _host_state(self):
        """Bulk device->host materialization of the solution state
        (ONE gather per array, not one per scenario row)."""
        st = self.state
        return {
            "x": np.asarray(st.x),
            "nonants": np.asarray(st.x[:, self.batch.nonant_idx]),
            "obj": np.asarray(st.obj),
            "prob": np.asarray(self.batch.prob),
            "W": np.asarray(st.W),
            "xbar": np.asarray(st.xbar),
        }

    def scenario_view(self, i):
        """Per-scenario slice of the current state — the analog of the
        reference's Pyomo scenario instance handed to denouements and
        extensions (reference spbase.py:505-522).  The host copy is
        cached per iteration so S denouement calls cost one gather."""
        h = getattr(self, "_host_cache", None)
        if h is None or h["state"] is not self.state:
            # keyed on state identity (PHState is frozen: every update
            # makes a new object), so checkpoint installs and re-solves
            # can never serve a stale view
            h = dict(self._host_state(), state=self.state)
            self._host_cache = h
        return ScenarioView(
            index=i,
            name=self.all_scenario_names[i],
            x=h["x"][i],
            nonants=h["nonants"][i],
            obj=float(h["obj"][i]),
            prob=float(h["prob"][i]),
            W=h["W"][i],
            xbar=h["xbar"][i],
        )

    # -- bounds -----------------------------------------------------------
    def lagrangian_bound(self, W=None, certify="auto", eps=None):
        """Valid outer bound from the current W (reference:
        cylinders/lagrangian_bounder.py — re-solve with W-only objective,
        no prox, then Ebound).  Valid because the prob-weighted W sums to
        zero per node by construction of update_W.

        certify="auto": when the subproblems are LPs with all-finite
        variable boxes, the PDHG dual objective equals the Lagrangian
        g(y) exactly for ANY dual iterate, so the bound is valid without
        a convergence certificate and the solve never needs the f64
        fallback (the bound merely tightens as y converges).  Otherwise
        falls back to certify=True: drive every scenario to the KKT
        tolerance and mask any uncertified scenario out of the published
        bound (-inf).  `eps` optionally loosens this solve alone
        (options key "lagrangian_eps") — a looser y costs bound
        tightness, never validity (in the auto/LP case)."""
        self.check_W_bound_supported()
        self._tel.counter("ph.lagrangian_bound_calls").inc()
        b = self.batch
        W = self.state.W if W is None else W
        c_eff = b.c.at[:, b.nonant_idx].add(W)
        if certify == "auto":
            certify = not (self.is_lp and self.all_bounds_finite)
        if eps is None:
            eps = self.options.get("lagrangian_eps")
        if eps is not None:
            eps = jnp.asarray(eps, b.c.dtype)
        # optional per-solve budget ("lagrangian_iters_cap"): in the
        # auto/LP case a CAPPED solve is still a valid bound (dual
        # objective valid at any iterate) — it only costs tightness.
        # The W-only objective has no prox term, so uncapped bound
        # solves cost ~4x a PH iteration; a cap makes the bound-check
        # cadence affordable.  Never applied when certify is on
        # (capped+certified would mask most scenarios to -inf).
        cap = None if certify else self.options.get(
            "lagrangian_iters_cap")
        res = self.solve_loop(c=c_eff, warm="lagrangian", certify=certify,
                              eps=eps, iters_cap=cap)
        return float(self.Ebound(res.dual_obj,
                                 converged=res.converged if certify
                                 else None))

    # -- spoke support ----------------------------------------------------
    def root_xbar(self):
        """Root-node consensus vector (K,) — candidate first-stage
        solution, for xhat spokes and solution writers."""
        return self.state.xbar[0]
