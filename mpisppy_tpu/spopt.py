"""SPOpt — the solve engine (reference: mpisppy/spopt.py, 903 LoC).

Where the reference's `solve_loop` walks local subproblems serially and
crosses a process boundary into Gurobi per scenario (spopt.py:226, :85),
here one call = one jitted batched PDHG solve over ALL scenarios at
once.  Objective modifications (PH's W and prox, Lagrangian W-only,
xhat fixing) arrive as array arguments — the nonant fix/restore caches
of the reference (spopt.py:528-740) become pure functions of bounds
arrays.

Expectations (Eobjective spopt.py:310, Ebound :346) are probability-
weighted sums over the sharded scenario axis; XLA inserts the psum.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from . import global_toc
from . import telemetry as _telemetry
from .ir import (SparseSplitA, SplitA, bmatvec, delta_idx, delta_runs,
                 shared_density, sparsify_split)
from .ops.pdhg import (PDHGSolver, PreparedBatch, prepare_batch,
                       prepare_batch_split, prepare_split_native)
from .spbase import SPBase


def _host(a):
    """Host (numpy) copy of `a`.  A jax.Array that spans devices of
    other processes (multi-host meshes) cannot be fetched directly: a
    replicated one is read from a local copy, a sharded one is
    allgathered — a collective, which every process reaches in the same
    order because solve_loop runs SPMD."""
    if isinstance(a, jax.Array) and not a.is_fully_addressable:
        if a.is_fully_replicated:
            return np.asarray(a.addressable_data(0))
        from jax.experimental import multihost_utils
        return np.asarray(multihost_utils.process_allgather(a, tiled=True))
    return np.asarray(a)


class SPOpt(SPBase):
    # subclasses needing one column scaling shared across scenarios
    # (consensus/EF solves) set this so the batch is prepared once
    _shared_cols = False
    # subclasses that tile / index prep.A as a dense array (the MIP
    # dive's stacked bound-variants) opt out of the SplitA fast path
    _use_split_prep = True

    def __init__(self, *args, prep=None, **kwargs):
        super().__init__(*args, **kwargs)
        o = self.options
        self.solver = PDHGSolver.from_options(o)
        if prep is not None:
            # shared PreparedBatch from a sibling cylinder over the SAME
            # batch (WheelSpinner passes the hub's — Ruiz scaling and the
            # norm estimate depend only on (A, row bounds, _shared_cols))
            self.prep = prep
        else:
            global_toc("Preparing batch (Ruiz scaling + ||A|| estimate)")
            self.prep = self._build_prep(hot=self.solver.hot_dtype)
        # density of the shared constraint block actually carried by the
        # prep (None for non-split preps), reported by pdhg_stats
        self._shared_nnz_frac = (float(shared_density(self.prep.A))
                                 if isinstance(self.prep.A, SplitA)
                                 else None)
        # warm-start caches (analog of persistent-solver state,
        # reference spopt.py:877 set_instance_retry — license logic gone)
        self._x_warm = None
        self._y_warm = None
        self._named_warm = {}
        self._solve_times = []
        self._solve_wall = 0.0     # accumulated timed solve seconds
        self._certify_wall = 0.0   # seconds inside f64 certified re-solves
        self._kernel_iters = 0     # accumulated PDHG kernel iterations
        self._restarts_total = 0   # accumulated PDHG restart events
        self._active_traj = []     # last compacted solve's trajectory
        self._active_fraction = 1.0  # last solve's final active fraction
        self._promotions = 0       # solves promoted hot-dtype -> full
        self._sparse_matvecs = 0   # matvecs routed through BCOO
        # telemetry (telemetry/): the options value configures the
        # process-global handle; every instrument lookup below is a
        # null no-op when disabled (zero-cost-when-off contract)
        self._tel = _telemetry.configure_from_options(o.get("telemetry"))
        # dynamic solver tolerance (Gapper schedules it) as a jnp
        # scalar — traced, so schedule changes never recompile
        self.solver_eps = jnp.asarray(self.solver.eps, self.batch.c.dtype)
        # f64 fallback solver for certified solves (lazily built)
        self._solver64 = None
        # full-precision (solver, prep) pair a hot-dtype run promotes
        # to once the tolerance crosses the dtype's eps floor
        self._promoted_cache = None
        self._np_cache = {}

    def _build_prep(self, hot=None, batch=None):
        """Ruiz scaling + ||A|| estimate over the batch constraint data.

        hot: a HOT_DTYPES key — cast A and the row bounds to that mode's
        COMPUTE dtype first, so the equilibration and the power-iteration
        norm estimate themselves run in low precision (the prep is an
        input to the hot loop only; certified paths build their own f64
        prep in `_certified_resolve`).  When the solver carries a
        sparse_threshold, a SplitA prep whose shared block is sparse
        enough is converted to the BCOO-backed SparseSplitA afterward —
        Ruiz row/column scaling preserves the zero pattern, so the
        density measured post-scaling equals the structural density.

        batch: prepare a DIFFERENT ScenarioBatch than self.batch with
        the same routing (split-native / delta-split / dense) — the
        streaming layer preps each sampled scenario block through here
        so every pow2 block width hits the per-shape jit caches of the
        prepare_* functions.
        """
        b = self.batch if batch is None else batch
        o = self.options
        A, row_lo, row_hi = b.A, b.row_lo, b.row_hi
        pair = (self.solver._hot_pair(jnp.asarray(b.c).dtype)
                if hot else None)
        if pair is not None:
            compute = pair[1]
            A = (A.astype(compute) if isinstance(A, SplitA)
                 else jnp.asarray(A, compute))
            row_lo = jnp.asarray(row_lo, compute)
            row_hi = jnp.asarray(row_hi, compute)
        delta = delta_idx(b)
        if b.split_A:
            # batch born split-native (no dense A exists, true-size
            # instances): the split prep is the ONLY prep
            prep = prepare_split_native(A, row_lo, row_hi)
        elif (delta is not None and self._use_split_prep
                and not b.shared_A and not o.get("no_split_prep")):
            # sparse matrix uncertainty (ir.SplitA): shared-scaling
            # Ruiz keeps the shared+delta structure, and shared
            # columns satisfy _shared_cols implicitly
            prep = prepare_batch_split(
                A, jnp.asarray(delta[0], jnp.int32),
                jnp.asarray(delta[1], jnp.int32), row_lo, row_hi,
                runs=delta_runs(delta[0], delta[1], *b.A.shape[1:]))
        else:
            prep = prepare_batch(A, row_lo, row_hi,
                                 shared_cols=self._shared_cols)
        if self.solver.sparse_threshold > 0.0 \
                and isinstance(prep.A, SplitA):
            spA = sparsify_split(prep.A, self.solver.sparse_threshold)
            if spA is not prep.A:
                prep = dataclasses.replace(prep, A=spA)
        return prep

    def _promoted_pair(self):
        """The full-precision (solver, prep) pair used once a solve's
        tolerance crosses the hot dtype's eps floor.  Built lazily (one
        extra prep + at most one extra jit compile per run — promotion
        is monotone under the eps ladder) and cached."""
        if self._promoted_cache is None:
            self._promoted_cache = (self.solver.clone(hot_dtype=None),
                                    self._build_prep(hot=None))
        return self._promoted_cache

    def active_solver_prep(self, eps=None, count=True):
        """(solver, prep) for a solve at tolerance `eps`: the configured
        pair until `eps` crosses the hot dtype's floor (100x machine
        epsilon of the compute dtype), then the promoted full-precision
        pair.  With no hot_dtype this is always (self.solver, self.prep).
        count=True increments the promotion accounting when the
        promoted pair is selected."""
        e = float(self.solver_eps if eps is None else eps)
        if not self.solver.wants_promotion(e):
            return self.solver, self.prep
        solver, prep = self._promoted_pair()
        if count:
            self._promotions += 1
            if self._tel.enabled:
                self._tel.registry.counter("pdhg.promotions").inc()
        return solver, prep

    # -- hot path ---------------------------------------------------------
    def solve_loop(self, c=None, qdiag=None, lb=None, ub=None,
                   warm=True, dtiming=False, certify=False, eps=None,
                   iters_cap=None, batch=None, prep=None, x0=None,
                   y0=None):
        """Solve every scenario subproblem (batched).  Any of
        c/qdiag/lb/ub override the batch's own arrays (this is how PH,
        Lagrangian and xhat objectives/fixings are expressed).

        warm: True/False for the default warm-start cache, or a string
        TAG for a named cache — repeated bound evaluations (xhat,
        Lagrangian) warm-start from their own previous solve instead
        of going cold (the persistent-solver analog, spopt.py:877).

        batch/prep: solve a DIFFERENT ScenarioBatch than self.batch
        (the streaming layer's sampled blocks).  A block solve must
        bring its own prep (the Ruiz scaling belongs to the block's
        constraint data) and manages warm starts explicitly via
        x0/y0 — the instance warm caches are shaped for self.batch, so
        block solves neither read nor write them.  certify is
        unsupported on block solves (`_certified_resolve` scatters
        into self.batch-shaped results).

        x0/y0: explicit warm-start point; overrides the warm cache.

        certify: drive scenarios to the KKT tolerance via a float64
        re-solve.  Scenarios the fast (typically f32) batched solve
        leaves unconverged — the f32 primal-residual floor sits ~1e-4
        on ill-scaled instances — are gathered into a compact float64
        sub-batch and re-solved warm-started (on the CPU backend when
        the accelerator lacks f64).  This is the analog of the
        reference's solver-status classification + retry
        (spopt.py:175-194).  Modes:
          False  — never refine;
          True   — refine every non-converged prob>0 scenario;
          "feas" — refine only PRIMAL-infeasible scenarios (pres over
                   tolerance).  Dual-side non-convergence is left
                   alone — it only weakens bounds, which Ebound
                   handles (mask / finite-box validity) — so solves
                   that legitimately ride to a big artificial box
                   (e.g. an epigraph variable before its cuts exist)
                   are not chased to the bottom.

        Returns the ops.pdhg.SolveResult.
        """
        if batch is not None:
            if prep is None:
                raise ValueError(
                    "solve_loop(batch=...) requires an explicit prep "
                    "for the block's constraint data")
            if certify:
                raise ValueError(
                    "certify is not supported on block solves "
                    "(batch=...): _certified_resolve scatters into "
                    "self.batch-shaped results")
        b = self.batch if batch is None else batch
        tel = self._tel
        with tel.span("solve.loop"):
            t0 = time.time()
            if batch is not None:
                cache = (x0, y0)
            elif isinstance(warm, str):
                cache = self._named_warm.get(warm, (None, None))
            else:
                cache = (self._x_warm, self._y_warm) if warm else (None, None)
                if x0 is not None or y0 is not None:
                    cache = (x0, y0)
            eps_arg = self.solver_eps if eps is None else eps
            if prep is not None:
                # explicit prep (streaming block solves): hot-dtype
                # promotion does not apply — the caller chose the prep's
                # dtype, and a promoted solver with a mismatched-dtype
                # prep would silently recompile per call
                solver = self.solver
            else:
                # hot-dtype promotion: once the requested tolerance crosses
                # the low-precision eps floor, route this solve through the
                # full-precision pair (monotone under the ladder/Gapper
                # schedules, so this re-routes at most once per run)
                solver, prep = self.active_solver_prep(eps_arg)
            args = (prep,
                    b.c if c is None else c,
                    b.qdiag if qdiag is None else qdiag,
                    b.lb if lb is None else lb,
                    b.ub if ub is None else ub)
            kw = dict(obj_const=b.obj_const, x0=cache[0], y0=cache[1],
                      eps=eps_arg)
            # compaction (opt-in via pdhg_compact_threshold) applies only
            # to uncapped solves: an iters_cap caller is screening and owns
            # its own budget/shape discipline
            if solver.compact_threshold > 0.0 and iters_cap is None:
                traj = []
                res = solver.solve_compacted(
                    *args, **kw, probs=b.prob, on_segment=traj.append)
                self._active_traj = traj
                full = float(max(int(np.sum(_host(b.prob) > 0)), 1))
                self._active_fraction = (traj[-1]["active"] / full
                                         if traj else 0.0)
            else:
                res = solver.solve(*args, **kw, iters_cap=iters_cap)
                self._active_fraction = float(
                    np.sum(~_host(res.converged)
                           & (_host(b.prob) > 0))
                    / max(int(np.sum(_host(b.prob) > 0)), 1))
            it_n = int(_host(res.iters))
            rst_n = int(np.sum(_host(res.restarts)))
            if isinstance(prep.A, SparseSplitA):
                # two shared-block products (forward + transpose) per
                # inner iteration route through jax.experimental.sparse
                self._sparse_matvecs += 2 * it_n
            self._kernel_iters += it_n
            self._restarts_total += rst_n
            if certify:
                select = None
                if certify == "feas":
                    tol = 10 * float(self.solver_eps)
                    select = _host(res.pres) >= tol
                res = self._certified_resolve(res, c, qdiag, lb, ub,
                                              select=select)
            if batch is not None:
                pass  # block solves never clobber the self.batch-shaped caches
            elif isinstance(warm, str):
                self._named_warm[warm] = (res.x, res.y)
            elif warm:
                self._x_warm = res.x
                self._y_warm = res.y
            jax.block_until_ready(res.x)
            dt = time.time() - t0
        self._solve_wall += dt
        if tel.enabled:
            r = tel.registry
            r.counter("solve.calls").inc()
            r.counter("solve.kernel_iters").inc(it_n)
            r.histogram("solve.seconds").observe(dt)
            r.counter("pdhg.inner_iters_total").inc(it_n)
            r.counter("pdhg.restarts_total").inc(rst_n)
            r.gauge("pdhg.active_fraction").set(self._active_fraction)
            r.gauge("pdhg.active_scenarios").set(
                self._active_fraction
                * int(np.sum(_host(b.prob) > 0)))
            if isinstance(prep.A, SparseSplitA):
                r.counter("pdhg.sparse_matvecs").inc(2 * it_n)
            if rst_n:
                # mean restart cycle length in inner iterations: total
                # iterate-steps taken across the batch over the number
                # of cycles those steps were split into
                r.event("pdhg.restart", count=rst_n,
                        mean_cycle=it_n * b.num_scens / max(
                            rst_n + b.num_scens, 1),
                        iters=it_n)
        if dtiming or self.options.get("display_timing"):
            self._solve_times.append(dt)
            global_toc(f"solve_loop: {dt*1e3:8.1f} ms, "
                       f"iters={int(_host(res.iters))}, "
                       f"conv={int(np.sum(_host(res.converged)))}"
                       f"/{b.num_scens}")
        return res

    # -- certified fallback ----------------------------------------------
    def _np64(self, key, arr):
        """Cached float64 numpy view of a static batch array."""
        hit = self._np_cache.get(key)
        if hit is None:
            hit = np.asarray(arr, np.float64)
            self._np_cache[key] = hit
        return hit

    def _certified_resolve(self, res, c=None, qdiag=None, lb=None,
                           ub=None, A=None, row_lo=None, row_hi=None,
                           obj_const=None, prep_key="_prep64",
                           select=None):
        """Re-solve unconverged prob>0 scenarios in float64, warm-started
        from the fast solve, and scatter the refined solutions back.

        Float32 PDHG stalls at a primal-residual floor ~1e-4 on a small
        fraction of ill-scaled scenarios (measured: 155/1000 on
        farmer-1000, crops_mult=10); the same instances converge in
        ~2.5k f64 iterations.  This path refines INDEPENDENT
        per-scenario solves only (solve_loop never passes a
        ConsensusSpec); the coupled consensus (EF) solve has its own
        full-batch f64 fallback in opt/ef.py solve_extensive_form.

        A/row_lo/row_hi/obj_const override the batch constraint data
        (the reduced xhat path passes its eliminated-column system);
        prep_key names the cached f64 scaling for the given A —
        Ruiz/anorm depend only on A, so the full-batch f64 prep is
        computed once per key and indexed per call.
        """
        conv = _host(res.converged)
        live = _host(self.batch.prob) > 0
        pick = ~conv if select is None else np.asarray(select)
        idx = np.flatnonzero(pick & live)
        if idx.size == 0:
            return res
        t_cert = time.time()
        b = self.batch
        A = b.A if A is None else A
        row_lo = b.row_lo if row_lo is None else row_lo
        row_hi = b.row_hi if row_hi is None else row_hi
        obj_const = b.obj_const if obj_const is None else obj_const
        sub = {
            "obj_const": np.asarray(obj_const, np.float64)[idx],
            "row_lo": np.asarray(row_lo, np.float64)[idx],
            "row_hi": np.asarray(row_hi, np.float64)[idx],
            "c": np.asarray(b.c if c is None else c, np.float64)[idx],
            "qdiag": np.asarray(
                b.qdiag if qdiag is None else qdiag, np.float64)[idx],
            "lb": np.asarray(b.lb if lb is None else lb, np.float64)[idx],
            "ub": np.asarray(b.ub if ub is None else ub, np.float64)[idx],
            "x0": np.asarray(res.x, np.float64)[idx],
            "y0": np.asarray(res.y, np.float64)[idx],
        }
        # options["certify_max_iters"] bounds the f64 fallback's
        # budget: on accelerators without f64 this path runs on the
        # host CPU, and an uncapped 100k-iteration re-solve of a
        # large straggler set can dominate wall-clock (r4 UC-on-TPU
        # timeout); a capped certify still improves stragglers and
        # the Ebound mask keeps unrescued ones out of the bound.
        # Keyed on the RESOLVED budget so an extension rescheduling
        # the option mid-run gets a fresh solver, not a stale cache.
        cert_iters = int(self.options.get(
            "certify_max_iters", max(self.solver.max_iters, 100000)))
        if self._solver64 is None or \
                self._solver64.max_iters != cert_iters:
            # clone: keeps the restart policy/betas (and every future
            # knob) in lockstep with the fast solver's config.
            # hot_dtype is pinned OFF: the certified verdict is this
            # path's whole purpose, so it never inherits a low-precision
            # hot loop (AST-guarded in tests/test_precision.py).
            self._solver64 = self.solver.clone(
                max_iters=cert_iters, hot_dtype=None)
        try:
            cpu = jax.devices("cpu")[0]
        except RuntimeError:
            cpu = None
        with jax.enable_x64(True):
            put = ((lambda a: jax.device_put(a, cpu))
                   if cpu is not None else jnp.asarray)
            full = self._np_cache.get(prep_key)
            if full is None:
                if isinstance(A, SplitA):
                    # split-native constraint data: the f64 prep stays
                    # split too (the dense (S, M, N) tensor may be
                    # unmaterializable at true-size instances)
                    a64 = SplitA(
                        shared=put(np.asarray(A.shared, np.float64)),
                        rows=put(np.asarray(A.rows)),
                        cols=put(np.asarray(A.cols)),
                        vals=put(np.asarray(A.vals, np.float64)))
                    full = prepare_split_native(
                        a64,
                        put(np.asarray(row_lo, np.float64)),
                        put(np.asarray(row_hi, np.float64)))
                else:
                    full = prepare_batch(
                        put(np.asarray(A, np.float64)),
                        put(np.asarray(row_lo, np.float64)),
                        put(np.asarray(row_hi, np.float64)),
                        shared_cols=self._shared_cols)
                full = jax.tree.map(np.asarray, full)
                self._np_cache[prep_key] = full

            S_all = self.batch.num_scens

            def take(a):
                # shared-A batches keep singleton leaves (A, d_row,
                # d_col, anorm stay (1, ...)); per-scenario leaves are
                # gathered to the straggler sub-batch
                return a if (a.shape[0] == 1 and S_all > 1) else a[idx]

            if isinstance(full.A, SplitA):
                # only the per-scenario delta values gather; the shared
                # matrix and coordinates serve every straggler as-is
                sub_A = jax.tree.map(put, dataclasses.replace(
                    full.A, vals=full.A.vals[idx]))
                prep64 = PreparedBatch(
                    A=sub_A,
                    row_lo=put(take(full.row_lo)),
                    row_hi=put(take(full.row_hi)),
                    d_row=put(take(full.d_row)),
                    d_col=put(take(full.d_col)),
                    anorm=put(take(full.anorm)))
            else:
                prep64 = jax.tree.map(lambda a: put(take(a)), full)
            # row bounds may be call-specific (xhat candidates shift
            # them); rebuild the scaled fields from the raw bounds
            dr = np.asarray(take(np.asarray(full.d_row)))
            prep64 = dataclasses.replace(
                prep64,
                row_lo=put(np.where(np.isfinite(sub["row_lo"]),
                                    sub["row_lo"] * dr, sub["row_lo"])),
                row_hi=put(np.where(np.isfinite(sub["row_hi"]),
                                    sub["row_hi"] * dr, sub["row_hi"])))
            r64 = self._solver64.solve(
                prep64, put(sub["c"]), put(sub["qdiag"]),
                put(sub["lb"]), put(sub["ub"]),
                obj_const=put(sub["obj_const"]),
                x0=put(sub["x0"]), y0=put(sub["y0"]),
                eps=float(self.solver_eps))
            jax.block_until_ready(r64.x)
        dt_cert = time.time() - t_cert
        self._certify_wall += dt_cert
        if self._tel.enabled:
            r = self._tel.registry
            r.counter("solve.certify_calls").inc()
            r.counter("solve.certify_scenarios").inc(int(idx.size))
            r.histogram("solve.certify_seconds").observe(dt_cert)
        n_ok = int(np.sum(np.asarray(r64.converged)))
        if n_ok < idx.size:
            global_toc(f"WARNING: f64 fallback left {idx.size - n_ok} "
                       f"scenario(s) unconverged")
        dt = res.x.dtype
        ix = jnp.asarray(idx)

        def scat(a, a64, d=dt):
            return a.at[ix].set(jnp.asarray(np.asarray(a64), d))

        restarts = res.restarts
        if getattr(restarts, "ndim", 0):     # (S,) array, not the
            restarts = restarts.at[ix].add(  # scalar-0 pytree default
                jnp.asarray(np.asarray(r64.restarts), restarts.dtype))
        return dataclasses.replace(
            res,
            x=scat(res.x, r64.x), y=scat(res.y, r64.y),
            obj=scat(res.obj, r64.obj),
            dual_obj=scat(res.dual_obj, r64.dual_obj),
            pres=scat(res.pres, r64.pres), dres=scat(res.dres, r64.dres),
            gap=scat(res.gap, r64.gap),
            converged=scat(res.converged, r64.converged, bool),
            restarts=restarts)

    def clear_warmstart(self):
        self._x_warm = None
        self._y_warm = None
        self._named_warm = {}

    # -- expectations (Allreduce analogs) ---------------------------------
    def Eobjective(self, objs):
        """E[objective] over scenarios (reference spopt.py:310).  `objs`
        is the per-scenario (S,) objective; padding scenarios carry
        probability 0 so they vanish."""
        return jnp.sum(self.batch.prob * objs)

    def Ebound(self, dual_objs, converged=None):
        """Valid expected outer bound from per-scenario dual objectives
        (reference spopt.py:346 uses solver bounds).

        converged: optional (S,) bool certification mask.  A prob>0
        scenario without a certificate contributes -inf (minimization),
        so an uncertified solve can never publish a finite bound —
        the conservative analog of the reference's solver-status gate
        (spopt.py:175-194).  Use solve_loop(certify=True) to obtain
        the mask."""
        vals = self.batch.prob * dual_objs
        if converged is not None:
            bad = (~converged) & (self.batch.prob > 0)
            vals = jnp.where(bad, -jnp.inf, vals)
        return jnp.sum(vals)

    def reset_solve_stats(self):
        """Zero the wall-clock and work accumulators (benchmarks call
        this after compile warmup so the counts cover the timed
        region)."""
        self._solve_wall = 0.0
        self._certify_wall = 0.0
        self._kernel_iters = 0
        self._solve_times = []
        self._restarts_total = 0
        self._active_traj = []
        self._active_fraction = 1.0
        self._promotions = 0
        self._sparse_matvecs = 0

    def pdhg_stats(self):
        """Adaptive-work counters across all solve_loop calls since the
        last reset: total inner iterations, restart events, the final
        active fraction, the last compacted solve's active-fraction
        trajectory (one entry per segment), plus the precision/sparsity
        state: the configured hot dtype, solves promoted to full
        precision, sparse matvec count and the shared-block density
        (None when the prep carries no split matrix)."""
        return {
            "inner_iters": int(self._kernel_iters),
            "restarts_total": int(self._restarts_total),
            "active_fraction_final": float(self._active_fraction),
            "active_fraction_traj": list(self._active_traj),
            "hot_dtype": self.solver.hot_dtype,
            "promotions_total": int(self._promotions),
            "sparse_matvecs": int(self._sparse_matvecs),
            "shared_nnz_frac": self._shared_nnz_frac,
        }

    def solve_stats(self):
        """Accumulated wall-clock across all solve_loop calls (dtiming
        analog): seconds in the timed solves, and the seconds spent in
        float64 certified re-solves."""
        return {
            "solve_wall_s": self._solve_wall,
            "certify_wall_s": self._certify_wall,
        }

    def feas_prob(self, res, tol=None):
        """Probability mass of scenarios whose solve is feasible/
        converged (reference spopt.py:411 feas_prob; :175-194
        classifies solver status).  First-order analog: primal residual
        under tolerance.  The tolerance tracks the DYNAMIC solver_eps
        (Gapper schedules it per iteration), not the construction-time
        eps — a deliberately loose early solve is not 'infeasible'."""
        tol = tol or 10 * float(self.solver_eps)
        ok = res.pres < tol
        return float(jnp.sum(jnp.where(ok, self.batch.prob, 0.0)))

    def infeas_prob(self, res, tol=None):
        return 1.0 - self.feas_prob(res, tol)

    @property
    def is_lp(self):
        """True when every subproblem is an LP (no quadratic term)."""
        hit = self._np_cache.get("_is_lp")
        if hit is None:
            hit = not bool(jnp.any(self.batch.qdiag != 0))
            self._np_cache["_is_lp"] = hit
        return hit

    def valid_Ebound(self, res):
        """Outer bound that is ALWAYS valid: for LPs with all-finite
        variable boxes the PDHG dual objective equals the Lagrangian
        g(y) exactly at ANY iterate, so no certificate is needed;
        otherwise uncertified scenarios are masked to -inf (Ebound)."""
        if self.is_lp and self.all_bounds_finite:
            return self.Ebound(res.dual_obj)
        return self.Ebound(res.dual_obj, converged=res.converged)

    def check_W_bound_supported(self):
        """W-based Lagrangian bounds are valid because the scenario-
        probability-weighted W sums to zero per node (phbase.update_W
        with probability-weighted xbar).  Under variable_probability
        the xbar weights differ from the scenario probabilities, that
        telescoping breaks, and a W-relaxation bound would be WRONG —
        fail loudly (the conservative stance this build takes wherever
        a bound would silently lose validity)."""
        if self.batch.var_prob is not None:
            raise NotImplementedError(
                "W-based Lagrangian bounds are not valid under "
                "variable_probability (prob-weighted W no longer "
                "telescopes to zero per node); use the EF consensus "
                "solve or Iter0's W-free bound instead")

    @property
    def all_bounds_finite(self):
        """True when every variable box is finite — then the PDHG dual
        objective is an exact Lagrangian value for ANY dual iterate
        (no infinite-bound reduced-cost mass to drop), so Ebound is
        valid without a convergence certificate."""
        hit = self._np_cache.get("_bounds_finite")
        if hit is None:
            hit = bool(jnp.all(jnp.isfinite(self.batch.lb))
                       and jnp.all(jnp.isfinite(self.batch.ub)))
            self._np_cache["_bounds_finite"] = hit
        return hit

    def avg_min_max(self, vals):
        """Prob>0-masked avg/min/max of a per-scenario quantity
        (reference spopt.py:469)."""
        mask = self.batch.prob > 0
        v = np.asarray(vals)
        vm = v[np.asarray(mask)]
        return float(np.mean(vm)), float(np.min(vm)), float(np.max(vm))

    # -- xhat evaluation (reduced second-stage solve) ---------------------
    #
    # Fixing nonants via lb=ub=v is how the reference does it (Pyomo
    # var.fix), but it is hostile to a first-order solver: every fixed
    # coordinate reads as "at bound" (blinding the dual residual), the
    # step sizes were tuned for the full operator norm, and — decisive —
    # a candidate averaged from tolerance-accurate scenario solutions
    # violates pure-first-stage rows by ~S*eps absolute, making the
    # equality-fixed problem literally infeasible (measured: xbar on
    # farmer-1000/f32 violates total-acreage by ~0.05; f64 PDHG then
    # pins pres at 1e-5 forever with gap ~0.7).  Commercial solvers
    # absorb this with an absolute feasibility tolerance; we do the
    # equivalent, structurally: ELIMINATE the fixed columns
    # (row bounds -= A_na @ v, objective const += c_na @ v), solve the
    # well-scaled reduced problem with its own Ruiz prep, and widen the
    # reduced row bounds by a relative feastol (option "xhat_feastol",
    # default 1e-5 — the analog of Gurobi FeasibilityTol).

    @staticmethod
    def _shift_and_widen_rows(prep, row_lo, row_hi, shift, ftol):
        """Shared by evaluate_xhat and evaluate_candidates: shift the
        row bounds by the fixed-column contribution and widen by the
        feastol slack (at the scale of |shift| ~ |A_na @ v|, see the
        block comment above _xhat_cache), then rebuild the scaled prep
        row bounds.  ONE implementation so the certified single-
        candidate path and the stacked screening path can never
        disagree about a candidate's feasibility."""
        slack = ftol * (1.0 + jnp.abs(shift))
        rlo = row_lo - shift
        rhi = row_hi - shift
        rlo = jnp.where(jnp.isfinite(rlo),
                        rlo - slack - ftol * (1.0 + jnp.abs(rlo)), rlo)
        rhi = jnp.where(jnp.isfinite(rhi),
                        rhi + slack + ftol * (1.0 + jnp.abs(rhi)), rhi)
        prep2 = dataclasses.replace(
            prep,
            row_lo=jnp.where(jnp.isfinite(rlo), rlo * prep.d_row, rlo),
            row_hi=jnp.where(jnp.isfinite(rhi), rhi * prep.d_row, rhi))
        return prep2, rlo, rhi

    def _xhat_cache(self, upto_stage=None):
        key = ("xhat_red", upto_stage)
        hit = self._np_cache.get(key)
        if hit is not None:
            return hit
        b = self.batch
        na = np.asarray(b.nonant_idx)
        pos = np.arange(na.size)
        if upto_stage is not None:
            stage = np.asarray(b.tree.stage_of)
            pos = np.flatnonzero(stage <= upto_stage)
            na = na[pos]
        nai = jnp.asarray(na, jnp.int32)
        delta = delta_idx(b)
        if b.split_A:
            # split-native batch: the reduced system exists only if
            # every scenario-varying entry sits in an ELIMINATED column
            # (farmer: yields multiply the nonant acreages) — then
            # A_red is the scenario-independent shared matrix with the
            # nonant columns dropped, and the per-scenario part lives
            # entirely in the A_na row-bound shift, expressed as a
            # SplitA over the REDUCED (Kf-wide) column space
            cols_np = np.asarray(b.A.cols)
            if not np.all(np.isin(cols_np, na)):
                raise NotImplementedError(
                    "xhat evaluation on a split-native batch requires "
                    "all A-delta columns to be eliminated (nonant) "
                    "columns; this batch has deltas in kept columns")
            pos_of = np.zeros(b.num_vars, np.int64)
            pos_of[na] = np.arange(na.size)
            A_na = SplitA(
                shared=jnp.asarray(b.A.shared)[:, nai],   # (M, Kf)
                rows=jnp.asarray(b.A.rows, jnp.int32),
                cols=jnp.asarray(pos_of[cols_np], jnp.int32),
                vals=b.A.vals).with_runs()
            A_red = jnp.asarray(b.A.shared)[None].at[:, :, nai].set(0.0)
        elif (delta is not None and not b.shared_A
                and not self.options.get("no_split_prep")
                and np.all(np.isin(np.asarray(delta[1]), na))):
            # every scenario-varying matrix entry sits in an ELIMINATED
            # column (farmer: yields multiply the nonant acreages), so
            # the reduced system is scenario-independent — store it
            # (1, M, N) and every downstream solve rides the shared-A
            # matmul fast path (the per-scenario part lives entirely in
            # the A_na shift of the row bounds)
            A_na = jnp.take(b.A, nai, axis=2)          # (S, M, Kf)
            A_red = jnp.asarray(b.A[0:1]).at[:, :, nai].set(0.0)
        else:
            A_na = jnp.take(b.A, nai, axis=2)          # (S, M, Kf)
            A_red = jnp.asarray(b.A).at[:, :, nai].set(0.0)
        c_na = jnp.take(b.c, nai, axis=1)
        q_na = jnp.take(b.qdiag, nai, axis=1)
        c_red = jnp.asarray(b.c).at[:, nai].set(0.0)
        q_red = jnp.asarray(b.qdiag).at[:, nai].set(0.0)
        lb_red = jnp.asarray(b.lb).at[:, nai].set(0.0)
        ub_red = jnp.asarray(b.ub).at[:, nai].set(0.0)
        prep = prepare_batch(A_red, b.row_lo, b.row_hi)
        # FeasibilityTol analog, scaled to the accuracy of the solves
        # that GENERATE candidates (the loosest of the solver eps and
        # the PH hot-loop superstep_eps): a candidate averaged from
        # eps-accurate solutions violates first-stage rows by ~eps
        # relative, so a few eps of slack absorbs it; a fixed large
        # default would grant the reduced LP real objective slack
        # (measured: ftol=1e-5 at f64/eps=1e-7 made inner bounds
        # ~4e-5 optimistic)
        gen_eps = max(self.solver.eps,
                      float(self.options.get("superstep_eps") or 0.0))
        ftol = float(self.options.get(
            "xhat_feastol", min(1e-3, 3.0 * gen_eps)))

        def impl(vals, x0, y0, eps):
            vals2 = jnp.broadcast_to(
                jnp.atleast_2d(vals), (b.num_scens, na.size)
            ).astype(b.c.dtype)
            shift = bmatvec(A_na, vals2)
            prep2, rlo, rhi = self._shift_and_widen_rows(
                prep, b.row_lo, b.row_hi, shift, ftol)
            oc = (b.obj_const + jnp.sum(c_na * vals2, axis=1)
                  + 0.5 * jnp.sum(q_na * vals2 * vals2, axis=1))
            return self.solver._solve_impl(
                prep2, c_red, q_red, lb_red, ub_red, oc, x0, y0,
                None, eps), (rlo, rhi, oc)

        hit = {"na": na, "pos": pos, "A_na": A_na, "A_red": A_red,
               "c_red": c_red,
               "q_red": q_red, "lb_red": lb_red, "ub_red": ub_red,
               "prep": prep, "jit": jax.jit(impl), "impl": impl,
               "ftol": ftol}
        self._np_cache[key] = hit
        return hit

    def evaluate_xhat(self, nonant_values, upto_stage=None, tol=None,
                      warm="xhat_eval", certify="auto"):
        """Expected objective with nonants fixed to a candidate — the
        implementable inner bound (reference utils/xhat_eval.py:293).
        Returns (Eobj, feasible).  Successive evaluations warm-start
        from the named cache (candidates move slowly).

        Validity: the objective at any PRES-FEASIBLE point upper-bounds
        the subproblem optimum regardless of dual convergence, so the
        inner bound needs only primal feasibility (within the
        documented xhat_feastol, the FeasibilityTol analog).
        certify="auto" runs the f64 fallback only when the fast solve
        fails the feasibility check; certify=True always refines
        stragglers."""
        t0 = time.time()
        cache = self._xhat_cache(upto_stage)
        b = self.batch
        # callers pass full-K candidate vectors; slice to the slots the
        # cache eliminates (upto_stage filters to early-stage slots)
        vals = jnp.asarray(nonant_values)[..., jnp.asarray(cache["pos"])]
        x0, y0 = self._named_warm.get(warm, (None, None))
        if x0 is None:
            x0 = jnp.zeros_like(b.c)
            y0 = jnp.zeros_like(b.row_lo)
        res, (rlo, rhi, oc) = cache["jit"](
            vals, x0, y0, self.solver_eps)
        if certify == "auto":
            certify = not (self.feas_prob(res, tol=tol) > 1.0 - 1e-6)
        if certify:
            res = self._certified_resolve(
                res, c=cache["c_red"], qdiag=cache["q_red"],
                lb=cache["lb_red"], ub=cache["ub_red"],
                A=cache["A_red"], row_lo=rlo, row_hi=rhi,
                obj_const=oc, prep_key=("_prep64_xhat", upto_stage))
        self._named_warm[warm] = (res.x, res.y)
        feas = self.feas_prob(res, tol=tol) > 1.0 - 1e-6
        eobj = float(self.Eobjective(res.obj))
        self._solve_wall += time.time() - t0
        return eobj, feas

    def evaluate_candidates(self, candidates, tol=None,
                            warm="xhat_candidates", eps=None,
                            iters_cap=None, return_mass=False):
        """Evaluate k candidates in ONE stacked kernel launch:
        candidates (k, K) -> (Eobjs (k,), feas (k,)).

        The reduced problem is tiled k-fold along the scenario axis —
        the speculative-parallelism axis of the reference's xhat spokes
        (SURVEY.md §2.10) made literal batching.

        This is a SCREENING pass (no f64 certification on the stacked
        system): pres-based feasibility only.  Certify the winning
        candidate's bound with evaluate_xhat — calculate_incumbent
        (utils/xhat_eval.py) does exactly that.

        eps / iters_cap: per-call solver tolerance and traced
        iteration budget.  Rank-only callers (uc.one_opt_commitment
        sweeps) pass a loose eps and a small cap so one launch costs a
        fraction of a full-accuracy solve; pair with a looser `tol` so
        a capped solve's residuals still count as feasible."""
        cands = np.asarray(candidates)
        k, K = cands.shape
        b = self.batch
        cache = self._xhat_cache(None)
        tkey = ("xhat_stack", k)
        # one live stack only: each holds a k-fold tiling of the full
        # constraint tensor, so letting every distinct k accrete its
        # own copy would grow device memory without bound
        for stale in [key for key in self._np_cache
                      if isinstance(key, tuple) and key
                      and key[0] == "xhat_stack" and key != tkey]:
            del self._np_cache[stale]
        stack = self._np_cache.get(tkey)
        if stack is None:
            S_all = b.num_scens

            def tile(a):
                # shared-A leaves (shape (1, ...)) serve every stacked
                # candidate as-is; per-scenario leaves tile k-fold.
                # A SplitA tiles its per-scenario delta values only
                if isinstance(a, SplitA):
                    return dataclasses.replace(a, vals=tile(a.vals))
                if a.shape[0] == 1 and S_all > 1:
                    return a
                return jnp.tile(a, (k,) + (1,) * (a.ndim - 1))
            prep = cache["prep"]
            nai = jnp.asarray(cache["na"], jnp.int32)
            stack = {
                "A_na": tile(cache["A_na"]),
                "c_na": tile(jnp.take(b.c, nai, axis=1)),
                "q_na": tile(jnp.take(b.qdiag, nai, axis=1)),
                "c_red": tile(cache["c_red"]), "q_red": tile(cache["q_red"]),
                "lb_red": tile(cache["lb_red"]), "ub_red": tile(cache["ub_red"]),
                "row_lo": tile(b.row_lo), "row_hi": tile(b.row_hi),
                "obj_const": tile(b.obj_const),
                "prob": tile(b.prob),
                "prep": dataclasses.replace(
                    prep, A=tile(prep.A), row_lo=tile(prep.row_lo),
                    row_hi=tile(prep.row_hi), d_row=tile(prep.d_row),
                    d_col=tile(prep.d_col), anorm=tile(prep.anorm)),
            }
            ftol = cache["ftol"]

            def impl(vals_ks, x0, y0, eps, iters_cap=None):
                # vals_ks: (k, K) -> (k*S, K)
                vals2 = jnp.repeat(vals_ks, b.num_scens, axis=0).astype(
                    b.c.dtype)
                shift = bmatvec(stack["A_na"], vals2)
                prep2, rlo, rhi = self._shift_and_widen_rows(
                    stack["prep"], stack["row_lo"], stack["row_hi"],
                    shift, ftol)
                oc = (stack["obj_const"]
                      + jnp.sum(stack["c_na"] * vals2, axis=1)
                      + 0.5 * jnp.sum(stack["q_na"] * vals2 * vals2,
                                      axis=1))
                res = self.solver._solve_impl(
                    prep2, stack["c_red"], stack["q_red"],
                    stack["lb_red"], stack["ub_red"], oc, x0, y0, None,
                    eps, iters_cap)
                objs = jnp.sum(
                    (stack["prob"] * res.obj).reshape(k, b.num_scens),
                    axis=1)
                return res, objs

            stack["jit"] = jax.jit(impl)
            self._np_cache[tkey] = stack
        t0 = time.time()
        x0, y0 = self._named_warm.get(warm, (None, None))
        if x0 is None or x0.shape[0] != k * b.num_scens:
            x0 = jnp.zeros_like(stack["c_red"])
            y0 = jnp.zeros_like(stack["row_lo"])
        if eps is None:
            eps = self.solver_eps
        else:
            eps = jnp.asarray(eps, b.c.dtype)
        if iters_cap is not None:
            iters_cap = jnp.asarray(iters_cap, jnp.int32)
        res, objs = stack["jit"](jnp.asarray(cands), x0, y0,
                                 eps, iters_cap)
        jax.block_until_ready(res.x)
        self._solve_wall += time.time() - t0
        self._named_warm[warm] = (res.x, res.y)
        tol = tol or 10 * float(self.solver_eps)
        ok = (np.asarray(res.pres) < tol).reshape(k, b.num_scens)
        live = np.asarray(b.prob) > 0
        feas = np.all(ok | ~live[None, :], axis=1)
        if return_mass:
            # per-candidate feasible probability mass — the diagnostic
            # for "feasible for MOST scenarios but screened out":
            # near-1 mass with feas=False means straggler solves, not
            # an infeasible candidate.  Mass is the fraction of TOTAL
            # probability mass: batch builders normalize prob to 1
            # (stack_scenarios does so explicitly; pads carry 0), so
            # this is a probability — the divisor guard only protects
            # degenerate all-zero test batches, where mass is 0 anyway
            prob = np.asarray(b.prob)
            mass = (ok * prob[None, :]).sum(axis=1) / max(prob.sum(),
                                                          1e-12)
            return np.asarray(objs), feas, mass
        return np.asarray(objs), feas

    # -- nonant fixing (reference spopt.py:592-740 _fix_nonants) ----------
    def fixed_nonant_bounds(self, values, upto_stage=None):
        """Bounds arrays that pin nonant slots to `values`.

        values: (K,) to pin all scenarios alike, or (S, K) per-scenario
        (multistage candidate trees).  upto_stage: only fix slots whose
        stage <= upto_stage (reference xhat_eval.py:326
        fix_nonants_upto_stage).
        Returns (lb, ub).
        """
        b = self.batch
        vals = jnp.asarray(values)
        if vals.ndim == 1:
            vals = jnp.broadcast_to(vals[None, :],
                                    (b.num_scens, b.num_nonants))
        lb = b.lb.at[:, b.nonant_idx].set(vals)
        ub = b.ub.at[:, b.nonant_idx].set(vals)
        if upto_stage is not None:
            stage = jnp.asarray(b.tree.stage_of, jnp.int32)
            keep = stage <= upto_stage
            lb = lb.at[:, b.nonant_idx].set(
                jnp.where(keep[None, :], vals, b.lb[:, b.nonant_idx]))
            ub = ub.at[:, b.nonant_idx].set(
                jnp.where(keep[None, :], vals, b.ub[:, b.nonant_idx]))
        return lb, ub
