"""ExtensiveForm — monolithic EF solve (reference: mpisppy/opt/ef.py).

The reference builds one big Pyomo model: scenario sub-blocks, a
probability-weighted summed objective, and explicit nonanticipativity
equality constraints against first-seen reference variables
(reference sputils.py:209-341 _create_EF_from_scen_dict), then makes a
single monolithic solver call (opt/ef.py:66 solve_extensive_form) —
2939 s of Gurobi barrier at farmer-1000 scale (BASELINE.md).

Here the EF is never materialized: the batched PDHG kernel runs in
consensus mode (ops/pdhg.ConsensusSpec) where each (node, nonant-slot)
is one shared variable — the per-scenario matvecs stay batched on the
MXU and the consensus coupling is a segment-sum per iteration.  The
probability weighting moves into the per-scenario objective arrays.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from .. import global_toc
from ..ops.pdhg import ConsensusSpec, balance_consensus
from ..spopt import SPOpt


def consensus_of(batch):
    """The batch's nonanticipativity structure as one shared variable per
    (tree node, nonant slot)."""
    return ConsensusSpec(node_of=batch.tree.node_of,
                         nonant_idx=batch.nonant_idx,
                         num_nodes=batch.tree.num_nodes)


class ExtensiveForm(SPOpt):
    # consensus solves need one column scaling shared by all scenarios;
    # SPOpt.__init__ reads this so the batch is prepared exactly once
    _shared_cols = True

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.consensus = consensus_of(self.batch)
        self._result = None
        self._solves = 0     # the solve id carried by this EF's spans

    def _build_prep(self, hot=None, batch=None):
        """SPOpt's prep, balanced where the EF operator's nonant block
        outweighs the rest (ops/pdhg.balance_consensus)."""
        prep = super()._build_prep(hot=hot, batch=batch)
        return balance_consensus(
            prep, consensus_of(self.batch if batch is None else batch))

    def solve_extensive_form(self, solver_options=None, tee=False,
                             certify=True, x0=None, y0=None):
        """One batched consensus solve == the reference's single
        monolithic solver call (opt/ef.py:66).

        certify: if the fast solve leaves the (single, coupled) EF
        unconverged, re-solve the FULL batch in float64 warm-started —
        the consensus system cannot be subset the way the per-scenario
        fallback (spopt._certified_resolve) does.

        x0/y0: optional warm starts (user space) — sequential-
        relaxation callers (models/acopf3.soc_refine's cut loop) hand
        each round the previous round's iterates, the persistent-
        solver analog."""
        b, tel = self.batch, self._tel
        self._solves += 1
        k = self._solves
        with tel.span("ef.solve", solve=k):
            with tel.span("ef.weights", solve=k):
                p = b.prob[:, None]
                c, qdiag = b.c * p, b.qdiag * p
                obj_const = b.obj_const * b.prob
            res = self.solver.solve(
                self.prep, c, qdiag, b.lb, b.ub, obj_const=obj_const,
                x0=x0, y0=y0, consensus=self.consensus)
            with tel.span("ef.certify", solve=k):
                if certify and not bool(jnp.all(res.converged)):
                    res = self._certified_ef_resolve(res)
            self._result = res
            with tel.span("ef.log", solve=k):
                global_toc(
                    f"EF solve: obj={self.get_objective_value():.6g} "
                    f"pres={float(jnp.max(res.pres)):.2e} "
                    f"gap={float(jnp.max(res.gap)):.2e} "
                    f"iters={int(res.iters)}", tee)
        return res

    def _certified_ef_resolve(self, res, c=None, qdiag=None, lb=None,
                              ub=None, obj_const=None):
        """Full-batch float64 consensus re-solve, warm-started from the
        fast result (on the CPU backend when the accelerator lacks
        f64).  The f32 kernel's primal-residual floor (~1e-4 relative)
        applies to the EF exactly as to per-scenario solves.

        c/qdiag/lb/ub/obj_const override the batch's own
        (probability-weighted) arrays — callers solving a MODIFIED EF
        (opt/mip.py dives fix integer boxes) MUST pass their arrays or
        the fallback would silently re-solve the unmodified EF and
        report its solution as the modified one."""
        import dataclasses

        import jax

        from .. import global_toc
        from ..ir import SplitA
        from ..ops.pdhg import prepare_batch, prepare_split_native

        b = self.batch
        p = np.asarray(b.prob, np.float64)[:, None]
        if c is None:
            c = np.asarray(b.c, np.float64) * p
        if qdiag is None:
            qdiag = np.asarray(b.qdiag, np.float64) * p
        if obj_const is None:
            obj_const = np.asarray(b.obj_const, np.float64) * p[:, 0]
        lb = b.lb if lb is None else lb
        ub = b.ub if ub is None else ub
        try:
            cpu = jax.devices("cpu")[0]
        except RuntimeError:
            cpu = None
        with jax.enable_x64(True):
            def put(a, dt=np.float64):
                a = np.asarray(a, dt)
                return (jax.device_put(a, cpu) if cpu is not None
                        else jnp.asarray(a))

            cons64 = dataclasses.replace(
                self.consensus,
                node_of=put(self.consensus.node_of, np.int32),
                nonant_idx=put(self.consensus.nonant_idx, np.int32))
            if isinstance(b.A, SplitA):
                # a split-native batch has no dense A: the f64 prep stays
                # split, as the fast one is
                prep64 = prepare_split_native(
                    SplitA(shared=put(b.A.shared),
                           rows=put(b.A.rows, np.int32),
                           cols=put(b.A.cols, np.int32),
                           vals=put(b.A.vals)),
                    put(b.row_lo), put(b.row_hi))
            else:
                prep64 = prepare_batch(put(b.A), put(b.row_lo),
                                       put(b.row_hi), shared_cols=True)
            prep64 = balance_consensus(prep64, cons64)
            # hot_dtype pinned OFF: this is the certified f64 authority
            # for the coupled EF solve (AST-guarded in
            # tests/test_precision.py)
            s64 = self.solver.clone(
                max_iters=max(self.solver.max_iters, 100000),
                hot_dtype=None)
            r64 = s64.solve(
                prep64,
                put(c),
                put(qdiag),
                put(lb), put(ub),
                obj_const=put(obj_const),
                x0=put(res.x), y0=put(res.y), consensus=cons64,
                eps=float(self.solver.eps))
            jax.block_until_ready(r64.x)
        if not bool(jnp.all(r64.converged)):
            global_toc("WARNING: EF f64 fallback did not fully converge")
        dt = res.x.dtype
        cast = lambda a: jnp.asarray(np.asarray(a), dt)  # noqa: E731
        return dataclasses.replace(
            res, x=cast(r64.x), y=cast(r64.y), obj=cast(r64.obj),
            dual_obj=cast(r64.dual_obj), pres=cast(r64.pres),
            dres=cast(r64.dres), gap=cast(r64.gap),
            converged=jnp.asarray(np.asarray(r64.converged), bool))

    @property
    def solved(self):
        return self._result is not None

    def get_objective_value(self):
        """EF objective = sum of probability-weighted scenario pieces
        (reference opt/ef.py:97)."""
        if self._result is None:
            raise RuntimeError("call solve_extensive_form first")
        return float(jnp.sum(self._result.obj))

    def get_dual_bound(self):
        """Valid lower bound from the EF dual estimate."""
        if self._result is None:
            raise RuntimeError("call solve_extensive_form first")
        return float(jnp.sum(self._result.dual_obj))

    def get_root_solution(self):
        """Root-node nonant values (K,) (reference opt/ef.py:114)."""
        if self._result is None:
            raise RuntimeError("call solve_extensive_form first")
        x_na = self.batch.nonants(self._result.x)
        # all scenarios agree by construction; read scenario 0
        return np.asarray(x_na[0])

    def nonants(self):
        """Per-scenario nonant values for the REAL scenarios, padding
        excluded (reference sputils.ef_nonants)."""
        if self._result is None:
            raise RuntimeError("call solve_extensive_form first")
        return np.asarray(
            self.batch.nonants(self._result.x))[: self.n_real_scens]


def ef_dual_bound(batch, scenario_names, eps=1e-5, max_iters=100000):
    """(bound, seconds): one consensus-EF LP solve's dual objective —
    a valid outer bound at ANY iterate when the batch is an LP with
    all-finite boxes (spopt valid-Ebound rule #1), and measured (UC
    S=50 vs a HiGHS oracle) much tighter than a W-path Lagrangian
    bound at small PH iteration counts (examples/uc_scale_demo.py)."""
    import time

    t0 = time.time()
    ef = ExtensiveForm({"pdhg_eps": eps, "pdhg_max_iters": max_iters},
                       scenario_names, batch=batch)
    ef.solve_extensive_form()
    return ef.get_dual_bound(), time.time() - t0
