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
from ..ops.pdhg import ConsensusSpec
from ..spopt import SPOpt


class ExtensiveForm(SPOpt):
    # consensus solves need one column scaling shared by all scenarios;
    # SPOpt.__init__ reads this so the batch is prepared exactly once
    _shared_cols = True

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        b = self.batch
        self.consensus = ConsensusSpec(
            node_of=b.tree.node_of,
            nonant_idx=b.nonant_idx,
            num_nodes=b.tree.num_nodes)
        self._result = None

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
        b = self.batch
        p = b.prob[:, None]
        res = self.solver.solve(
            self.prep,
            b.c * p,
            b.qdiag * p,
            b.lb, b.ub,
            obj_const=b.obj_const * b.prob,
            x0=x0, y0=y0,
            consensus=self.consensus)
        if certify and not bool(jnp.all(res.converged)):
            res = self._certified_ef_resolve(res)
        self._result = res
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
        from ..ops.pdhg import prepare_batch

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
            put = ((lambda a: jax.device_put(np.asarray(a, np.float64),
                                             cpu))
                   if cpu is not None
                   else (lambda a: jnp.asarray(np.asarray(a, np.float64))))
            prep64 = prepare_batch(put(b.A), put(b.row_lo), put(b.row_hi),
                                   shared_cols=True)
            # hot_dtype pinned OFF: this is the certified f64 authority
            # for the coupled EF solve (AST-guarded in
            # tests/test_precision.py)
            s64 = self.solver.clone(
                max_iters=max(self.solver.max_iters, 100000),
                use_pallas=False, hot_dtype=None)
            r64 = s64.solve(
                prep64,
                put(c),
                put(qdiag),
                put(lb), put(ub),
                obj_const=put(obj_const),
                x0=put(res.x), y0=put(res.y),
                consensus=dataclasses.replace(
                    self.consensus,
                    node_of=jax.device_put(
                        np.asarray(self.consensus.node_of, np.int32),
                        cpu),
                    nonant_idx=jax.device_put(
                        np.asarray(self.consensus.nonant_idx, np.int32),
                        cpu)),
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
    bound at small PH iteration counts.  Shared by bench.py worker_uc
    and examples/uc_scale_demo.py so the bench artifact and the demo
    certify with the same protocol."""
    import time

    t0 = time.time()
    ef = ExtensiveForm({"pdhg_eps": eps, "pdhg_max_iters": max_iters},
                       scenario_names, batch=batch)
    ef.solve_extensive_form()
    return ef.get_dual_bound(), time.time() - t0
