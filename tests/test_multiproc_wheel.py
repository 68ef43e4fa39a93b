"""Separate-process cylinder deployment test — the analog of the
reference's multi-rank `mpiexec` cylinder runs (reference
spin_the_wheel.py:219-237 launches hub + spokes as distinct MPI
programs over RMA windows; here they are distinct OS processes over the
C++ mmap seqlock exchange, runtime/exchange.cpp).

Asserts the end-to-end contract: the hub PH process and two spoke
processes (Lagrangian outer bound, xhat-shuffle inner bound) exchange
through the window files, the children exit cleanly on the kill signal,
and the resulting bounds BRACKET the independently computed EF optimum.
"""

import os
import shutil

import numpy as np
import pytest

from efcheck import ef_linprog
from mpisppy_tpu.cylinders.hub import PHHub
from mpisppy_tpu.cylinders.lagrangian_bounder import LagrangianOuterBound
from mpisppy_tpu.cylinders.xhatshufflelooper_bounder import (
    XhatShuffleInnerBound,
)
from mpisppy_tpu.models import farmer
from mpisppy_tpu.opt.ph import PH
from mpisppy_tpu.runtime import native
from mpisppy_tpu.spin_the_wheel import WheelSpinner
from mpisppy_tpu.utils.xhat_eval import Xhat_Eval

S = 6
OPTS = {"defaultPHrho": 1.0, "PHIterLimit": 25, "convthresh": 0.0,
        "pdhg_eps": 1e-7, "pdhg_max_iters": 20000}


@pytest.mark.skipif(not native.available(),
                    reason="native exchange library unavailable")
def test_multiproc_wheel_farmer():
    names = [f"scen{i}" for i in range(S)]
    b = farmer.build_batch(S)
    batch_spec = {"module": "mpisppy_tpu.models.farmer",
                  "builder": "build_batch",
                  "kwargs": {"num_scens": S}}
    hub_dict = {
        "hub_class": PHHub,
        "hub_kwargs": {"options": {"rel_gap": 1e-4}},
        "opt_class": PH,
        "opt_kwargs": {"options": dict(OPTS), "all_scenario_names": names,
                       "batch": b},
    }
    spoke_dicts = [
        {"spoke_class": LagrangianOuterBound, "opt_class": PH,
         "spoke_kwargs": {"options": {}},
         "opt_kwargs": {"options": dict(OPTS),
                        "all_scenario_names": names},
         "proc": {"batch": batch_spec}},
        {"spoke_class": XhatShuffleInnerBound, "opt_class": Xhat_Eval,
         "spoke_kwargs": {"options": {}},
         "opt_kwargs": {"options": dict(OPTS),
                        "all_scenario_names": names},
         "proc": {"batch": batch_spec}},
    ]
    ws = WheelSpinner(hub_dict, spoke_dicts, mode="multiproc",
                      keep_workdir=True).spin()

    # children exited cleanly on the kill signal, and each spoke's log
    # names the backend it was pinned to
    for h in ws.spcomm.spokes:
        assert h.proc is not None and h.proc.returncode == 0
        with open(h.proc.log_path) as f:
            assert "jax backend cpu" in f.read()
    shutil.rmtree(os.path.dirname(ws.spcomm.spokes[0].proc.log_path),
                  ignore_errors=True)

    ib, ob = ws.BestInnerBound, ws.BestOuterBound
    assert np.isfinite(ob), "no outer bound crossed the process boundary"
    ref, _ = ef_linprog(b, n_real=S)
    # bounds must bracket the EF optimum (tolerances: solver eps scale)
    tol = 1e-4 * abs(ref)
    assert ob <= ref + tol
    if np.isfinite(ib):
        assert ib >= ref - tol
        # with both spokes alive the gap should have closed well
        assert (ib - ob) / abs(ref) < 0.05
