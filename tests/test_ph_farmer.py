"""PH end-to-end golden tests on farmer — the analog of the reference's
workhorse test_ef_ph.py (golden values at low precision,
tests/utils.py:30 round_pos_sig).

Golden numbers: classic 3-scenario farmer optimum is -108390
(Birge & Louveaux), trivial bound -115405.55 (wait-and-see).
"""

import numpy as np
import pytest

from mpisppy_tpu.models import farmer
from mpisppy_tpu.opt.ph import PH
from mpisppy_tpu.utils.xhat_eval import Xhat_Eval


def round_pos_sig(x, sig=1):
    """Reference: mpisppy/tests/utils.py:30."""
    import math
    return round(x, -int(math.floor(math.log10(abs(x)))) + (sig - 1))


@pytest.fixture(scope="module")
def ph3():
    b = farmer.build_batch(3)
    opts = {"defaultPHrho": 1.0, "PHIterLimit": 200,
            "convthresh": 1e-5, "pdhg_eps": 1e-7}
    ph = PH(opts, [f"scen{i}" for i in range(3)], batch=b)
    ph.ph_main()
    return ph


def test_trivial_bound(ph3):
    # wait-and-see bound for classic farmer: -115405.55
    assert round_pos_sig(ph3.trivial_bound, 5) == -115410.0 or \
        abs(ph3.trivial_bound - -115405.55) < 5.0


def test_ph_converges_to_ef_objective(ph3):
    eobj = float(ph3.Eobjective(ph3.state.obj))
    assert abs(eobj - -108390.0) < 20.0


def test_xbar_solution(ph3):
    xbar = np.asarray(ph3.root_xbar())
    assert np.allclose(xbar, [170.0, 80.0, 250.0], atol=0.5)


def test_lagrangian_bound_valid(ph3):
    lb = ph3.lagrangian_bound()
    # must be a valid lower bound on -108390, and tighter than trivial
    assert lb <= -108389.0
    assert lb >= ph3.trivial_bound - 1.0


def test_xhat_eval_inner_bound(ph3):
    ev = Xhat_Eval(dict(ph3.options), ph3.all_scenario_names,
                   batch=farmer.build_batch(3))
    eobj, feas = ev.evaluate(np.asarray(ph3.root_xbar()))
    assert feas
    # fixing to the optimal xbar recovers the EF objective
    assert abs(eobj - -108390.0) < 20.0
    # a deliberately bad candidate is worse
    bad, feas2 = ev.evaluate(np.array([0.0, 0.0, 0.0]))
    assert feas2
    assert bad > eobj + 1000


def test_scenario_denouement_contract():
    """Denouements receive (rank, name, scenario) with THAT scenario's
    data — a ScenarioView slice, not the global state (reference
    spbase.py:505-522 contract; VERDICT r3 item 7)."""
    b = farmer.build_batch(3)
    seen = {}

    def denouement(rank, name, scen):
        assert rank == 0
        assert scen.name == name
        # per-scenario arrays, not the (S, N) global state
        assert scen.x.ndim == 1 and scen.x.shape[0] == b.num_vars
        assert scen.nonants.shape == (b.num_nonants,)
        seen[name] = (scen.obj, scen.prob, scen.nonants.copy())

    opts = {"defaultPHrho": 1.0, "PHIterLimit": 50,
            "convthresh": 1e-4, "pdhg_eps": 1e-6}
    ph = PH(opts, [f"scen{i}" for i in range(3)], batch=b,
            scenario_denouement=denouement)
    ph.ph_main()
    assert set(seen) == {"scen0", "scen1", "scen2"}
    probs = [p for (_, p, _) in seen.values()]
    assert abs(sum(probs) - 1.0) < 1e-9
    # per-scenario objectives differ (different yields) and their
    # probability-weighted sum is the expected objective
    objs = [seen[f"scen{i}"][0] for i in range(3)]
    eobj = float(ph.Eobjective(ph.state.obj))
    assert abs(sum(p * o for p, o in zip(probs, objs)) - eobj) < 1e-6
    # converged PH: every scenario's nonants agree with xbar
    xbar = np.asarray(ph.root_xbar())
    for _, _, na in seen.values():
        assert np.allclose(na, xbar, atol=2.0)


def test_ph_sharded_multi_device():
    """8 virtual CPU devices (conftest): same answer, sharded batch.
    Analog of the reference's mpiexec smoke tier (straight_tests.py)."""
    import jax
    assert len(jax.devices()) == 8
    b = farmer.build_batch(16)  # 2 scenarios per device
    opts = {"defaultPHrho": 2.0, "PHIterLimit": 40,
            "convthresh": 1e-4, "pdhg_eps": 1e-6}
    ph = PH(opts, [f"scen{i}" for i in range(16)], batch=b)
    conv, eobj, triv = ph.ph_main()
    assert conv < 2.0  # started ~30; must be well into consensus
    assert eobj >= triv - 1.0  # trivial bound stays a lower bound
    # serial re-run on 1 device mesh gives the same trajectory
    from mpisppy_tpu.parallel.mesh import ScenarioMesh
    mesh1 = ScenarioMesh(devices=jax.devices()[:1])
    ph1 = PH(opts, [f"scen{i}" for i in range(16)],
             batch=farmer.build_batch(16), mesh=mesh1)
    conv1, eobj1, triv1 = ph1.ph_main()
    assert abs(triv - triv1) < 1e-3 * abs(triv)
    assert abs(eobj - eobj1) < 1e-3 * abs(eobj)


def test_iter0_certify_off_and_certify_budget(monkeypatch):
    """options['iter0_certify']=False must keep Iter0 off the f64
    straggler-rescue path entirely (the UC-on-TPU wall-clock guard),
    and options['certify_max_iters'] must bound the f64 fallback
    solver's budget."""
    b = farmer.build_batch(3)
    ph = PH({"defaultPHrho": 1.0, "PHIterLimit": 2, "convthresh": 0.0,
             "pdhg_eps": 1e-7, "iter0_certify": False,
             "certify_max_iters": 1234},
            [f"s{i}" for i in range(3)], batch=b)
    calls = []
    orig = ph._certified_resolve

    def spy(res, *a, **kw):
        calls.append((a, kw))
        return orig(res, *a, **kw)
    monkeypatch.setattr(ph, "_certified_resolve", spy)
    ph.Iter0()
    assert calls == []          # no rescue attempted at Iter0
    assert np.isfinite(ph.trivial_bound)
    # force the refine path explicitly (a tiny LP can converge to
    # machine-zero residuals, so an "unreachable eps" is not reliably
    # a straggler); the lazily-built f64 solver must carry the budget
    res = ph.solve_loop()
    ph._certified_resolve(
        res, None, None, None, None,
        select=np.ones(ph.batch.num_scens, bool))
    assert ph._solver64 is not None
    assert ph._solver64.max_iters == 1234


def test_farmer_4096_scenarios_sharded_gap():
    """farmer-10k tier (BASELINE.md target row 'farmer, 10,000 scen')
    at test scale: S=4096 sharded over the 8-virtual-device mesh, PH
    to a VERIFIED <=1% outer/inner gap."""
    S = 4096
    b = farmer.build_batch(S)
    ph = PH({"defaultPHrho": 1.0, "PHIterLimit": 60, "convthresh": 0.0,
             "pdhg_eps": 1e-6, "superstep_eps": 1e-4,
             "lagrangian_eps": 1e-4},
            [f"scen{i}" for i in range(S)], batch=b)
    assert ph.batch.num_scens == S          # 4096 = 8 * 512, no pad
    ph.Iter0()
    outer = ph.trivial_bound
    gap = np.inf
    for k in range(60):
        ph.ph_iteration()
        if (k + 1) % 4 == 0:
            inner, feas = ph.evaluate_xhat(ph.root_xbar())
            outer = max(outer, ph.lagrangian_bound())
            if feas:
                gap = abs(inner - outer) / max(abs(inner), 1e-9)
            if gap <= 0.01:
                break
    assert gap <= 0.01
