"""UC at scale (VERDICT r2 item 9 / BASELINE stretch axis): 100 wind
scenarios lowered in one batch, commitment recovered near the TRUE MIP
optimum, plus a valid LP-based outer bound.

Ground truth: scipy/HiGHS branch-and-cut on the same EF gives MIP
optimum 24567.04 and LP relaxation 23077.82 — an inherent 6.1%
integrality gap, so no LP-bound-based certificate can reach 1% here
(the reference's UC runs close such gaps by solving MIP subproblems
inside the Lagrangian spokes).  On the 1-core CPU test budget the
threshold-screening pipeline lands within ~3% of the oracle optimum
(measured 25255 = +2.8%); the batched 1-opt flip search
(uc.one_opt_commitment, smoke-tested separately) is the TPU-scale
refinement stage.

Recovery pipeline (all batched): PH consensus -> threshold-commitment
candidates screened in one stacked launch (speculative parallelism,
SURVEY.md §2.10).
"""

import numpy as np

from mpisppy_tpu.models import uc
from mpisppy_tpu.opt.ph import PH

ORACLE_MIP = 24567.04        # HiGHS branch-and-cut, mip_rel_gap 1e-4
ORACLE_LP = 23077.82


def test_uc_100_scenarios_near_optimum():
    S = 100
    b = uc.build_batch(S, H=6)
    ph = PH({"defaultPHrho": 50.0, "PHIterLimit": 10,
             "convthresh": 0.0, "pdhg_eps": 1e-6,
             "superstep_eps": 1e-4, "lagrangian_eps": 1e-5,
             "pdhg_max_iters": 200000},
            [f"s{i}" for i in range(S)], batch=b)
    ph.Iter0()
    outer = ph.trivial_bound
    for _ in range(10):
        ph.ph_iteration()
    outer = max(outer, ph.lagrangian_bound())

    xbar = np.asarray(ph.state.xbar)[0]
    cands = uc.commitment_candidates(b, xbar)   # default 5 thresholds
    objs, feas = ph.evaluate_candidates(cands)
    ok = np.flatnonzero(feas)
    assert ok.size > 0
    best = int(ok[np.argmin(objs[ok])])
    inner, cfeas = ph.evaluate_xhat(cands[best])
    assert cfeas

    # incumbent within 3.5% of the true MIP optimum (measured +2.8%)
    assert inner <= ORACLE_MIP * 1.035, inner
    assert inner >= ORACLE_MIP * (1 - 1e-6)      # oracle is optimal
    # valid outer bound: below the incumbent, consistent with the LP
    assert outer <= inner
    assert outer <= ORACLE_LP * 1.001
    assert outer >= ORACLE_LP * 0.97


def test_uc_1000_reference_scale_fits():
    """The reference's larger_uc stretch instance — 1000 wind
    scenarios, 21-unit fleet, 24 h horizon (paperruns/larger_uc) — must
    LOWER and FIT: with the shared constraint matrix (uc shared_A,
    ir.ScenarioBatch.shared_A) the constraint tensor is (1, M, N)
    instead of (1000, M, N), a ~1000x memory cut that brings the
    instance under a single chip's HBM."""
    b = uc.build_batch(1000, H=24, fleet_multiplier=7)
    G = 21
    assert b.num_scens == 1000
    assert b.shared_A and b.A.shape[0] == 1
    assert b.num_nonants == 2 * G * 24
    dense_bytes = 1000 * b.num_rows * b.num_vars * b.A.dtype.itemsize
    shared_bytes = b.A.nbytes
    assert shared_bytes * 500 < dense_bytes     # the memory story
    # total batch well under 1 GB (fits HBM with room for solver state)
    total = sum(np.asarray(getattr(b, f)).nbytes
                for f in ("A", "c", "qdiag", "row_lo", "row_hi",
                          "lb", "ub"))
    assert total < 1e9, total


def test_uc_1000_scenarios_slow():
    """1000-wind-scenario tier (VERDICT r3 item 6): PH + Lagrangian +
    threshold-commitment xhat on a 6-unit fleet at S=1000, all batched
    through the shared-A matmul path, to a MEASURED gap.  (This tier
    keeps the per-scenario LP small enough for the 1-core CPU test
    budget; the 21-unit/24 h full instance has no CPU tier.)"""
    S = 1000
    b = uc.build_batch(S, H=6)
    assert b.shared_A
    ph = PH({"defaultPHrho": 50.0, "PHIterLimit": 2,
             "convthresh": 0.0, "pdhg_eps": 1e-5,
             "superstep_eps": 1e-3, "lagrangian_eps": 1e-4,
             "pdhg_max_iters": 2000},
            [f"s{i}" for i in range(S)], batch=b)
    ph.Iter0()
    outer = ph.trivial_bound
    assert np.isfinite(outer)
    for _ in range(2):
        ph.ph_iteration()
    outer = max(outer, ph.lagrangian_bound())

    xbar = np.asarray(ph.state.xbar)[0]
    cands = uc.commitment_candidates(b, xbar)
    objs, feas = ph.evaluate_candidates(cands)
    ok = np.flatnonzero(feas)
    assert ok.size > 0
    best = int(ok[np.argmin(objs[ok])])
    inner, cfeas = ph.evaluate_xhat(cands[best])
    assert cfeas
    # a measured, finite gap with a VALID outer bound (UC carries an
    # inherent integrality gap — see the module docstring — so the
    # assertion is validity + sanity, not 1%)
    assert np.isfinite(inner) and outer <= inner
    gap = (inner - outer) / max(abs(inner), 1e-9)
    assert gap < 0.5, gap


def test_uc_shared_vs_dense_parity():
    """The shared-A matmul path must reproduce the dense per-scenario
    path exactly (same model, same solves)."""
    S = 8
    bs = uc.build_batch(S, H=6)
    bd = uc.build_batch(S, H=6, shared_A=False)
    assert bs.shared_A and not bd.shared_A
    opts = {"defaultPHrho": 50.0, "PHIterLimit": 2, "convthresh": 0.0,
            "pdhg_eps": 1e-6, "pdhg_max_iters": 100000}
    phs = PH(opts, [f"s{i}" for i in range(S)], batch=bs)
    phd = PH(opts, [f"s{i}" for i in range(S)], batch=bd)
    ts, td = phs.Iter0(), phd.Iter0()
    assert abs(ts - td) <= 1e-6 * max(abs(td), 1.0), (ts, td)
    phs.ph_iteration()
    phd.ph_iteration()
    assert np.allclose(np.asarray(phs.state.xbar),
                       np.asarray(phd.state.xbar), atol=1e-5)
    ls, ld = phs.lagrangian_bound(), phd.lagrangian_bound()
    assert abs(ls - ld) <= 1e-5 * max(abs(ld), 1.0), (ls, ld)


def test_uc_one_opt_smoke():
    """Batched 1-opt flip search improves (or retains) a deliberately
    over-committed candidate on a small instance."""
    S = 10
    b = uc.build_batch(S, H=6)
    ph = PH({"defaultPHrho": 50.0, "PHIterLimit": 3,
             "convthresh": 0.0, "pdhg_eps": 1e-6,
             "pdhg_max_iters": 100000},
            [f"s{i}" for i in range(S)], batch=b)
    ph.Iter0()
    ph.ph_iteration()
    all_on = uc.commitment_candidate(
        b, np.ones(b.num_nonants), threshold=0.5)
    v0, f0 = ph.evaluate_xhat(all_on)
    assert f0
    cand, v1 = uc.one_opt_commitment(ph, b, all_on, max_sweeps=2,
                                     flip_slots=np.arange(6))
    assert v1 <= v0 + 1e-6
    # chunked sweeps (reference-scale fleets launch bounded stacks)
    # must satisfy the same contract as one whole-sweep launch: a
    # feasible incumbent no worse than the start, and in the same
    # neighborhood.  NOT bitwise/solver-tolerance equality — chunk
    # layout changes warm-start chains, so a near-tied argmin may
    # legitimately pick a different flip and descend to a different
    # (comparable) local optimum.
    cand2, v2 = uc.one_opt_commitment(ph, b, all_on, max_sweeps=2,
                                      flip_slots=np.arange(6), chunk=2)
    assert v2 <= v0 + 1e-6
    assert abs(v1 - v2) <= 2e-2 * (1 + abs(v1))
    # screen/verify mode (loose-eps capped ranking launches, accurate
    # certify in rank order) obeys the same contract: every acceptance
    # is gated by evaluate_xhat, so a bad screen can cost improvement
    # but never a worse-than-start or unverified incumbent
    cand3, v3 = uc.one_opt_commitment(ph, b, all_on, max_sweeps=2,
                                      flip_slots=np.arange(6),
                                      screen_eps=3e-3, screen_cap=500)
    assert v3 <= v0 + 1e-6
    assert abs(v1 - v3) <= 2e-2 * (1 + abs(v1))


def test_uc_min_up_down_rows():
    """min_up_down=True adds the egret-style uptime/downtime window
    rows: a commitment that starts a big unit for a single hour
    violates its min-up window; honoring the window satisfies it."""
    b = uc.build_batch(4, H=6, min_up_down=True)
    b0 = uc.build_batch(4, H=6)
    assert b.num_rows > b0.num_rows
    A = np.asarray(b.A)[0]
    hi = np.asarray(b.row_hi)[0]
    G, H = 3, 6
    GH = G * H

    def commit(u):
        x = np.zeros(b.num_vars)
        x[:GH] = u.reshape(-1)
        return x

    # big unit (g=0, UT=3) on for exactly one hour (h=2): min-up rows
    # u_3 - u_2 - u_tau <= 0 must be violated for tau in {4, 5}... in
    # 0-based: start at h=2 (u[2]=1, u[1]=0) with u[3]=u[4]=0
    u_bad = np.zeros((G, H))
    u_bad[0, 2] = 1.0
    viol = A @ commit(u_bad) - np.where(np.isfinite(hi), hi, np.inf)
    assert np.max(viol) > 0.5            # some min-up row violated
    # honoring the 3-hour window satisfies every extra row
    u_ok = np.zeros((G, H))
    u_ok[0, 2:5] = 1.0
    viol2 = A @ commit(u_ok) - np.where(np.isfinite(hi), hi, np.inf)
    assert np.max(viol2[b0.num_rows:]) <= 1e-9
    # min-down: shutting the big unit for one hour then restarting
    u_cyc = np.ones((G, H))
    u_cyc[0, 3] = 0.0
    viol3 = A @ commit(u_cyc) - np.where(np.isfinite(hi), hi, np.inf)
    assert np.max(viol3[b0.num_rows:]) > 0.5


def test_uc_commitment_repair_windows():
    """Threshold candidates on a min_up_down batch are repaired to
    window feasibility (runs extended), so the recovery pipeline keeps
    producing feasible incumbents with the windows on."""
    S = 6
    b = uc.build_batch(S, H=6, min_up_down=True)
    # a single-hour spike for the big unit (UT=3) must stretch to 3h;
    # the tables come from the batch's own metadata
    ut = np.asarray(b.model_meta["uc_ut"])
    dt_ = np.asarray(b.model_meta["uc_dt"])
    u = np.zeros(18)
    u[2] = 1.0                       # unit 0, hour 2
    rep = uc.repair_min_up_down(u, ut, dt_, 6)
    assert rep[2:5].sum() == 3.0     # extended to the 3-hour window
    # a 1-hour off-gap inside an on-run gets merged (DT=3)
    u2 = np.ones(18)
    u2[3] = 0.0
    rep2 = uc.repair_min_up_down(u2, ut, dt_, 6)
    assert rep2[:6].sum() == 6.0
    # end-to-end: PH consensus -> candidates stay feasible
    ph = PH({"defaultPHrho": 50.0, "PHIterLimit": 3, "convthresh": 0.0,
             "pdhg_eps": 1e-6, "pdhg_max_iters": 100000},
            [f"s{i}" for i in range(S)], batch=b)
    ph.Iter0()
    for _ in range(3):
        ph.ph_iteration()
    cands = uc.commitment_candidates(b, np.asarray(ph.state.xbar)[0])
    objs, feas = ph.evaluate_candidates(cands)
    assert np.any(feas)
    best = int(np.flatnonzero(feas)[np.argmin(objs[np.asarray(feas)])])
    inner, cfeas = ph.evaluate_xhat(cands[best])
    assert cfeas and np.isfinite(inner)


def test_ef_dual_bound_validity():
    """The shared EF-dual outer bound helper (opt/ef.ef_dual_bound,
    used by uc_scale_demo) must lower-bound any
    feasible integer commitment's objective, and must beat the iter-0
    trivial bound's slack at small iteration counts (the calibration
    that cut the r4 UC artifact's reported gap from 17.7% to 4.1%)."""
    from mpisppy_tpu.opt.ef import ef_dual_bound

    S = 20
    b = uc.build_batch(S, H=6, fleet_multiplier=2)
    names = [f"s{i}" for i in range(S)]
    bound, secs = ef_dual_bound(b, names)
    assert np.isfinite(bound) and secs >= 0.0
    ph = PH({"defaultPHrho": 50.0, "PHIterLimit": 2, "convthresh": 0.0,
             "pdhg_eps": 1e-5, "pdhg_max_iters": 60000,
             "iter0_infeasibility_ok": True},
            names, batch=b)
    ph.Iter0()
    ph.ph_iteration()
    # valid: below every feasible integer commitment
    cands = uc.commitment_candidates(b, np.asarray(ph.state.xbar)[0])
    objs, feas = ph.evaluate_candidates(cands)
    ok = np.flatnonzero(feas)
    assert ok.size and bound <= float(np.min(objs[ok])) + 1e-6
    # and tighter than (or equal to) the trivial bound
    assert bound >= ph.trivial_bound - 1e-6 * (1 + abs(bound))


def test_uc_spinning_reserve_rows():
    """reserve_factor adds per-hour capacity-adequacy reserve rows
    (egret-style: committed capacity >= net load + r * demand).
    Neither dispatch nor shed appears in the row — a PARTIALLY
    committed fleet whose energy balance is shed-rescuable must still
    be reserve-infeasible (the leak a headroom-form constraint would
    have: shedding frees dispatch headroom one-for-one)."""
    S = 6
    br = uc.build_batch(S, H=6, reserve_factor=0.25)
    b0 = uc.build_batch(S, H=6)
    assert br.shared_A                     # reserve keeps the matmul path
    assert br.num_rows == b0.num_rows + 6  # one row per hour
    opts = {"defaultPHrho": 50.0, "PHIterLimit": 2, "convthresh": 0.0,
            "pdhg_eps": 1e-6, "pdhg_max_iters": 100000}
    phr = PH(opts, [f"s{i}" for i in range(S)], batch=br)
    ph0 = PH(opts, [f"s{i}" for i in range(S)], batch=b0)
    phr.Iter0()
    ph0.Iter0()
    all_on = uc.commitment_candidate(br, np.ones(br.num_nonants),
                                     threshold=0.5)
    vr, fr = phr.evaluate_xhat(all_on)
    assert fr and np.isfinite(vr)
    # peaker-only (Pmax 100 << net load + reserve): energy is
    # shed-rescuable, capacity is not — the partial-commitment case
    # that distinguishes the capacity form from the leaky headroom form
    GH = br.num_nonants // 2
    u = np.zeros(GH)
    u[2 * 6: 3 * 6] = 1.0           # unit 2 = the peaker, all hours
    peaker = uc.commitment_candidate(
        br, np.concatenate([u, np.zeros(GH)]), threshold=0.5)
    v0_p, f0_p = ph0.evaluate_xhat(peaker)
    vr_p, fr_p = phr.evaluate_xhat(peaker)
    assert f0_p                      # shed (penalty 1000/MWh) rescues
    assert not fr_p                  # reserve cannot be shed
    # reserve binds the commitment: all-on objective >= no-reserve one
    v0, _ = ph0.evaluate_xhat(all_on)
    assert vr >= v0 - 1e-6 * (1 + abs(v0))


def test_infeasible_uc_detected_without_iter0_certify():
    """The bench's UC path disables the iter0 certified hard-stop
    (iter0_certify=False + iter0_infeasibility_ok=True) on the
    argument that UC is structurally feasible (load shed) and the
    published bounds are validated independently.  This test closes
    the loophole: a GENUINELY infeasible variant (shed capped to zero,
    demand above fleet capacity) must still be caught by that
    independent validation — iter0 feasible mass collapses and every
    recovered-commitment candidate fails the feasibility screen, so
    the bench reports 'no feasible commitment candidate' instead of a
    gap."""
    import dataclasses

    S, H = 8, 4
    b = uc.build_batch(S, H=H)
    G = 3
    ub = np.asarray(b.ub).copy()
    ub[:, 3 * G * H:] = 0.0                  # no load shed allowed
    row_lo = np.asarray(b.row_lo).copy()
    cap = 700.0                              # fleet Pmax sum
    bal = 2 * G * H + np.arange(H)           # balance row indices
    row_lo[:, bal] = 10.0 * cap              # unserviceable demand
    b = dataclasses.replace(b, ub=ub, row_lo=row_lo)

    ph = PH({"defaultPHrho": 50.0, "PHIterLimit": 2, "convthresh": 0.0,
             "pdhg_eps": 1e-6, "pdhg_max_iters": 20000,
             "iter0_certify": False, "iter0_infeasibility_ok": True},
            [f"s{i}" for i in range(S)], batch=b)
    ph.Iter0()
    # the uncertified iter0 path still SEES the infeasibility
    assert ph.iter0_feas_mass < 0.5
    ph.ph_iteration()
    xbar = np.asarray(ph.state.xbar)[0]
    cands = uc.commitment_candidates(b, xbar)
    objs, feas, mass = ph.evaluate_candidates(cands, return_mass=True)
    # every candidate fails the independent feasibility screen — the
    # bench path publishes value -1, never a gap/incumbent
    assert not np.any(feas)
    assert float(np.max(mass)) < 0.5


def test_infeasible_uc_raises_with_message_when_not_ok():
    """Without iter0_infeasibility_ok the uncertified iter0 hard-stops,
    and the message says certification was SKIPPED (ADVICE r4: the old
    message claimed 'after certified re-solve' even when
    iter0_certify=False)."""
    import dataclasses

    import pytest as _pytest

    S, H = 4, 3
    b = uc.build_batch(S, H=H)
    G = 3
    ub = np.asarray(b.ub).copy()
    ub[:, 3 * G * H:] = 0.0
    row_lo = np.asarray(b.row_lo).copy()
    row_lo[:, 2 * G * H + np.arange(H)] = 7000.0
    b = dataclasses.replace(b, ub=ub, row_lo=row_lo)
    ph = PH({"defaultPHrho": 50.0, "PHIterLimit": 1, "convthresh": 0.0,
             "pdhg_eps": 1e-6, "iter0_certify": False},
            [f"s{i}" for i in range(S)], batch=b)
    with _pytest.raises(RuntimeError, match="UNCERTIFIED"):
        ph.Iter0()
