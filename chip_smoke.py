"""Chip smoke: Progressive Hedging on the TPU through the public API.

    python chip_smoke.py             # one chip: golden drive + full size
    python chip_smoke.py --chips 4   # four chips: mesh parity + MPMD wheel

One process drives the chip.  The default run uses exactly one device:
  * correctness — the golden farmer drive (3 scenarios, f32), checked
    against the recorded goldens (E[obj] -108390, xbar (170, 80, 250),
    Lagrangian bound below E[obj]);
  * full size — the reference's own instance, farmer S=1000 x
    crops_multiplier=1000 (BASELINE.md's 2939.1 s Gurobi EF), built
    split-native in f32: Iter0 and 2 PH iterations under an iteration
    cap per PDHG solve.
`--chips 4` runs only the paths that cross chips: farmer PH on a
4-chip ScenarioMesh against the same instance on one chip (bounds and
conv to rtol 1e-4), then a few supersteps of the MPMD wheel on the
collective exchange fabric, whose reads must pass `read_checked`.

Every check is an assert.  The last stdout line is the contract line
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}},
printed only after every phase passed on a TPU; any failure, "no TPU"
included, exits non-zero without it.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

T0 = time.perf_counter()

FULL_SCENS = 1000          # BASELINE.md: the 2939.1 s Gurobi instance
FULL_MULT = 1000
FULL_ITERS_CAP = 1000      # PDHG iterations per solve at full size
GOLDEN_EOBJ = -108390.0
GOLDEN_XBAR = (170.0, 80.0, 250.0)
PARITY_RTOL = 1e-4


def log(*parts):
    print(f"[{time.perf_counter() - T0:8.2f}s]", *parts, flush=True)


class CompileClock:
    """Seconds jax spent compiling (backend compiles, persistent-cache
    reads included) and persistent-cache hits, from jax.monitoring."""

    def __init__(self):
        import jax.monitoring as mon
        self.seconds = 0.0
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def contract_line(devices):
    """The final line; refuses any device that is not a TPU."""
    d = devices[0]
    if d.platform != "tpu":
        raise RuntimeError(
            f"chip_smoke: JAX found no TPU (platform {d.platform!r})")
    return json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}})


def _mesh(devices):
    from mpisppy_tpu.parallel.mesh import ScenarioMesh
    return ScenarioMesh(devices=list(devices))


def phase_golden(devices, dtype="float32", clock=None):
    """The verify skill's golden drive on `devices`; returns its
    numbers after asserting them against the recorded goldens."""
    import numpy as np

    from mpisppy_tpu.models import farmer
    from mpisppy_tpu.opt.ph import PH
    from mpisppy_tpu.utils.xhat_eval import Xhat_Eval

    t = time.perf_counter()
    c0 = clock.seconds if clock else 0.0
    opts = {"defaultPHrho": 1.0, "PHIterLimit": 150, "convthresh": 1e-5,
            "pdhg_eps": 1e-7}
    names = [f"scen{i}" for i in range(3)]
    ph = PH(opts, names, batch=farmer.build_batch(3, dtype=dtype),
            mesh=_mesh(devices))
    conv, eobj, triv = ph.ph_main()
    xbar = np.asarray(ph.root_xbar(), np.float64)
    lag = ph.lagrangian_bound()
    ev = Xhat_Eval(dict(opts), names,
                   batch=farmer.build_batch(3, dtype=dtype),
                   mesh=_mesh(devices))
    inner, feas = ev.evaluate(xbar)
    wall = time.perf_counter() - t
    log(f"golden: eobj={eobj!r} trivial={triv!r} lagrangian={lag!r} "
        f"xbar={xbar.tolist()} conv={conv!r} xhat_eval={inner!r} "
        f"feasible={feas} wall={wall:.2f}s"
        + (f" compile={clock.seconds - c0:.2f}s" if clock else ""))
    assert abs(eobj - GOLDEN_EOBJ) <= 1e-3 * abs(GOLDEN_EOBJ), eobj
    assert np.all(np.abs(xbar - np.asarray(GOLDEN_XBAR)) <= 1.0), xbar
    tol = 1e-3 * abs(GOLDEN_EOBJ)
    assert triv <= eobj + tol and lag <= eobj + tol, (triv, lag, eobj)
    assert feas and abs(inner - GOLDEN_EOBJ) <= 1e-3 * abs(GOLDEN_EOBJ)
    return {"eobj": eobj, "trivial": triv, "lagrangian": lag,
            "xbar": xbar, "conv": conv}


def _device_bytes(tree):
    import jax
    return sum(int(a.nbytes) for a in jax.tree.leaves(tree)
               if hasattr(a, "nbytes"))


def phase_full_size(devices, S=FULL_SCENS, mult=FULL_MULT,
                    iters_cap=FULL_ITERS_CAP, ph_iters=2, clock=None):
    """Farmer S x crops_multiplier split-native in f32: Iter0 and
    `ph_iters` PH iterations, each PDHG solve capped at `iters_cap`
    iterations.  Prints shape, device bytes, compile and phase walls,
    bounds, conv and the host f64 certify seconds."""
    import math

    import jax
    import numpy as np

    from mpisppy_tpu.ir import SplitA
    from mpisppy_tpu.models import farmer
    from mpisppy_tpu.opt.ph import PH

    def timed(fn):
        c0 = clock.seconds if clock else 0.0
        t = time.perf_counter()
        out = fn()
        jax.block_until_ready(ph.state)
        wall = time.perf_counter() - t
        comp = (clock.seconds - c0) if clock else float("nan")
        return out, wall, comp

    t = time.perf_counter()
    b = farmer.build_batch(S, crops_multiplier=mult, dtype=np.float32,
                           split=True)
    assert isinstance(b.A, SplitA)
    Sx, M, N = b.A.shape
    log(f"full-size instance: farmer S={Sx} crops_multiplier={mult}: "
        f"{M} rows x {N} cols per scenario, {b.A.vals.shape[1]} "
        f"per-scenario matrix entries (ir.SplitA), f32; host build "
        f"{time.perf_counter() - t:.2f}s")
    # capped solves may leave stragglers the (equally capped) host f64
    # re-solve cannot finish: report the feasible mass instead of
    # aborting — farmer's all-finite boxes keep the bound valid anyway
    opts = {"defaultPHrho": 1.0, "pdhg_max_iters": iters_cap,
            "pdhg_eps": 1e-5, "superstep_eps": 1e-4,
            "certify_max_iters": iters_cap,
            "iter0_infeasibility_ok": True}
    log(f"full-size iteration cap: {iters_cap} PDHG iterations per "
        f"solve (pdhg_eps 1e-5, superstep_eps 1e-4)")
    c0 = clock.seconds if clock else 0.0
    t = time.perf_counter()
    ph = PH(opts, [f"scen{i}" for i in range(S)], batch=b,
            mesh=_mesh(devices))
    jax.block_until_ready((ph.batch, ph.prep))
    log(f"full-size PH built (placement + Ruiz prep) in "
        f"{time.perf_counter() - t:.2f}s, compile "
        f"{(clock.seconds - c0) if clock else float('nan'):.2f}s; "
        f"device bytes: batch {_device_bytes(ph.batch)}, "
        f"prep {_device_bytes(ph.prep)}")
    _, wall, comp = timed(ph.Iter0)
    triv = ph.trivial_bound
    log(f"full-size Iter0: wall {wall:.2f}s (compile {comp:.2f}s), "
        f"trivial bound {triv!r}, conv {ph.conv!r}, "
        f"feasible mass {ph.iter0_feas_mass!r}, "
        f"kernel iters {int(ph.state.solve_iters)}")
    convs = [ph.conv]
    for k in range(1, ph_iters + 1):
        _, wall, comp = timed(ph.ph_iteration)
        convs.append(ph.conv)
        log(f"full-size PH iteration {k}: wall {wall:.2f}s (compile "
            f"{comp:.2f}s), trivial bound {ph.trivial_bound!r}, conv "
            f"{ph.conv!r}, kernel iters {int(ph.state.solve_iters)}")
    stats = ph.solve_stats()
    mem = (devices[0].memory_stats() or {}).get("peak_bytes_in_use")
    log(f"full-size f64 certified re-solve (host CPU): "
        f"{stats['certify_wall_s']:.3f}s; device peak bytes in use: "
        f"{mem}")
    assert math.isfinite(triv) and all(math.isfinite(c) for c in convs)
    return {"trivial": triv, "convs": convs,
            "certify_s": stats["certify_wall_s"]}


def ph_trajectory(devices, S, mult, iters=3, max_iters=3000):
    """Iter0 + `iters` PH iterations of farmer S x mult (f32) on a
    ScenarioMesh over `devices`: (trivial bound, [conv per step],
    Lagrangian bound)."""
    import numpy as np

    from mpisppy_tpu.models import farmer
    from mpisppy_tpu.opt.ph import PH

    opts = {"defaultPHrho": 1.0, "PHIterLimit": iters, "convthresh": 0.0,
            "pdhg_max_iters": max_iters, "pdhg_eps": 1e-5}
    ph = PH(opts, [f"scen{i}" for i in range(S)],
            batch=farmer.build_batch(S, crops_multiplier=mult,
                                     dtype=np.float32),
            mesh=_mesh(devices))
    ph.Iter0()
    convs = [ph.conv]
    for _ in range(iters):
        ph.ph_iteration()
        convs.append(ph.conv)
    return ph.trivial_bound, convs, ph.lagrangian_bound()


def phase_mesh_parity(devices, S=64, mult=10):
    """PH on a len(devices)-chip ScenarioMesh against one chip: bounds
    and conv must agree to PARITY_RTOL."""
    t = time.perf_counter()
    triv, convs, lag = ph_trajectory(devices, S, mult)
    t_n = time.perf_counter() - t
    t = time.perf_counter()
    triv1, convs1, lag1 = ph_trajectory(devices[:1], S, mult)
    t_1 = time.perf_counter() - t
    log(f"mesh parity farmer S={S} crops_multiplier={mult}: "
        f"{len(devices)} chips trivial={triv!r} lagrangian={lag!r} "
        f"conv={convs} ({t_n:.2f}s) | 1 chip trivial={triv1!r} "
        f"lagrangian={lag1!r} conv={convs1} ({t_1:.2f}s)")

    def close(a, b):
        return abs(a - b) <= PARITY_RTOL * max(abs(b), 1.0)

    assert close(triv, triv1), (triv, triv1)
    assert close(lag, lag1), (lag, lag1)
    assert all(close(a, c) for a, c in zip(convs, convs1)), (convs, convs1)
    assert convs[-1] < convs[0], convs
    return {"trivial": (triv, triv1), "lagrangian": (lag, lag1),
            "convs": (convs, convs1)}


def phase_mpmd_collective(devices, S=8, iters=5):
    """A few lockstep supersteps of the MPMD wheel (PH hub, Lagrangian
    and xhat spokes on disjoint slices) over the collective exchange
    fabric; every mailbox read passes read_checked."""
    import math

    import numpy as np

    from mpisppy_tpu import telemetry
    from mpisppy_tpu.cylinders.hub import PHHub
    from mpisppy_tpu.cylinders.lagrangian_bounder import (
        LagrangianOuterBound)
    from mpisppy_tpu.cylinders.xhatshufflelooper_bounder import (
        XhatShuffleInnerBound)
    from mpisppy_tpu.models import farmer
    from mpisppy_tpu.mpmd import MPMDWheel, SlicePlan
    from mpisppy_tpu.opt.ph import PH
    from mpisppy_tpu.utils.xhat_eval import Xhat_Eval

    names = [f"scen{i}" for i in range(S)]
    opts = {"defaultPHrho": 1.0, "PHIterLimit": iters, "convthresh": 0.0,
            "pdhg_eps": 1e-5, "pdhg_max_iters": 5000}
    hub_dict = {
        "hub_class": PHHub,
        "hub_kwargs": {"options": {"rel_gap": 1e-9, "abs_gap": 1e-9,
                                   "window_backend": "collective"}},
        "opt_class": PH,
        "opt_kwargs": {"options": dict(opts), "all_scenario_names": names,
                       "batch": farmer.build_batch(S, dtype=np.float32)},
    }
    spoke_dicts = [
        {"spoke_class": LagrangianOuterBound,
         "spoke_kwargs": {"options": {}}, "opt_class": PH,
         "opt_kwargs": {"options": dict(opts),
                        "all_scenario_names": names}},
        {"spoke_class": XhatShuffleInnerBound,
         "spoke_kwargs": {"options": {}}, "opt_class": Xhat_Eval,
         "opt_kwargs": {"options": dict(opts),
                        "all_scenario_names": names}},
    ]
    telemetry.reset()
    telemetry.configure(True)
    try:
        plan = SlicePlan.partition(len(spoke_dicts), devices=list(devices))
        ws = MPMDWheel(hub_dict, spoke_dicts, plan=plan, lockstep=True)
        t = time.perf_counter()
        ws.spin()
        wall = time.perf_counter() - t
        counters = telemetry.wheel_counters()
    finally:
        telemetry.reset()
    checked = [w.read_checked() for w in ws.fabric.windows]
    outer, inner = float(ws.BestOuterBound), float(ws.BestInnerBound)
    log(f"mpmd collective wheel: {plan.n_slices} slices over "
        f"{len(plan.devices)} chips, backend {ws.exchange_backend_used}, "
        f"{counters['wheel_collective_exchanges']} fused exchanges, "
        f"{counters['wheel_exchange_writes']} writes, corrupt reads "
        f"{counters['wheel_corrupt_reads']}, outer {outer!r} inner "
        f"{inner!r}, windows read_checked ok "
        f"{sum(ok for _, _, ok, _ in checked)}/{len(checked)}, "
        f"wall {wall:.2f}s")
    assert ws.exchange_backend_used == "collective"
    assert counters["wheel_collective_exchanges"] > 0
    assert counters["wheel_corrupt_reads"] == 0, counters
    assert checked and all(ok for _, _, ok, _ in checked), checked
    assert math.isfinite(outer) and math.isfinite(inner)
    assert outer <= inner + 1e-3 * max(abs(inner), 1.0), (outer, inner)
    return {"outer": outer, "inner": inner, "counters": counters}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (devices: {devices})")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"TPU chips, JAX found {len(devices)}")
    devices = devices[:args.chips]

    from mpisppy_tpu.utils.platform import enable_compile_cache
    cache_dir = enable_compile_cache()
    clock = CompileClock()
    log(f"devices: {len(devices)} x {devices[0].device_kind}; "
        f"compile cache {cache_dir}")
    if args.chips == 1:
        phase_golden(devices, clock=clock)
        phase_full_size(devices, clock=clock)
    else:
        phase_mesh_parity(devices)
        phase_mpmd_collective(devices)
    log(f"compile seconds total: {clock.seconds:.2f} "
        f"(persistent-cache hits: {clock.cache_hits})")
    print(contract_line(devices), flush=True)


if __name__ == "__main__":
    main()
