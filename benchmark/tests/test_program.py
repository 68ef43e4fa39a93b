"""program.py: the program's spans and scope names beside trace.py's
reduction, on a hand-made trace with known answers and on traces
recorded on a TPU v5e (data/)."""

import dataclasses
import importlib
import os
from types import SimpleNamespace as NS

import pytest

from benchmark import program as P
from benchmark import trace as T

DATA = os.path.join(os.path.dirname(__file__), "data")
F_TRACE = os.path.join(DATA, "tpu_v5e.xplane.pb")
EF_TRACE = os.path.join(DATA, "tpu_v5e_sslp_ef.xplane.pb")
READERS = ("ef_idle_us", "launches_per_solve", "consensus_share",
           "matvec_share")


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def profile(planes):
    return NS(planes=[NS(name=n, lines=[NS(name=ln, events=evs)
                                        for ln, evs in lines])
                      for n, lines in planes])


# window 0..1000 ns, two EF solves.  Solve 1: eager ops [120,125)
# [140,145), the kernel's while [210,400) around a consensus fusion
# [210,300), a matvec fusion [300,350) and a copy [350,400); a readback's
# reduce [420,430).  Solve 2: while [700,800) around consensus [700,760)
# and matvec [760,800); a while that encloses nothing [850,860).
# Launches: 5 in solve 1, 3 in solve 2, 1 between them.
HAND = profile([
    ("/device:TPU:0", [("XLA Ops", [
        ev("multiply.1 multiply", 120, 5), ev("multiply.2 multiply", 140, 5),
        ev("while.3 while", 210, 190), ev("fusion.1 fusion", 210, 90),
        ev("fusion.2 fusion", 300, 50), ev("copy.1 copy", 350, 50),
        ev("reduce.1 reduce", 420, 10),
        ev("while.3 while", 700, 100), ev("fusion.1 fusion", 700, 60),
        ev("fusion.2 fusion", 760, 40), ev("while.9 while", 850, 10)])]),
    ("/host:CPU", [
        ("python", [
            ev("bench.window", 0, 1000),
            ev("bench.solve_extensive_form", 100, 400),
            ev("sppy.ef.solve", 110, 380), ev("sppy.ef.weights", 110, 40),
            ev("sppy.pdhg.args", 150, 20), ev("sppy.pdhg.dispatch", 170, 30),
            ev("sppy.ef.log", 200, 290),
            ev("bench.solve_extensive_form", 600, 300),
            ev("sppy.ef.solve", 610, 280),
            ev("sppy.pdhg.dispatch", 650, 30), ev("sppy.ef.log", 680, 210)]),
        ("launch", [ev(T.LAUNCH, t, 1)
                    for t in (115, 135, 175, 205, 415, 550, 655, 690, 845)]),
    ]),
])
SCOPE = {"fusion.1 fusion": "jit(_solve_impl)/while/body/pdhg_consensus/"
                            "scatter-add",
         "fusion.2 fusion": "jit(_solve_impl)/while/body/pdhg_matvec/"
                            "dot_general",
         "copy.1 copy": "jit(_solve_impl)/while",
         "reduce.1 reduce": "jit(sum)/reduce_sum"}


def reader(stem):
    return importlib.import_module(f"benchmark.layers.{stem}")


def hand():
    tr = T.from_profile(HAND)
    prog = dataclasses.replace(P.from_profile(HAND, b""), scope={0: SCOPE})
    return tr, prog


def test_leaf_drops_only_containers_that_enclose_ops():
    tr, _ = hand()
    names = [n for _, _, n in P.leaf(tr.ops[0])]
    assert "while.3 while" not in names
    assert "while.9 while" in names
    assert len(names) == len(tr.ops[0]) - 2


def test_scope_of_takes_the_innermost_scope():
    assert P.scope_of("jit(f)/while/body/pdhg_matvec/dot_general") == \
        P.MATVEC
    assert P.scope_of("jit(f)/pdhg_matvec/pdhg_consensus/add") == \
        P.CONSENSUS
    assert P.scope_of("jit(f)/while") is None
    assert P.scope_of("") is None


def test_hand_trace():
    tr, prog = hand()
    lo, hi = T.span_window(tr)
    assert [n for _, _, n in prog.spans].count(P.SOLVE) == 2
    # idle inside the solve spans: 380 - 210 and 280 - 110 ns
    assert P.ef_idle_us(tr, prog, lo, hi) == pytest.approx(0.170)
    assert P.launches_per_solve(prog, lo, hi) == 4.0
    shares = P.scope_shares(tr, prog, lo, hi)
    assert shares == pytest.approx({P.CONSENSUS: 100 * 150 / 320,
                                    P.MATVEC: 100 * 90 / 320,
                                    None: 100 * 80 / 320})
    idle = P.idle_by_program_span(tr, prog, lo, hi)
    assert idle == pytest.approx({
        "window": 300e-9, "solve_extensive_form": 40e-9,
        "sppy.ef.solve": 40e-9, "sppy.ef.weights": 30e-9,
        "sppy.pdhg.args": 20e-9, "sppy.pdhg.dispatch": 60e-9,
        "sppy.ef.log": 190e-9})
    # the solve span's own entry and its children's make up its idle
    in_solve = sum(v for k, v in idle.items() if k.startswith(P.PREFIX))
    assert in_solve * 1e6 / 2 == pytest.approx(
        P.ef_idle_us(tr, prog, lo, hi))
    # trace.py's own breakdown reads as before
    assert T.idle_by_span(tr, lo, hi) == pytest.approx({
        "window": 300e-9, "solve_extensive_form": 380e-9})


def test_readers_on_the_hand_trace():
    tr, prog = hand()
    lo, hi = T.span_window(tr)
    ctx = NS(trace=tr, program=prog, lo=lo, hi=hi)
    got = {r: reader(r).read(ctx) for r in READERS}
    assert got == pytest.approx({
        "ef_idle_us": 0.170, "launches_per_solve": 4.0,
        "consensus_share": 100 * 150 / 320, "matvec_share": 100 * 90 / 320})


def test_readers_read_nothing_without_program_spans():
    tr, prog = hand()
    lo, hi = T.span_window(tr)
    # a harness that hands no program spans, and a program that has none
    for ctx in (NS(trace=tr, lo=lo, hi=hi),
                NS(trace=tr, program=P.Program([], [], {}), lo=lo, hi=hi)):
        assert all(reader(r).read(ctx) is None for r in READERS)
    # a trace without scope paths, as on the CPU: no shares, idle still
    bare = dataclasses.replace(prog, scope={})
    ctx = NS(trace=tr, program=bare, lo=lo, hi=hi)
    assert reader("consensus_share").read(ctx) is None
    assert reader("matvec_share").read(ctx) is None
    assert reader("ef_idle_us").read(ctx) == pytest.approx(0.170)


@pytest.mark.skipif(not os.path.exists(F_TRACE), reason="no recorded trace")
def test_scope_map_of_the_recorded_trace():
    with open(F_TRACE, "rb") as f:
        scope = P.scope_map(f.read())
    assert list(scope) == [0]
    assert scope[0]["reduce_sum.7 reduce"] == "jit(f)/reduce_sum"
    assert scope[0]["fusion.8 fusion"] == \
        "jit(f)/while/body/closed_call/dot_general"


@pytest.mark.skipif(not os.path.exists(EF_TRACE), reason="no recorded trace")
def test_recorded_ef_trace():
    """Two sslp_5_25_50 EF solves stopped at their first check (pdhg_eps
    1e6), traced on a TPU v5e with the program's telemetry on, inside
    the benchmark's spans.  Cut to what the readers read: the TPU plane's
    "XLA Ops" (without their stats) and "XLA Modules" lines, each op's
    text up to its kind and its tf_op stat, the host's span and launch
    events."""
    from jax.profiler import ProfileData
    with open(EF_TRACE, "rb") as f:
        raw = f.read()
    profile = ProfileData.from_serialized_xspace(raw)
    tr, prog = T.from_profile(profile), P.from_profile(profile, raw)
    lo, hi = T.span_window(tr)
    names = [n for _, _, n in prog.spans]
    assert names.count(P.SOLVE) == 2
    assert set(names) == {P.SOLVE, "sppy.ef.weights", "sppy.pdhg.args",
                          "sppy.pdhg.dispatch", "sppy.ef.certify",
                          "sppy.ef.log"}
    # on the chip's clock as trace.py shifts it, every module launched
    # inside a dispatch span starts after that span opens
    modules = sorted(e.start_ns for plane in profile.planes
                     if plane.name == "/device:TPU:0"
                     for line in plane.lines if line.name == "XLA Modules"
                     for e in line.events)
    assert len(modules) == len(prog.launches)
    dt = T.clock_offset(modules, prog.launches)
    dispatch = [(s, e) for s, e, n in prog.spans if n == "sppy.pdhg.dispatch"]
    started = [(m + dt, s) for m, t in zip(modules, prog.launches)
               for s, e in dispatch if s <= t < e]
    assert len(started) == len(dispatch)
    assert all(m >= s for m, s in started)
    # the kernel's leaf ops fall under both scopes, and the shares add up
    shares = P.scope_shares(tr, prog, lo, hi)
    assert shares[P.CONSENSUS] > shares[P.MATVEC] > 0
    assert sum(shares.values()) == pytest.approx(100.0)
    # no while-container counted: each one here encloses its body's ops
    assert any(n.endswith(" while") for _, _, n in tr.ops[0])
    assert not any(n.endswith(" while") for _, _, n in P.leaf(tr.ops[0]))
    # the program spans split the solve's idle time, remainder included
    idle = P.idle_by_program_span(tr, prog, lo, hi)
    in_solve = sum(v for k, v in idle.items() if k.startswith(P.PREFIX))
    assert in_solve * 1e6 / 2 == pytest.approx(P.ef_idle_us(tr, prog, lo, hi))
    assert P.launches_per_solve(prog, lo, hi) == 12.0
