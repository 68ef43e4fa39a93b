"""Telemetry subsystem tests (mpisppy_tpu/telemetry/): registry and
tracer units, Chrome trace export, the program's spans in a JAX
profiler trace and its scope names on the lowered PDHG solve, wheel
smoke producing the merged trace.json + metrics.jsonl, supervisor
lifecycle events, and the two structural guarantees of the
zero-cost-when-off contract —

  * the telemetry package imports nothing of jax but the tracer's
    profiler annotation and never syncs the device (AST guard over the
    package AND over every instrumented hot-path module, pinned to an
    allowlist of block_until_ready sites);
  * a telemetry-disabled PH iteration runs the pre-telemetry fused
    superstep with <2% measured overhead.
"""

import ast
import inspect
import json
import subprocess
import sys
import time
import types
from pathlib import Path

import numpy as np
import pytest

import mpisppy_tpu
from mpisppy_tpu import phbase, telemetry
from mpisppy_tpu.models import farmer
from mpisppy_tpu.opt.ph import PH
from mpisppy_tpu.telemetry import export
from mpisppy_tpu.telemetry.metrics import (MetricsRegistry, NULL_COUNTER,
                                           NULL_GAUGE, NULL_HISTOGRAM)
from mpisppy_tpu.telemetry.tracer import NULL_SPAN, Tracer
from mpisppy_tpu.utils.wtracker import WTracker

pytestmark = pytest.mark.telemetry

S = 3
NAMES = [f"scen{i}" for i in range(S)]
OPTS = {"defaultPHrho": 1.0, "PHIterLimit": 40, "convthresh": 0.0,
        "pdhg_eps": 1e-7, "pdhg_max_iters": 20000}


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    """Each test starts and ends with no process-global telemetry (the
    env var is absent in the test tier, so get() is disabled)."""
    telemetry.reset()
    yield
    telemetry.reset()


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------
class TestRegistry:
    def test_counter_gauge_histogram(self):
        r = MetricsRegistry()
        c = r.counter("a")
        c.inc()
        c.inc(4)
        assert c.value == 5
        assert r.counter("a") is c          # get-or-create
        g = r.gauge("b")
        g.set(2.5)
        assert g.value == 2.5
        h = r.histogram("t")
        for v in (0.001, 0.01, 3.0):
            h.observe(v)
        assert h.count == 3
        assert h.min == 0.001 and h.max == 3.0
        assert abs(h.mean - (3.011 / 3)) < 1e-12
        assert sum(h.bucket_counts) == 3

    def test_histogram_inf_tail(self):
        r = MetricsRegistry()
        h = r.histogram("t")
        h.observe(1e9)                      # beyond every bucket
        assert h.bucket_counts[-1] == 1

    def test_event_log_bounded(self):
        r = MetricsRegistry(max_events=4)
        for i in range(10):
            r.event("e", i=i)
        evs = r.events("e")
        assert len(evs) == 4
        assert evs[-1]["i"] == 9
        assert all("ts" in e for e in evs)

    def test_disabled_registry_returns_nulls(self):
        r = MetricsRegistry(enabled=False)
        assert r.counter("x") is NULL_COUNTER
        assert r.gauge("x") is NULL_GAUGE
        assert r.histogram("x") is NULL_HISTOGRAM
        r.counter("x").inc()
        r.gauge("x").set(1.0)
        r.histogram("x").observe(1.0)
        r.event("boom")
        snap = r.snapshot()
        assert snap["counters"] == {} and snap["events"] == []

    def test_jsonl_snapshot_strict_json(self, tmp_path):
        r = MetricsRegistry()
        r.counter("window.writes").inc(3)
        r.gauge("hub.best_outer").set(float("-inf"))   # pre-seed bound
        r.gauge("hub.best_inner").set(float("nan"))
        p = tmp_path / "metrics.jsonl"
        r.write_jsonl(str(p))
        r.write_jsonl(str(p))               # JSONL appends
        lines = p.read_text().strip().splitlines()
        assert len(lines) == 2
        snap = json.loads(lines[-1])        # strict parser
        assert snap["counters"]["window.writes"] == 3
        assert snap["gauges"]["hub.best_outer"] is None   # non-finite
        assert snap["gauges"]["hub.best_inner"] is None

    def test_prometheus_text(self, tmp_path):
        r = MetricsRegistry()
        r.counter("window.writes").inc(2)
        r.gauge("ph.conv").set(0.5)
        h = r.histogram("solve.seconds")
        h.observe(0.02)
        h.observe(200.0)
        text = r.prometheus_text()
        assert "# TYPE window_writes counter\nwindow_writes 2" in text
        assert "ph_conv 0.5" in text
        # cumulative le buckets + +Inf + sum/count
        assert 'solve_seconds_bucket{le="+Inf"} 2' in text
        assert "solve_seconds_count 2" in text
        assert "solve_seconds_sum 200.02" in text
        p = tmp_path / "prom.txt"
        r.write_prometheus(str(p))
        assert p.read_text() == text


# ---------------------------------------------------------------------------
# tracer + chrome export
# ---------------------------------------------------------------------------
class TestTracer:
    def test_span_records_complete_event(self):
        tr = Tracer()
        with tr.span("work", args={"k": 1}):
            time.sleep(0.002)
        recs = tr.records()
        assert len(recs) == 1
        kind, name, pid, tid, ts, dur, args = recs[0]
        assert kind == "X" and name == "work"
        assert pid == tr._pid and args == {"k": 1}
        assert dur >= 1000                  # at least 1 ms, in µs

    def test_ring_wraps_and_counts_drops(self):
        tr = Tracer(capacity=16)
        for i in range(40):
            tr.instant(f"e{i}")
        recs = tr.records()
        assert len(recs) == 16
        assert recs[-1][1] == "e39"         # newest survives
        assert tr.emitted == 40 and tr.dropped == 24

    def test_tracks_get_distinct_row_pids(self):
        tr = Tracer(main_label="hub")
        with tr.span("hub-side"):
            pass
        with tr.track("spoke0:Lagrangian"):
            with tr.span("step"):
                pass
        with tr.track("spoke1:Xhat"):
            tr.instant("evt")
        pids = {rec[2] for rec in tr.records()}
        assert len(pids) == 3
        assert tr._pid in pids
        assert len(tr._tracks) == 2
        assert len(set(tr._tracks.values()) | {tr._pid}) == 3

    def test_span_and_counter(self):
        tr = Tracer()
        with tr.span("solve.loop"):
            time.sleep(0.005)
        tr.counter("hub.bounds", {"outer": -1.0})
        recs = tr.records()
        assert recs[0][0] == "X" and recs[0][5] >= 5000   # µs
        assert recs[1][0] == "C" and recs[1][4] == {"outer": -1.0}

    def test_chrome_export_is_valid_trace(self, tmp_path):
        tr = Tracer(main_label="hub")
        with tr.span("a"):
            pass
        with tr.track("spoke0"):
            tr.instant("b")
        p = tmp_path / "trace.json"
        export.write_trace(str(p), export.chrome_events(tr))
        data = json.loads(p.read_text())
        evs = data["traceEvents"]
        meta = [e for e in evs if e["ph"] == "M"]
        names = {e["args"]["name"] for e in meta}
        assert {"hub", "spoke0"} <= names
        assert all({"ph", "pid"} <= set(e) for e in evs)
        assert any(e["ph"] == "X" and e["name"] == "a" for e in evs)
        assert any(e["ph"] == "i" and e["name"] == "b" for e in evs)

    def test_merge_traces_metadata_first_then_by_ts(self, tmp_path):
        tr1, tr2 = Tracer(main_label="hub"), Tracer(main_label="spokeP")
        with tr1.span("one"):
            time.sleep(0.001)
        with tr2.span("two"):
            pass
        f2 = tmp_path / "spoke.json"
        export.write_trace(str(f2), export.chrome_events(tr2))
        out = tmp_path / "trace.json"
        export.merge_traces(str(out),
                            event_lists=[export.chrome_events(tr1)],
                            trace_files=[str(f2), str(tmp_path / "no")])
        evs = json.loads(out.read_text())["traceEvents"]
        kinds = [e["ph"] for e in evs]
        assert kinds[:2] == ["M", "M"]
        rest = [e["ts"] for e in evs if e["ph"] != "M"]
        assert rest == sorted(rest)

    def test_corrupt_trace_file_ignored(self, tmp_path):
        p = tmp_path / "bad.json"
        p.write_text("{not json")
        assert export.load_trace_events(str(p)) == []


# ---------------------------------------------------------------------------
# config facade
# ---------------------------------------------------------------------------
class TestConfig:
    def test_default_disabled(self):
        tel = telemetry.get()
        assert not tel.enabled
        assert tel.span("x") is NULL_SPAN
        assert tel.counter("c") is NULL_COUNTER

    def test_config_forms(self, tmp_path):
        assert telemetry.configure(True).enabled
        assert not telemetry.configure("off").enabled
        tel = telemetry.configure(str(tmp_path))
        assert tel.enabled and tel.out_dir == str(tmp_path)
        tel = telemetry.configure({"enabled": True,
                                   "phase_timing": False})
        assert tel.enabled and not tel.phase_timing

    def test_env_var_wins(self, monkeypatch):
        monkeypatch.setenv(telemetry.ENV_VAR, "0")
        assert not telemetry.configure({"enabled": True}).enabled
        monkeypatch.setenv(telemetry.ENV_VAR, "1")
        assert telemetry.configure(None).enabled

    def test_env_dir_form(self, monkeypatch, tmp_path):
        monkeypatch.setenv(telemetry.ENV_VAR, str(tmp_path))
        telemetry.reset()
        tel = telemetry.get()
        assert tel.enabled and tel.out_dir == str(tmp_path)

    def test_configure_from_options_is_idempotent(self):
        a = telemetry.configure_from_options({"enabled": True})
        a.counter("keep").inc()
        b = telemetry.configure_from_options({"enabled": True})
        assert a is b                       # same registry survives
        assert b.registry.counter("keep").value == 1
        c = telemetry.configure_from_options({"enabled": True,
                                              "capacity": 1024})
        assert c is not a
        # None never resets an installed instance
        assert telemetry.configure_from_options(None) is c

    def test_traffic_counters_stable_keys_when_off(self):
        tc = telemetry.traffic_counters()
        assert tc == {"window_writes": 0, "window_reads": 0,
                      "window_stale_reads": 0, "window_kill_signals": 0,
                      "window_bound_rejects": 0}


# ---------------------------------------------------------------------------
# instrumented hot paths
# ---------------------------------------------------------------------------
def _ph(tel_cfg, batch=None, **overrides):
    opts = {**OPTS, "telemetry": tel_cfg, **overrides}
    return PH(opts, NAMES, batch=batch or farmer.build_batch(S))


class TestInstrumentation:
    def test_disabled_mode_records_nothing(self):
        ph = _ph(None)
        assert not ph._tel.enabled
        ph.Iter0()
        for _ in range(2):
            ph.ph_iteration()
        tel = telemetry.get()
        assert tel.tracer.records() == []
        assert tel.registry._counters == {}
        assert tel.registry.events() == []

    def test_enabled_ph_metrics(self):
        ph = _ph({"enabled": True, "phase_timing": False})
        ph.Iter0()
        ph.ph_iteration()
        ph.ph_iteration()
        r = ph._tel.registry
        assert r.counter("ph.iterations").value == 2
        # the fused superstep never routes through solve_loop; Iter0 does
        assert r.counter("solve.calls").value >= 1
        assert r.counter("solve.kernel_iters").value > 0
        assert r.histogram("ph.iteration_seconds").count == 2
        assert r.histogram("solve.seconds").count >= 1
        assert r.events("ph.iter0") and \
            r.events("ph.iter0")[0]["trivial_bound"] == pytest.approx(
                ph.trivial_bound)
        # the supersteps' inner iterations add to Iter0's
        assert r.counter("pdhg.inner_iters_total").value > \
            r.counter("solve.kernel_iters").value > 0
        names = {rec[1] for rec in ph._tel.tracer.records()}
        assert {"solve.loop", "ph.iteration"} <= names

    def test_phased_superstep_matches_fused(self):
        b = farmer.build_batch(S)
        ph_f = _ph(False, batch=b)
        ph_f.Iter0()
        telemetry.reset()
        ph_p = _ph({"enabled": True, "phase_timing": True}, batch=b)
        ph_p.Iter0()
        for _ in range(3):
            ph_f.ph_iteration()
            ph_p.ph_iteration()
        for field in ("x", "xbar", "W", "conv"):
            np.testing.assert_allclose(
                np.asarray(getattr(ph_p.state, field)),
                np.asarray(getattr(ph_f.state, field)),
                rtol=1e-6, atol=1e-8, err_msg=field)
        h = ph_p._tel.registry.histogram
        for k in ("solve", "psum", "w_update", "conv"):
            assert h(f"ph.phase.{k}_seconds").count == 3


# ---------------------------------------------------------------------------
# spans on the profiler's clock, scope names on the device ops
# ---------------------------------------------------------------------------
EF_SPANS = {"ef.solve", "ef.weights", "pdhg.args", "pdhg.dispatch",
            "ef.certify", "ef.log"}


def _ef(tel_cfg):
    from mpisppy_tpu.opt.ef import ExtensiveForm
    return ExtensiveForm({"pdhg_eps": 1e-6, "telemetry": tel_cfg}, NAMES,
                         batch=farmer.build_batch(S))


def _traced_solve(ef, trace_dir):
    """The program's spans of one EF solve under the JAX profiler, as
    the benchmark reads them (benchmark/program.py)."""
    import jax

    from benchmark import program
    ef.solve_extensive_form(certify=False)     # compiles outside the trace
    jax.profiler.start_trace(str(trace_dir))
    try:
        ef.solve_extensive_form(certify=False)
    finally:
        jax.profiler.stop_trace()
    return program.load(str(trace_dir)).spans


class TestProfilerSpans:
    def test_enabled_spans_land_in_the_profiler_trace(self, tmp_path):
        ef = _ef({"enabled": True, "phase_timing": False})
        spans = _traced_solve(ef, tmp_path)
        assert {n for _, _, n in spans} == {"sppy." + n for n in EF_SPANS}
        (s, e), = [(s, e) for s, e, n in spans if n == "sppy.ef.solve"]
        (ds, de), = [(s, e) for s, e, n in spans
                     if n == "sppy.pdhg.dispatch"]
        assert s <= ds < de <= e
        recs = [r for r in ef._tel.tracer.records() if r[0] == "X"]
        assert {r[1] for r in recs} == EF_SPANS
        # the spans of one solve carry its id: the traced solve is the 2nd
        assert [r[6] for r in recs if r[1] == "ef.solve"] == [
            {"solve": 1}, {"solve": 2}]

    def test_disabled_spans_leave_no_trace(self, tmp_path):
        ef = _ef(None)
        assert not ef._tel.enabled
        assert _traced_solve(ef, tmp_path) == []
        assert ef._tel.span("ef.solve", solve=1) is NULL_SPAN
        assert ef._tel.tracer.records() == []

    def test_scopes_in_the_lowered_consensus_solve(self):
        import re

        import jax.numpy as jnp

        from mpisppy_tpu.ops.pdhg import shared_solve_jit
        ef = _ef(None)
        b = ef.batch
        (n_s, n), m = b.c.shape, ef.prep.A.shape[1]
        text = shared_solve_jit(ef.solver).lower(
            ef.prep, b.c, b.qdiag, b.lb, b.ub, jnp.zeros(n_s),
            jnp.zeros((n_s, n)), jnp.zeros((n_s, m)), ef.consensus, None,
            None).as_text(debug_info=True)
        locs = set(re.findall(r'loc\("([^"]+)"', text))
        for scope in ("pdhg_matvec", "pdhg_consensus"):
            # in the PDHG loop's body, not only in its prologue
            assert any(re.search(rf"/while/body/.*\b{scope}/", loc)
                       for loc in locs), scope


class TestWTracker:
    def test_ring_buffer_is_deque(self):
        import collections
        fake = types.SimpleNamespace(state=None)
        wt = WTracker(fake, wlen=10)
        assert isinstance(wt._hist, collections.deque)
        assert wt._hist.maxlen == 10
        for i in range(15):
            fake.state = types.SimpleNamespace(
                it=i, W=np.full((2, 3), float(i)))
            wt.grab_local_Ws()
        assert len(wt._hist) == 10
        assert wt._hist[0][0] == 5          # oldest evicted
        mean, std = wt.moving_stats()
        assert mean.shape == (2, 3)
        assert mean[0, 0] == pytest.approx(np.mean(range(5, 15)))


# ---------------------------------------------------------------------------
# wheel smoke: merged trace + metrics artifacts
# ---------------------------------------------------------------------------
class TestWheelSmoke:
    def test_farmer_wheel_writes_merged_trace_and_metrics(self, tmp_path):
        from mpisppy_tpu.cylinders.hub import PHHub
        from mpisppy_tpu.cylinders.lagrangian_bounder import (
            LagrangianOuterBound)
        from mpisppy_tpu.cylinders.xhatshufflelooper_bounder import (
            XhatShuffleInnerBound)
        from mpisppy_tpu.spin_the_wheel import WheelSpinner
        from mpisppy_tpu.utils.xhat_eval import Xhat_Eval

        tel_cfg = {"enabled": True, "dir": str(tmp_path),
                   "prometheus": True}
        b = farmer.build_batch(S)
        opts = {**OPTS, "PHIterLimit": 10, "telemetry": tel_cfg}
        hub_dict = {
            "hub_class": PHHub,
            "hub_kwargs": {"options": {"rel_gap": 1e-4, "abs_gap": 1.0,
                                       "telemetry": tel_cfg}},
            "opt_class": PH,
            "opt_kwargs": {"options": opts, "all_scenario_names": NAMES,
                           "batch": b},
        }
        spoke_dicts = [
            {"spoke_class": LagrangianOuterBound,
             "spoke_kwargs": {"options": {"telemetry": tel_cfg}},
             "opt_class": PH,
             "opt_kwargs": {"options": dict(opts),
                            "all_scenario_names": NAMES}},
            {"spoke_class": XhatShuffleInnerBound,
             "spoke_kwargs": {"options": {"telemetry": tel_cfg}},
             "opt_class": Xhat_Eval,
             "opt_kwargs": {"options": dict(opts),
                            "all_scenario_names": NAMES}},
        ]
        WheelSpinner(hub_dict, spoke_dicts, mode="interleaved").spin()

        # -- merged Chrome trace: one row per hub/spoke ------------------
        trace = tmp_path / "trace.json"
        assert trace.exists()
        evs = json.loads(trace.read_text())["traceEvents"]
        meta_names = {e["args"]["name"] for e in evs if e["ph"] == "M"}
        assert "hub" in meta_names
        assert any(n.startswith("spoke0:") for n in meta_names)
        assert any(n.startswith("spoke1:") for n in meta_names)
        pids = {e["pid"] for e in evs if e["ph"] != "M"}
        assert len(pids) >= 3               # hub + 2 spoke rows
        names = {e["name"] for e in evs}
        assert "hub.sync" in names
        assert "LagrangianOuterBound.step" in names
        assert "XhatShuffleInnerBound.step" in names

        # -- metrics snapshot --------------------------------------------
        mpath = tmp_path / "metrics.jsonl"
        assert mpath.exists()
        snap = json.loads(mpath.read_text().strip().splitlines()[-1])
        c = snap["counters"]
        assert c["window.writes"] > 0
        assert c["window.reads"] > 0
        assert c["window.kill_signals"] >= 2
        assert c["ph.iterations"] > 0
        # phase timing was on (default): superstep phase histograms
        assert snap["histograms"]["ph.phase.solve_seconds"]["count"] > 0
        assert (tmp_path / "prometheus.txt").exists()


# ---------------------------------------------------------------------------
# supervisor lifecycle events
# ---------------------------------------------------------------------------
def _fake_hub(n):
    from mpisppy_tpu.cylinders.spcommunicator import Window
    hub = types.SimpleNamespace(
        options={},
        spokes=[types.SimpleNamespace(proc=None, spoke_name=f"Spoke{i}")
                for i in range(n)],
        pairs=[types.SimpleNamespace(to_hub=Window(1)) for _ in range(n)],
        failed=[])
    hub._mark_spoke_failed = lambda i, exc: hub.failed.append(
        (i, str(exc)))
    return hub


def _sleeper_spawn(spec, workdir, tag):
    return subprocess.Popen([sys.executable, "-c",
                             "import time; time.sleep(60)"])


class TestSupervisorEvents:
    def _drive(self, sup, until, timeout=30.0):
        t0 = time.monotonic()
        while not until() and time.monotonic() - t0 < timeout:
            sup.poll(force=True)
            time.sleep(0.02)
        assert until(), "supervisor never reached the expected state"

    def test_restart_and_prune_events_recorded(self):
        from mpisppy_tpu.resilience.supervisor import SpokeSupervisor
        telemetry.configure({"enabled": True})
        hub = _fake_hub(1)    # no .telemetry -> falls back to get()
        sup = SpokeSupervisor(
            hub, specs=[{}], workdir=".", spawn_fn=_sleeper_spawn,
            options={"supervise_interval": 0.0,
                     "spoke_hang_timeout": 0.3,
                     "spoke_max_restarts": 1,
                     "spoke_restart_backoff": 0.01,
                     "spoke_term_deadline": 2.0})
        sup.start()
        try:
            self._drive(sup, lambda: sup.restarts[0] == 1)
            self._drive(sup, lambda: sup.spokes_failed == 1)
        finally:
            sup.kill_all()
        r = telemetry.get().registry
        # two incarnations spawned, one restart, then pruned
        assert len(r.events("supervisor.spawn")) == 2
        assert len(r.events("supervisor.restart")) == 1
        assert r.events("supervisor.restart")[0]["spoke"] == 0
        assert len(r.events("supervisor.prune")) == 1
        assert r.events("supervisor.sigterm")   # hang kill path
        assert r.counter("supervisor.restarts").value == 1
        assert r.counter("supervisor.spokes_failed").value == 1
        # heartbeat-age gauge was tracked while the spoke was live
        assert "supervisor.heartbeat_age.spoke0" in r._gauges

    def test_disabled_supervisor_emits_nothing(self):
        from mpisppy_tpu.resilience.supervisor import SpokeSupervisor
        hub = _fake_hub(1)

        def quick_spawn(spec, workdir, tag):
            return subprocess.Popen([sys.executable, "-c", "pass"])

        sup = SpokeSupervisor(hub, specs=[{}], workdir=".",
                              spawn_fn=quick_spawn,
                              options={"supervise_interval": 0.0})
        sup.start()
        hub.spokes[0].proc.wait(timeout=30)
        sup.poll(force=True)
        assert telemetry.get().registry.events() == []


# ---------------------------------------------------------------------------
# zero-cost-when-off guards
# ---------------------------------------------------------------------------
ROOT = Path(mpisppy_tpu.__file__).resolve().parent

# the ONLY functions in instrumented hot-path modules allowed to hold a
# device sync; anything new must be reviewed against the telemetry
# zero-cost contract (doc/src/telemetry.md) and added here explicitly
SYNC_ALLOWLIST = {
    "phbase.py": {"_run_superstep", "_superstep_phased"},
    "spopt.py": {"solve_loop", "_certified_resolve",
                 "evaluate_candidates"},
    "cylinders/spcommunicator.py": set(),
    "cylinders/spoke.py": set(),
    "cylinders/hub.py": set(),
    "cylinders/proc.py": set(),
    "resilience/supervisor.py": set(),
    "spin_the_wheel.py": set(),
}


def _sync_functions(path):
    """Names of functions whose body mentions block_until_ready."""
    src = path.read_text()
    tree = ast.parse(src)
    hits = [i + 1 for i, ln in enumerate(src.splitlines())
            if "block_until_ready" in ln and "#" not in ln.split(
                "block_until_ready")[0]]
    spans = [(n.lineno, n.end_lineno, n.name) for n in ast.walk(tree)
             if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
    out = set()
    for h in hits:
        enclosing = [s for s in spans if s[0] <= h <= s[1]]
        assert enclosing, f"{path}:{h}: module-level device sync"
        out.add(min(enclosing, key=lambda s: s[1] - s[0])[2])
    return out


# the one jax name the telemetry package may import: the tracer's
# profiler annotation, which cannot sync the device
TELEMETRY_JAX_IMPORT = ("tracer.py", "jax.profiler", ("TraceAnnotation",))


def _telemetry_jax_uses(filename, src):
    """What a telemetry module's source takes from jax beyond
    TELEMETRY_JAX_IMPORT, and every device sync it names."""
    bad = []
    for node in ast.walk(ast.parse(src)):
        if isinstance(node, ast.Import):
            bad += [a.name for a in node.names
                    if a.name.split(".")[0] == "jax"]
        elif isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            names = tuple(a.name for a in node.names)
            if mod.split(".")[0] == "jax" and \
                    (filename, mod, names) != TELEMETRY_JAX_IMPORT:
                bad.append(f"from {mod} import {', '.join(names)}")
        elif isinstance(node, ast.Attribute) and node.attr in (
                "block_until_ready", "device_get"):
            bad.append(node.attr)
    return bad


class TestZeroCostGuards:
    def test_telemetry_package_never_touches_jax(self):
        for p in (ROOT / "telemetry").glob("*.py"):
            assert _telemetry_jax_uses(p.name, p.read_text()) == [], p

    @pytest.mark.parametrize("filename, src", [
        ("tracer.py", "import jax"),
        ("tracer.py", "import jax.numpy as jnp"),
        ("tracer.py", "from jax import profiler"),
        ("tracer.py", "from jax.profiler import trace"),
        ("tracer.py", "from jax.profiler import TraceAnnotation, trace"),
        ("metrics.py", "from jax.profiler import TraceAnnotation"),
        ("tracer.py", "def f(x):\n    return x.block_until_ready()"),
    ])
    def test_telemetry_guard_refuses_other_jax_uses(self, filename, src):
        assert _telemetry_jax_uses(filename, src)

    def test_telemetry_guard_admits_the_tracer_annotation(self):
        assert _telemetry_jax_uses(
            "tracer.py", "from jax.profiler import TraceAnnotation") == []

    def test_hot_path_sync_sites_pinned_to_allowlist(self):
        for rel, allowed in SYNC_ALLOWLIST.items():
            p = ROOT / rel
            assert _sync_functions(p) == allowed, rel
            assert "device_get" not in p.read_text(), rel

    def test_disabled_path_is_the_fused_superstep(self):
        src = inspect.getsource(phbase.PHBase._run_superstep)
        # phased (unfused, per-phase-synced) execution is reachable
        # ONLY behind tel.phase_timing; the else branch is the original
        # fused jitted call
        assert "phase_timing" in src
        assert "_superstep_phased" in src
        assert "fused_superstep(solver)(" in src

    def test_disabled_overhead_under_two_percent(self):
        """Telemetry-off ph_iteration vs a replica of the pre-telemetry
        iteration body: <2% overhead (plus a small absolute slack to
        absorb scheduler noise at sub-ms per-iteration scale), taking
        the min over interleaved trials."""
        import jax

        ph = _ph(None, superstep_eps=1e-4)
        assert not ph._tel.enabled
        ph.Iter0()

        def raw_iteration():
            # byte-for-byte the pre-telemetry ph_iteration body
            ph._ext("pre_solve_loop")
            t0 = time.time()
            ph.state = ph._superstep(
                ph.state, ph.rho, ph.W_on, ph.prox_on,
                ph.lb_eff, ph.ub_eff, ph.superstep_eps, ph.prep,
                ph.batch)
            jax.block_until_ready(ph.state.x)
            it_n = int(ph.state.solve_iters)
            wall = time.time() - t0
            ph._solve_wall += wall
            ph._ext("post_solve_loop")
            ph.conv = float(ph.state.conv)
            return ph.conv

        def timed(fn, n=25):
            t0 = time.perf_counter()
            for _ in range(n):
                fn()
            return time.perf_counter() - t0

        raw_iteration()        # shared warmup
        ph.ph_iteration()
        t_raw, t_ins = [], []
        for _ in range(4):     # interleaved: drift hits both equally
            t_raw.append(timed(raw_iteration))
            t_ins.append(timed(ph.ph_iteration))
        assert min(t_ins) <= min(t_raw) * 1.02 + 0.05, \
            (min(t_ins), min(t_raw))
