"""Serve-layer tests: compile cache bucketing, request coalescing,
admission control, deadlines, worker supervision, and the batch=1
bitwise-parity guarantee against PH.ph_main (ISSUE 4 acceptance).

All tests here are tier-1 (`serve` marker, no `slow`): farmer-sized
batches, and every service in this file uses the SAME solver config so
the process-shared jit registries (phbase.fused_superstep,
ops.pdhg.shared_solve_jit) amortize compiles across tests.
"""

import ast
import os
import pathlib
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from mpisppy_tpu import telemetry
from mpisppy_tpu.models import farmer
from mpisppy_tpu.opt.ph import PH
from mpisppy_tpu.serve import compile_cache as cc
from mpisppy_tpu.serve.service import SolverService

pytestmark = pytest.mark.serve

REPO = pathlib.Path(__file__).resolve().parents[1]

# the golden-parity options (tests/test_ph_farmer.py's fixture config)
GOLDEN_OPTS = {"defaultPHrho": 1.0, "PHIterLimit": 200,
               "convthresh": 1e-5, "pdhg_eps": 1e-7}
# quick-loop options: SAME solver config (pdhg_eps keys the jit
# registries), loose superstep tolerance + tiny iteration budget
FAST_OPTS = {"defaultPHrho": 1.0, "PHIterLimit": 4, "convthresh": 1e-4,
             "pdhg_eps": 1e-7, "superstep_eps": 1e-5}


@pytest.fixture
def fresh_telemetry():
    prev = telemetry._active
    telemetry.reset()
    yield
    telemetry._active = prev


# -- import contract (the telemetry-guard pattern) ------------------------

def _module_level_imports(path):
    mods = set()
    for node in ast.parse(path.read_text()).body:
        if isinstance(node, ast.Import):
            mods.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            mods.add(node.module or "")
    return mods


def test_api_imports_jax_only_lazily():
    """serve/api.py (and the package front door) must be free to
    import: no module-level jax, directly or transitively.  The PR 11
    traffic layer (router.py, replica.py) is held to the same bar —
    the front door must be constructible in a process that never
    initializes a backend until a replica dispatches."""
    serve_dir = REPO / "mpisppy_tpu" / "serve"
    for fname in ("api.py", "__init__.py", "request.py",
                  "router.py", "replica.py"):
        mods = _module_level_imports(serve_dir / fname)
        bad = {m for m in mods
               if m == "jax" or m.startswith("jax.")}
        assert not bad, f"{fname} imports jax at module level: {bad}"
        # transitive heavyweights would smuggle jax in too
        heavy = {m for m in mods if ".service" in m or ".compile_cache"
                 in m or m.endswith("phbase") or m.endswith("spopt")}
        assert not heavy, f"{fname} imports {heavy} at module level"


def test_api_import_is_jax_free_in_fresh_process():
    code = ("import sys\n"
            "import mpisppy_tpu.serve.api\n"
            "import mpisppy_tpu.serve\n"
            "import mpisppy_tpu.serve.router\n"
            "import mpisppy_tpu.serve.replica\n"
            "sys.exit(1 if 'jax' in sys.modules else 0)\n")
    r = subprocess.run([sys.executable, "-c", code],
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]


# -- shared jit registries ------------------------------------------------

def test_solver_jit_shared_across_instances():
    from mpisppy_tpu.ops.pdhg import PDHGSolver
    a = PDHGSolver(eps=1e-7)
    b = PDHGSolver(eps=1e-7)
    c = PDHGSolver(eps=1e-6)
    assert a._solve_jit is b._solve_jit
    assert a._solve_jit is not c._solve_jit
    assert a.config_key() == b.config_key() != c.config_key()


def test_superstep_shared_across_ph_instances():
    b = farmer.build_batch(3)
    ph1 = PH(dict(FAST_OPTS), ["s0", "s1", "s2"], batch=b)
    ph2 = PH(dict(FAST_OPTS), ["s0", "s1", "s2"],
             batch=farmer.build_batch(3))
    assert ph1._superstep is ph2._superstep
    assert ph1.solver._solve_jit is ph2.solver._solve_jit


# -- platform satellite ---------------------------------------------------

def test_enable_compile_cache_env_dir(tmp_path, monkeypatch):
    import jax

    from mpisppy_tpu.utils import platform
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
    old = jax.config.jax_compilation_cache_dir
    try:
        got = platform.enable_compile_cache()
        assert got == str(tmp_path / "cc")
        assert jax.config.jax_compilation_cache_dir == got
        # the serve AOT artifacts live under the same root
        assert cc.aot_cache_dir() == os.path.join(got, "aot")
    finally:
        jax.config.update("jax_compilation_cache_dir", old)


@pytest.mark.parametrize("value,want", [
    ("cpu", True), (" cpu ", True), ("tpu,cpu", False), ("tpu", False),
    ("", False)])
def test_cpu_requested_only_when_exactly_cpu(monkeypatch, value, want):
    from mpisppy_tpu.utils import platform
    monkeypatch.setenv("JAX_PLATFORMS", value)
    assert platform.cpu_requested() is want


def test_compile_cache_root_defaults_to_checkout(monkeypatch):
    from mpisppy_tpu.utils import platform
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert platform.compile_cache_root() == os.path.join(repo,
                                                         ".jax_cache")
    assert cc.aot_cache_dir() == os.path.join(repo, ".jax_cache", "aot")


def test_restart_delay_shared_policy():
    from mpisppy_tpu.resilience import restart_delay
    assert restart_delay(1, 0.5, 30.0) == 0.5
    assert restart_delay(3, 0.5, 30.0) == 2.0
    assert restart_delay(10, 0.5, 4.0) == 4.0


# -- compile cache --------------------------------------------------------

def test_bucket_key_separates_shapes_and_config():
    b3, b4 = farmer.build_batch(3), farmer.build_batch(4)
    k3 = cc.bucket_key(b3, FAST_OPTS)
    assert k3 == cc.bucket_key(farmer.build_batch(3), dict(FAST_OPTS))
    assert k3 != cc.bucket_key(b4, FAST_OPTS)
    assert k3 != cc.bucket_key(b3, dict(FAST_OPTS, pdhg_eps=1e-6))
    assert cc.bucket_key(b3, FAST_OPTS, model="farmer") \
        != cc.bucket_key(b3, FAST_OPTS, model="other")


def test_cache_counts_hits_and_misses():
    cache = cc.CompileCache()
    b3 = farmer.build_batch(3)
    e1 = cache.get(b3, FAST_OPTS)
    e2 = cache.get(farmer.build_batch(3), FAST_OPTS)
    assert e1 is e2
    cache.get(farmer.build_batch(4), FAST_OPTS)
    assert cache.stats() == {"hits": 1, "misses": 2, "buckets": 2,
                             "aot_loads": 0, "aot_load_failures": 0,
                             "aot_saves": 0, "aot_export_failures": 0,
                             "aot_prewarm_hits": 0}


# -- admission control (no dispatch thread needed) ------------------------

def test_admission_queue_full():
    svc = SolverService({"serve_max_queue": 1})
    b = farmer.build_batch(3)
    h1 = svc.submit(b, FAST_OPTS)
    h2 = svc.submit(b, FAST_OPTS)
    assert svc.poll(h1) == "queued"
    res = svc.result(h2, timeout=1)
    assert res["status"] == "rejected" and res["reason"] == "queue_full"


def test_admission_max_inflight():
    svc = SolverService({"serve_max_inflight": 1})
    b = farmer.build_batch(3)
    svc.submit(b, FAST_OPTS)
    res = svc.result(svc.submit(b, FAST_OPTS), timeout=1)
    assert res["status"] == "rejected"
    assert res["reason"] == "max_inflight"


def test_result_never_hangs_and_unknown_handle():
    from mpisppy_tpu.serve.request import RequestHandle
    svc = SolverService()   # worker never started
    h = svc.submit(farmer.build_batch(3), FAST_OPTS)
    t0 = time.monotonic()
    res = svc.result(h, timeout=0.2)
    assert time.monotonic() - t0 < 5.0
    assert res["status"] == "timeout" and res["where"] == "result_wait"
    assert svc.poll(RequestHandle(999)) == "unknown"
    assert svc.result(RequestHandle(999))["status"] == "unknown"


def test_shutdown_rejects_leftovers_and_later_submits():
    svc = SolverService()
    h = svc.submit(farmer.build_batch(3), FAST_OPTS)
    svc.shutdown(timeout=1)
    assert svc.result(h, timeout=1)["status"] == "rejected"
    res = svc.result(svc.submit(farmer.build_batch(3), FAST_OPTS),
                     timeout=1)
    assert res["status"] == "rejected" and res["reason"] == "shutdown"


# -- golden parity (acceptance) -------------------------------------------

def test_batch1_result_bitwise_equals_ph_main():
    """The api.py guarantee: a service solve at batch=1 runs the SAME
    process-shared compiled superstep as PH.ph_main — the result is
    bitwise identical, and matches the farmer goldens."""
    names = [f"scen{i}" for i in range(3)]
    ph = PH(dict(GOLDEN_OPTS), names, batch=farmer.build_batch(3))
    conv, eobj, trivial = ph.ph_main()

    svc = SolverService().start()
    try:
        res = svc.solve(farmer.build_batch(3), GOLDEN_OPTS,
                        scenario_names=names, model="farmer")
    finally:
        svc.shutdown()
    assert res["status"] == "ok"
    # bitwise: plain float equality, no tolerance
    assert res["conv"] == conv
    assert res["eobj"] == eobj
    assert res["trivial_bound"] == trivial
    assert np.array_equal(res["xbar"], np.asarray(ph.root_xbar()))
    # goldens (tests/test_ph_farmer.py values)
    assert abs(res["eobj"] - -108390.0) < 20
    assert abs(res["trivial_bound"] - -115405.55) < 5
    assert np.allclose(res["xbar"], [170.0, 80.0, 250.0], atol=0.5)


# -- concurrency + compile-cache acceptance -------------------------------

def test_eight_concurrent_requests_single_compile(fresh_telemetry):
    """8 concurrent same-bucket requests: exactly one compile-cache
    miss, >= 7 hits — asserted on the service cache AND the
    serve.compile_cache.* telemetry counters."""
    svc = SolverService({"serve_max_batch": 8, "serve_max_inflight": 32,
                         "telemetry": True})
    handles = []
    hs_lock = threading.Lock()

    def client(i):
        h = svc.submit(farmer.build_batch(3), FAST_OPTS, model="farmer")
        with hs_lock:
            handles.append(h)

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    svc.start()
    try:
        results = [svc.result(h, timeout=600) for h in handles]
    finally:
        svc.shutdown()
    assert [r["status"] for r in results] == ["ok"] * 8
    # same model data -> identical solutions
    assert len({r["eobj"] for r in results}) == 1
    st = svc.cache.stats()
    assert st["misses"] == 1
    assert st["hits"] >= 7
    counters = svc._tel.registry._counters
    assert counters["serve.compile_cache.miss"].value == 1
    assert counters["serve.compile_cache.hit"].value >= 7
    assert telemetry.serve_counters(svc._tel.registry)[
        "serve_requests_ok"] == 8


# -- coalescing edge cases ------------------------------------------------

def test_mixed_shape_buckets_interleaved():
    """Interleaved S=3 / S=4 requests: dispatch must coalesce only
    same-bucket neighbors (skipping the other bucket without starving
    it), and every request completes with its own model's answer."""
    svc = SolverService({"serve_max_batch": 4})
    reqs = []
    for i in range(2):
        reqs.append(("s3", svc.submit(farmer.build_batch(3), FAST_OPTS)))
        reqs.append(("s4", svc.submit(farmer.build_batch(4), FAST_OPTS)))
    svc.start()
    try:
        results = {(kind, h.id): svc.result(h, timeout=600)
                   for kind, h in reqs}
    finally:
        svc.shutdown()
    assert all(r["status"] == "ok" for r in results.values())
    eobj3 = {r["eobj"] for (k, _), r in results.items() if k == "s3"}
    eobj4 = {r["eobj"] for (k, _), r in results.items() if k == "s4"}
    assert len(eobj3) == 1 and len(eobj4) == 1
    assert eobj3 != eobj4      # genuinely different problems
    assert svc.cache.stats()["misses"] == 2   # one per bucket
    assert svc.cache.stats()["hits"] == 2


def test_deadline_expiry_mid_batch():
    """Two coalesced requests; one can never converge and carries a
    deadline — it must come back as a structured timeout at some
    iteration while its batchmate finishes OK."""
    svc = SolverService({"serve_max_batch": 4})
    ok_h = svc.submit(farmer.build_batch(3), FAST_OPTS)
    doomed_h = svc.submit(
        farmer.build_batch(3),
        dict(FAST_OPTS, PHIterLimit=10 ** 6, convthresh=0.0),
        deadline=3.0)
    svc.start()
    try:
        ok_res = svc.result(ok_h, timeout=600)
        doomed_res = svc.result(doomed_h, timeout=600)
    finally:
        svc.shutdown()
    assert ok_res["status"] == "ok"
    assert doomed_res["status"] == "timeout"
    assert doomed_res["where"] == "iteration"
    assert doomed_res["iterations"] >= 1


def test_deadline_expired_while_queued():
    svc = SolverService()
    h = svc.submit(farmer.build_batch(3), FAST_OPTS, deadline=0.05)
    time.sleep(0.2)
    svc.start()
    try:
        res = svc.result(h, timeout=60)
    finally:
        svc.shutdown()
    assert res["status"] == "timeout"
    assert res["where"] in ("queued", "dispatch")


# -- worker supervision (resilience integration) --------------------------

@pytest.mark.chaos
def test_worker_crash_restart_then_recover():
    """crash_at_iter counts dispatches: the first dispatch crashes, the
    supervisor requeues the in-flight request and restarts the worker,
    the second dispatch succeeds."""
    svc = SolverService({"chaos": {"crash_at_iter": 1},
                         "serve_max_attempts": 3,
                         "serve_max_restarts": 2,
                         "serve_restart_backoff": 0.05})
    h = svc.submit(farmer.build_batch(3), FAST_OPTS)
    svc.start()
    try:
        res = svc.result(h, timeout=600)
    finally:
        svc.shutdown()
    assert res["status"] == "ok"
    assert svc.restarts == 1


@pytest.mark.chaos
def test_worker_crash_budget_exhausted_fails_service():
    """crash_at_step crashes EVERY dispatch: once the restart budget is
    spent the service fails closed — queued requests get structured
    FAILED results and later submits are rejected."""
    svc = SolverService({"chaos": {"crash_at_step": 1},
                         "serve_max_attempts": 10,
                         "serve_max_restarts": 1,
                         "serve_restart_backoff": 0.05})
    h = svc.submit(farmer.build_batch(3), FAST_OPTS)
    svc.start()
    res = svc.result(h, timeout=60)
    assert res["status"] == "failed"
    assert svc._failed is not None
    late = svc.result(svc.submit(farmer.build_batch(3), FAST_OPTS),
                      timeout=5)
    assert late["status"] == "rejected"
    assert late["reason"] == "service_failed"


# -- graceful drain + warm restart (PR 10) --------------------------------

def test_drain_checkpoints_leftovers_and_closes_admission(tmp_path):
    svc = SolverService()                # worker never started: the
    h = svc.submit(farmer.build_batch(3), FAST_OPTS)  # request stays queued
    p = str(tmp_path / "drain")
    out = svc.drain(deadline=0.3, checkpoint_path=p)
    assert out["drained"] == 1
    assert out["checkpoint"] is not None and out["checkpoint"].endswith(".npz")
    # the leftover got a structured rejection, never a hang
    res = svc.result(h, timeout=1)
    assert res["status"] == "rejected" and res["reason"] == "drained"
    # the saved request round-trips with host-numpy leaves
    from mpisppy_tpu.resilience.checkpoint import load_drain_checkpoint
    saved = load_drain_checkpoint(p)
    assert len(saved) == 1 and saved[0]["options"] == FAST_OPTS
    assert isinstance(saved[0]["batch"].c, np.ndarray)


def test_submit_during_drain_rejects_with_draining():
    svc = SolverService()
    with svc._work:
        svc._draining = True             # admission closed mid-drain
    res = svc.result(svc.submit(farmer.build_batch(3), FAST_OPTS),
                     timeout=1)
    assert res["status"] == "rejected" and res["reason"] == "draining"


def test_drain_empty_service_is_a_noop():
    svc = SolverService()
    out = svc.drain(deadline=0.1, checkpoint_path=None)
    assert out == {"drained": 0, "checkpoint": None}


def test_warm_from_resubmits_and_solves(tmp_path):
    """Full drain -> restart cycle: service 1 drains a queued request
    to disk, a fresh service 2 warms from the file and actually solves
    it."""
    p = str(tmp_path / "drain_cycle")
    svc1 = SolverService()
    svc1.submit(farmer.build_batch(3), FAST_OPTS)
    out = svc1.drain(deadline=0.3, checkpoint_path=p)
    assert out["drained"] == 1

    svc2 = SolverService()
    try:
        handles = svc2.warm_from(p)
        assert [old_id for old_id, _ in handles] == [1]
        res = svc2.result(handles[0][1], timeout=120)
        assert res["status"] == "ok"
        assert np.isfinite(res["conv"])
    finally:
        svc2.shutdown()


# -- api error paths (module-global front door) ----------------------------

def _api_isolated():
    """Import serve.api and stash the process-global router so these
    tests can't leak state into (or inherit it from) other tests."""
    from mpisppy_tpu.serve import api
    return api


@pytest.fixture
def api_mod():
    api = _api_isolated()
    prev = api._router
    api._router = None
    yield api
    api.shutdown_service(timeout=30)
    api._router = prev


def test_api_result_unknown_handle(api_mod):
    """result()/poll() on a handle nobody minted: structured `unknown`,
    never an exception — both before the service exists and against a
    live router that has no such request id."""
    from mpisppy_tpu.serve.request import RouterHandle

    ghost = RouterHandle(id=10**9)
    # no service started at all
    assert api_mod.get_service() is None
    assert api_mod.poll(ghost) == "unknown"
    res = api_mod.result(ghost)
    assert res == {"status": "unknown", "request_id": ghost.id}
    # live router, unknown id: same contract (and still no exception)
    api_mod.start_service({"serve_replicas": 1})
    assert api_mod.poll(ghost) == "unknown"
    res = api_mod.result(ghost, timeout=0.1)
    assert res["status"] == "unknown" and res["request_id"] == ghost.id


def test_api_double_shutdown_is_noop(api_mod):
    """shutdown_service() twice: the second call finds no router and
    returns without error (idempotent teardown)."""
    api_mod.start_service({"serve_replicas": 1})
    assert api_mod.get_service() is not None
    api_mod.shutdown_service(timeout=30)
    assert api_mod.get_service() is None
    api_mod.shutdown_service(timeout=30)     # must not raise
    assert api_mod.get_service() is None


def test_api_start_after_shutdown_gets_fresh_router(api_mod):
    """start_service after shutdown_service builds a FRESH router —
    the old object is gone, and handles minted by the dead router are
    `unknown` to its replacement."""
    r1 = api_mod.start_service({"serve_replicas": 1})
    api_mod.shutdown_service(timeout=30)
    r2 = api_mod.start_service({"serve_replicas": 1})
    assert r2 is not r1
    assert api_mod.get_service() is r2
    # a handle from the dead incarnation means nothing to the new one
    from mpisppy_tpu.serve.request import RouterHandle
    stale = RouterHandle(id=1)
    assert api_mod.poll(stale) == "unknown"
    assert api_mod.result(stale)["status"] == "unknown"
