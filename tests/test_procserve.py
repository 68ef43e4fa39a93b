"""Process-replica fleet tests (serve/procpool.py + serve/procworker.py)
plus the satellites that ride with them: DRR cross-bucket dispatch
fairness, AOT prewarm/eviction lifecycle, and the pooled pipelined
wire client.

CPU-safe small process counts throughout (1-2 workers per test); every
worker inherits the conftest's 8-virtual-device XLA_FLAGS topology and
the parent's x64 flag, so batch=1 results stay bitwise-comparable to
an in-process `PH.ph_main` across the process boundary.
"""

import ast
import os
import pathlib
import signal
import socket
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
from mpisppy_tpu.serve.net import protocol as P
from mpisppy_tpu.serve.net.client import PooledClient
from mpisppy_tpu.serve.router import Router
from mpisppy_tpu.serve.service import SolverService

pytestmark = pytest.mark.procserve

REPO = pathlib.Path(__file__).resolve().parents[1]

GOLDEN_OPTS = {"defaultPHrho": 1.0, "PHIterLimit": 200,
               "convthresh": 1e-5, "pdhg_eps": 1e-7}
FAST_OPTS = {"defaultPHrho": 1.0, "PHIterLimit": 4, "convthresh": 1e-4,
             "pdhg_eps": 1e-7, "superstep_eps": 1e-5}
# convthresh=0 never converges early: a deterministic fixed-length run
# that stays in flight long enough to be killed mid-batch
LONG_OPTS = {"defaultPHrho": 1.0, "PHIterLimit": 60, "convthresh": 0.0,
             "pdhg_eps": 1e-7}


@pytest.fixture
def fresh_telemetry():
    prev = telemetry._active
    telemetry.reset()
    yield
    telemetry._active = prev


# -- import contract (CI/tooling satellite) -------------------------------

def _module_level_imports(path):
    mods = set()
    for node in ast.parse(path.read_text()).body:
        if isinstance(node, ast.Import):
            mods.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            mods.add(node.module or "")
    return mods


def test_procserve_modules_import_jax_only_lazily():
    """procworker.py (the worker entrypoint) and procpool.py (the
    parent fleet) must stay jax-lazy at module level: the worker pins
    JAX_ENABLE_X64 BEFORE jax loads, which only works if importing the
    module didn't already load it; the parent never needs jax at all to
    run a process fleet."""
    serve_dir = REPO / "mpisppy_tpu" / "serve"
    for fname in ("procworker.py", "procpool.py"):
        mods = _module_level_imports(serve_dir / fname)
        bad = {m for m in mods if m == "jax" or m.startswith("jax.")}
        assert not bad, f"{fname} imports jax at module level: {bad}"
        heavy = {m for m in mods if ".service" in m or ".compile_cache"
                 in m or m.endswith("phbase") or m.endswith("spopt")}
        assert not heavy, f"{fname} imports {heavy} at module level"


def test_procserve_import_is_jax_free_in_fresh_process():
    code = ("import sys\n"
            "import mpisppy_tpu.serve.procworker\n"
            "import mpisppy_tpu.serve.procpool\n"
            "sys.exit(1 if 'jax' in sys.modules else 0)\n")
    r = subprocess.run([sys.executable, "-c", code],
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]


# -- pooled pipelined client (serve/net/client.py satellite) ---------------

class _MiniServer:
    """A protocol-speaking loopback peer with fault knobs: `hold` the
    first connection's first N responses back until all N requests have
    arrived (proves the client pipelines), or `drop_first` — tear the
    first connection down after reading one request without answering
    (proves reconnect-with-resend)."""

    def __init__(self, hold=0, drop_first=False):
        self.hold = hold
        self.drop_first = drop_first
        self.accepted = 0
        self.sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self.sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self.sock.bind(("127.0.0.1", 0))
        self.sock.listen(8)
        self.port = self.sock.getsockname()[1]
        self._stopped = False
        threading.Thread(target=self._accept, daemon=True).start()

    def _accept(self):
        while not self._stopped:
            try:
                conn, _ = self.sock.accept()
            except OSError:
                return
            self.accepted += 1
            threading.Thread(target=self._serve,
                             args=(conn, self.accepted),
                             daemon=True).start()

    def _serve(self, conn, conn_no):
        held = self.hold if conn_no == 1 else 0
        batch = []
        try:
            while True:
                hdr, _payload = P.read_message(conn)
                if hdr is None:
                    return
                if self.drop_first and conn_no == 1:
                    return             # vanish without answering
                resp = {"kind": "response", "ok": True,
                        "verb": hdr.get("verb"), "error_code": None,
                        "result": {"echo": hdr.get("x")}}
                if "seq" in hdr:
                    resp["seq"] = hdr["seq"]
                if held > 0:
                    batch.append(resp)
                    if len(batch) >= held:
                        for r in batch:
                            conn.sendall(P.pack_message(r))
                        batch, held = [], 0
                    continue
                conn.sendall(P.pack_message(resp))
        except (P.ProtocolError, ConnectionError, OSError):
            pass
        finally:
            try:
                conn.close()
            except OSError:
                pass

    def close(self):
        self._stopped = True
        try:
            self.sock.close()
        except OSError:
            pass


def test_pooled_client_pipelines_on_one_connection():
    """Three concurrent calls through a pool of ONE connection, against
    a server that answers nothing until all three requests arrived: a
    request-response-lockstep client would deadlock here; the pipelined
    client has all three frames in flight at once."""
    srv = _MiniServer(hold=3)
    client = PooledClient("127.0.0.1", srv.port, pool_size=1,
                          request_timeout=20.0)
    results, errors = {}, []

    def call(i):
        try:
            resp, _ = client.call("health", x=i)
            results[i] = resp["result"]["echo"]
        except Exception as exc:       # pragma: no cover - diagnostics
            errors.append(repr(exc))

    try:
        threads = [threading.Thread(target=call, args=(i,))
                   for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        assert not errors, errors
        assert results == {0: 0, 1: 1, 2: 2}   # seq echo matched FIFO
        assert srv.accepted == 1               # one socket carried all
        assert client.reconnects == 0
    finally:
        client.close()
        srv.close()


def test_pooled_client_reconnects_and_resends(fresh_telemetry):
    """A peer that tears the connection down mid-request: the client
    redials and resends (idempotency keys upstream make that safe), and
    both the plain-int stats and the telemetry counters record it."""
    telemetry.configure(True)
    srv = _MiniServer(drop_first=True)
    client = PooledClient("127.0.0.1", srv.port, pool_size=1,
                          request_timeout=20.0, jitter_seed=7)
    try:
        resp, _ = client.call("health", x="again")
        assert resp["result"]["echo"] == "again"
        assert client.reconnects >= 1
        assert client.resends >= 1
        assert srv.accepted == 2
        counters = telemetry.gateway_counters()
        assert counters["client_reconnects"] >= 1
        assert counters["client_resends"] >= 1
    finally:
        client.close()
        srv.close()


def test_pooled_client_reaps_idle_connections():
    srv = _MiniServer()
    client = PooledClient("127.0.0.1", srv.port, pool_size=2,
                          idle_timeout=0.05, request_timeout=20.0)
    try:
        client.call("health", x=1)
        time.sleep(0.2)                # idle past the reap horizon
        client.call("health", x=2)
        assert client.idle_reaped == 1
        assert srv.accepted == 2       # second call dialed fresh
    finally:
        client.close()
        srv.close()


# -- DRR cross-bucket dispatch fairness (service satellite) ----------------

def test_drr_bucket_fairness_no_starvation():
    """A hot bucket streaming same-shape requests cannot starve an
    interleaved cold one: with queue [A x6, B x2] and max_batch=4 the
    DRR ring serves [4xA, 2xB, 2xA] — B jumps the queue head exactly
    once, counted in bucket_starvation and surfaced via health()."""
    svc = SolverService({"serve_max_batch": 4,
                         "serve_max_inflight": 16})
    ba = farmer.build_batch(3)
    bb = farmer.build_batch(4)         # different scenario count: new bucket
    for _ in range(6):
        svc.submit(ba, FAST_OPTS, model="farmer")
    for _ in range(2):
        svc.submit(bb, FAST_OPTS, model="farmer")

    groups = [svc._next_group() for _ in range(3)]
    sizes = [len(g) for g in groups]
    scens = [g[0].batch.num_scens for g in groups]
    assert sizes == [4, 2, 2]
    assert scens == [3, 4, 3]          # A, then B's turn, then A again
    assert svc.bucket_starvation == 1
    assert svc.health()["bucket_starvation"] == 1


# -- AOT artifact lifecycle (compile_cache satellite) ----------------------

def _fake_artifact(d, name, size, age_s):
    p = d / (name + cc._AOT_SUFFIX)
    p.write_bytes(b"x" * size)
    old = time.time() - age_s
    os.utime(p, (old, old))
    return p


def test_prune_aot_dir_by_age_and_size(tmp_path, fresh_telemetry):
    telemetry.configure(True)
    d = tmp_path / "aot"
    d.mkdir()
    _fake_artifact(d, "ancient", 100, age_s=1000)
    _fake_artifact(d, "old", 100, age_s=500)
    _fake_artifact(d, "young1", 100, age_s=50)
    _fake_artifact(d, "young2", 100, age_s=10)
    (d / "not_an_artifact.txt").write_bytes(b"ignore me")

    # age eviction: everything older than 200s goes
    assert cc.prune_aot_dir(max_age_s=200, directory=str(d)) == 2
    left = sorted(f.name for f in d.glob("*" + cc._AOT_SUFFIX))
    assert left == ["young1" + cc._AOT_SUFFIX,
                    "young2" + cc._AOT_SUFFIX]

    # size eviction: cap below the survivors' total drops oldest-first
    assert cc.prune_aot_dir(max_total_bytes=150, directory=str(d)) == 1
    left = [f.name for f in d.glob("*" + cc._AOT_SUFFIX)]
    assert left == ["young2" + cc._AOT_SUFFIX]

    # both limits None / empty dir: no-ops
    assert cc.prune_aot_dir(directory=str(d)) == 0
    assert cc.prune_aot_dir(max_age_s=1, directory=str(tmp_path / "no")) == 0
    assert (d / "not_an_artifact.txt").exists()
    counters = telemetry.gateway_counters()
    assert counters["cache_aot_evictions"] == 3


def _persist_one_artifact(tmp_path):
    """Trace + persist one real batched executable into tmp_path/aot
    (the test_net_gateway recipe)."""
    from mpisppy_tpu.serve.service import stack_superstep_args
    phs = []
    for _ in range(2):
        ph = PH(dict(FAST_OPTS), ["s0", "s1", "s2"],
                batch=farmer.build_batch(3))
        ph.Iter0()
        phs.append(ph)
    args = stack_superstep_args(phs)
    cache = cc.CompileCache()
    exe = cache.get(phs[0].batch, FAST_OPTS,
                    model="farmer").batched_superstep(args)
    assert cache.stats()["aot_saves"] == 1
    # NOTE: phs[0].batch, not a fresh build_batch(3) — PH pads the
    # batch to the device count, so a fresh unpadded batch is a
    # DIFFERENT bucket (and a different artifact fingerprint)
    return args, exe, phs[0].batch


def test_prewarm_loads_artifacts_and_serves_hits(tmp_path, monkeypatch):
    """prewarm() makes the full artifact set resident; a fresh cache's
    next build is served from the registry (counted as a prewarm hit
    AND a load) without touching the disk file."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    args, _, batch = _persist_one_artifact(tmp_path)
    (tmp_path / "aot" / ("junk" + cc._AOT_SUFFIX)).write_bytes(b"torn")
    cc.clear_prewarmed()
    try:
        assert cc.prewarm() == 1       # junk rejected, artifact resident
        cache = cc.CompileCache()
        exe = cache.get(batch, FAST_OPTS,
                        model="farmer").batched_superstep(args)
        s = cache.stats()
        assert s["aot_prewarm_hits"] == 1
        assert s["aot_loads"] == 1
        assert s["aot_saves"] == 0
        out = exe(*args)
        assert np.asarray(out.conv).shape[0] == 2
        # idempotent: a second sweep re-reads nothing new
        assert cc.prewarm() == 1
    finally:
        cc.clear_prewarmed()


# -- process-replica fleet (tentpole) --------------------------------------

def test_process_fleet_refused_when_router_holds_the_chip(monkeypatch):
    """A router process that already holds the accelerator leaves no
    chip for any worker: refused before anything spawns."""
    from mpisppy_tpu.serve import procpool
    monkeypatch.setattr(procpool, "_parent_backend", lambda: "tpu")
    rs = procpool.ProcReplicaSet({"serve_proc_workdir": None},
                                 n_replicas=1)
    with pytest.raises(RuntimeError, match="already holds it"):
        rs.start()
    assert all(r.proc is None for r in rs.replicas)


def test_process_fleet_refuses_second_worker_on_accelerator(monkeypatch):
    """Parent off jax: slot 0 boots alone, and when it reports an
    accelerator the set refuses the rest (libtpu: one process per
    host's chips) and shuts slot 0 down."""
    from mpisppy_tpu.serve import procpool
    monkeypatch.setattr(procpool, "_parent_backend", lambda: None)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    booted, stopped = [], []

    def fake_start(self):
        booted.append(self.slot)
        self.platform = "tpu"
        return self

    monkeypatch.setattr(procpool.ProcReplica, "start", fake_start)
    monkeypatch.setattr(procpool.ProcReplica, "shutdown",
                        lambda self, timeout=5.0: stopped.append(self.slot))
    rs = procpool.ProcReplicaSet({}, n_replicas=3)
    with pytest.raises(RuntimeError, match="lockfile"):
        rs.start()
    assert booted == [0] and sorted(stopped) == [0, 1, 2]


def _proc_router(n, **extra):
    o = {"serve_replicas": n, "serve_replica_mode": "process",
         "serve_max_batch": 4, "router_hedge_threshold": None,
         "router_drain_deadline": 0.5, "telemetry": True}
    o.update(extra)
    return Router(o)


def test_process_mode_batch1_bitwise_equals_ph_main():
    """The acceptance bar: a batch=1 solve through a PROCESS replica —
    config JSON out, batch npz over the wire, an independent jax
    runtime in the worker, result npz back — returns bit-for-bit what
    an in-process PH.ph_main produces."""
    names = ["s0", "s1", "s2"]
    ph = PH(dict(GOLDEN_OPTS), names, batch=farmer.build_batch(3))
    conv, eobj, trivial = ph.ph_main()

    router = _proc_router(1).start()
    try:
        res = router.solve(farmer.build_batch(3), GOLDEN_OPTS,
                           scenario_names=names, model="farmer",
                           timeout=300)
        assert res["status"] == "ok"
        assert res["conv"] == conv
        assert res["eobj"] == eobj
        assert res["trivial_bound"] == trivial
        assert np.array_equal(res["xbar"], np.asarray(ph.root_xbar()))
        st = router.stats()
        assert st["replica_mode"] == "process"
        assert len(st["proc_boot_seconds"]) == 1
    finally:
        router.shutdown(timeout=15)


def test_sigkill_mid_batch_breaker_replacement_and_bitwise_replay(
        tmp_path, monkeypatch):
    """The kill -9 fault path end to end: a worker is SIGKILLed while
    executing a batch; the router's probe sees the corpse (waitpid, not
    a socket timeout), trips the breaker, boots a warm replacement
    (prewarmed from the shared AOT dir), and replays the stranded
    request — whose result is bitwise-identical to PH.ph_main, and
    whose idempotent resubmit returns the same handle and result."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    _persist_one_artifact(tmp_path)    # replacement has something to prewarm
    cc.clear_prewarmed()

    names = ["s0", "s1", "s2"]
    ph = PH(dict(LONG_OPTS), names, batch=farmer.build_batch(3))
    conv, eobj, trivial = ph.ph_main()

    router = _proc_router(2).start()
    try:
        key = "sigkill-victim"
        h = router.submit(farmer.build_batch(3), LONG_OPTS,
                          scenario_names=names, model="farmer",
                          idempotency_key=key)
        rreq = router._requests[h.id]
        # wait until the request is RUNNING on its replica, then murder
        # that worker process outright
        victim = None
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            handles = list(rreq.handles)
            if handles:
                replica, inner = handles[0]
                if replica.poll(inner) == "running":
                    victim = replica
                    break
            time.sleep(0.01)
        assert victim is not None, "request never started running"
        os.kill(victim.pid, signal.SIGKILL)

        res = router.result(h, timeout=300)
        assert res["status"] == "ok"
        # bitwise parity survives the crash-and-replay path
        assert res["conv"] == conv
        assert res["eobj"] == eobj
        assert res["trivial_bound"] == trivial
        assert np.array_equal(res["xbar"], np.asarray(ph.root_xbar()))

        st = router.stats()
        assert st["counts"].get("breaker_opens", 0) >= 1
        assert router.replica_set.replacements >= 1
        fresh = router.replica_set[victim.slot]
        assert fresh.incarnation == victim.incarnation + 1
        assert fresh.prewarm_loaded >= 1   # replacement booted warm
        assert fresh.pid != victim.pid

        # idempotent resubmit: same key -> the ORIGINAL handle and the
        # exact same terminal result
        h2 = router.submit(farmer.build_batch(3), LONG_OPTS,
                           scenario_names=names, model="farmer",
                           idempotency_key=key)
        assert h2.id == h.id
        res2 = router.result(h2, timeout=60)
        assert res2["conv"] == res["conv"]
        assert res2["eobj"] == res["eobj"]
        assert np.array_equal(res2["xbar"], res["xbar"])
    finally:
        cc.clear_prewarmed()
        router.shutdown(timeout=15)


def test_roll_under_load_process_mode_zero_failures():
    """Rolling restart of the PROCESS fleet under live traffic: every
    slot is replaced exactly once, and no in-flight request fails —
    warm_from adoption, bare-handle replay, and idempotency keys keep
    exactly-once intact across worker process swaps."""
    router = _proc_router(2).start()
    stop = threading.Event()
    outcomes, errors = [], []
    lock = threading.Lock()

    def load(i):
        try:
            k = 0
            while not stop.is_set():
                res = router.solve(farmer.build_batch(3), FAST_OPTS,
                                   model="farmer",
                                   idempotency_key=f"roll{i}-{k}",
                                   timeout=300)
                with lock:
                    outcomes.append(res["status"])
                k += 1
        except Exception as exc:       # pragma: no cover - diagnostics
            with lock:
                errors.append(repr(exc))

    try:
        threads = [threading.Thread(target=load, args=(i,))
                   for i in range(2)]
        for t in threads:
            t.start()
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            with lock:
                if outcomes:
                    break
            time.sleep(0.05)
        rolled = router.roll()
        assert rolled == 2
        time.sleep(0.5)                # keep load flowing a beat
        stop.set()
        for t in threads:
            t.join(120)
        assert not errors, errors
        assert outcomes and all(s == "ok" for s in outcomes), \
            [s for s in outcomes if s != "ok"]
        assert [r.incarnation for r in router.replica_set] == [1, 1]
        assert router.counts.get("rolled_replicas") == 2
    finally:
        stop.set()
        router.shutdown(timeout=15)
