"""Network front-door tests (ISSUE 19): the serve/net wire protocol,
Gateway/Client over real loopback sockets, AOT executable persistence
(`<compile cache root>/aot`), and the zero-downtime rolling
restart — plus the package-hygiene and import-laziness guards.

All tests are tier-1 (`net` marker, no `slow`): farmer-sized batches,
and every service uses the SAME solver config so the process-shared
jit registries amortize compiles across tests (the test_serve.py
discipline)."""

import ast
import json
import os
import pathlib
import socket
import struct
import subprocess
import sys
import threading
import time
import zlib

import numpy as np
import pytest

from mpisppy_tpu import telemetry
from mpisppy_tpu.models import farmer
from mpisppy_tpu.opt.ph import PH
from mpisppy_tpu.serve import compile_cache as cc
from mpisppy_tpu.serve.net import Client, ClientError, Gateway
from mpisppy_tpu.serve.net import protocol as P

pytestmark = pytest.mark.net

REPO = pathlib.Path(__file__).resolve().parents[1]

GOLDEN_OPTS = {"defaultPHrho": 1.0, "PHIterLimit": 200,
               "convthresh": 1e-5, "pdhg_eps": 1e-7}
FAST_OPTS = {"defaultPHrho": 1.0, "PHIterLimit": 4, "convthresh": 1e-4,
             "pdhg_eps": 1e-7, "superstep_eps": 1e-5}

# quick-loop gateway/router config: tight ticks, singleton groups
# (bitwise path), fast supervision — the test_serve_router timings
GW_OPTS = {
    "serve_replicas": 1,
    "serve_max_batch": 1,
    "serve_restart_backoff": 0.01,
    "serve_restart_backoff_cap": 0.05,
    "router_tick": 0.01,
    "router_probe_interval": 0.02,
    "router_drain_deadline": 0.3,
}


@pytest.fixture
def fresh_telemetry():
    prev = telemetry._active
    telemetry.reset()
    yield
    telemetry._active = prev


def _gateway(extra=None, **kw):
    o = dict(GW_OPTS)
    o.update(extra or {})
    return Gateway(o, **kw).start()


def _sockpair():
    a, b = socket.socketpair()
    a.settimeout(5.0)
    b.settimeout(5.0)
    return a, b


# -- wire protocol ---------------------------------------------------------

def test_protocol_roundtrip_over_socketpair():
    a, b = _sockpair()
    try:
        payload = os.urandom(1 << 12)
        n = P.write_message(a, {"kind": "request", "verb": "health",
                                "token": "t"}, payload)
        sizes = []
        hdr, got = P.read_message(b, on_bytes=sizes.append)
        assert hdr["verb"] == "health" and hdr["proto"] == P.PROTO_FORMAT
        assert got == payload
        assert sizes == [n]            # exact byte accounting
    finally:
        a.close(); b.close()


def test_protocol_clean_eof_vs_torn_frame():
    a, b = _sockpair()
    a.close()
    assert P.read_message(b) == (None, None)      # clean EOF
    b.close()
    a, b = _sockpair()
    try:
        data = P.pack_message({"kind": "request", "verb": "poll"})
        a.sendall(data[: len(data) // 2])
        a.close()                                  # EOF mid-message
        with pytest.raises(P.ProtocolError):
            P.read_message(b)
    finally:
        b.close()


@pytest.mark.parametrize("mutate", ["magic", "crc", "header"])
def test_protocol_rejects_corruption(mutate):
    data = bytearray(P.pack_message(
        {"kind": "request", "verb": "poll"}, b"payload-bytes"))
    if mutate == "magic":
        data[0] ^= 0xFF
    elif mutate == "crc":
        data[-1] ^= 0xFF
    else:
        data[len(P.MAGIC) + 4] ^= 0xFF             # first header byte
    a, b = _sockpair()
    try:
        a.sendall(bytes(data)); a.close()
        with pytest.raises(P.ProtocolError):
            P.read_message(b)
    finally:
        b.close()


def test_protocol_payload_cap_enforced():
    a, b = _sockpair()
    try:
        a.sendall(P.pack_message({"kind": "request", "verb": "submit"},
                                 b"x" * 4096))
        a.close()
        with pytest.raises(P.ProtocolError, match="exceeds cap"):
            P.read_message(b, max_payload=1024)
    finally:
        b.close()


def test_batch_codec_preserves_arrays_and_treedef():
    """decode(encode(batch)) is bit-exact AND treedef-identical to the
    fresh batch — aux metadata (stage_of, name tuples) must come back
    in canonical Python form or every jit cache downstream of a wire
    batch breaks on treedef comparison (the stage_of regression)."""
    import jax

    b = farmer.build_batch(3)
    rt = P.decode_batch(P.encode_batch(b))
    assert jax.tree_util.tree_structure((b,)) \
        == jax.tree_util.tree_structure((rt,))
    for l1, l2 in zip(jax.tree_util.tree_leaves(b),
                      jax.tree_util.tree_leaves(rt)):
        assert np.array_equal(np.asarray(l1), np.asarray(l2))
    assert rt.tree.stage_of == b.tree.stage_of
    assert isinstance(rt.tree.stage_of, tuple)


def test_result_codec_is_bitwise():
    res = {"status": "ok", "conv": 1.2345678901234567e-7,
           "eobj": -108390.0703125, "iterations": 9,
           "xbar": np.array([170.0, 80.0, 250.0]),
           "reason": None}
    hdr, payload = P.encode_result(res)
    out = P.decode_result(json.loads(json.dumps(hdr)), payload)
    assert out["conv"] == res["conv"]              # bitwise via repr
    assert out["eobj"] == res["eobj"]
    assert np.array_equal(out["xbar"], res["xbar"])
    assert out["status"] == "ok" and out["reason"] is None


def test_decode_batch_never_unpickles_hostile_payload(tmp_path):
    """REVIEW fix (high): the wire codec must never unpickle
    network-supplied bytes.  A crafted object array whose __reduce__
    has a side effect is a decode ERROR (allow_pickle=False), and the
    side effect never fires — at the protocol layer AND through a live
    gateway (mapped to bad_payload, connection stays usable)."""
    import io as _io
    import os as _os
    marker = tmp_path / "pwned"

    class Evil:
        def __reduce__(self):
            return (_os.mkdir, (str(marker),))

    buf = _io.BytesIO()
    np.savez(buf, c=np.array([Evil()], dtype=object))
    hostile = buf.getvalue()
    with pytest.raises(Exception):
        P.decode_batch(hostile)
    assert not marker.exists(), "pickle executed during decode!"

    gw = _gateway()
    try:
        with socket.create_connection(gw.address, timeout=5) as s:
            s.settimeout(10.0)
            P.write_message(s, {"kind": "request", "verb": "submit",
                                "token": ""}, hostile)
            hdr, _ = P.read_message(s)
            assert hdr["ok"] is False
            assert hdr["error_code"] == P.E_BAD_PAYLOAD
            # decode failed structurally; nothing executed
            assert not marker.exists()
            # the frame itself was well-formed: stream stays usable
            P.write_message(s, {"kind": "request", "verb": "health",
                                "token": ""})
            hdr, _ = P.read_message(s)
            assert hdr["ok"] is True
    finally:
        gw.shutdown()


def test_batch_codec_output_is_pickle_free():
    """Every array in an encoded batch payload loads under
    allow_pickle=False — including farmer's model_meta, whose tuple of
    index arrays rides the tagged-JSON sidecar, not a pickle."""
    import io as _io
    data = P.encode_batch(farmer.build_batch(3))
    z = np.load(_io.BytesIO(data), allow_pickle=False)
    for k in z.files:
        np.asarray(z[k])               # raises if pickle were needed


def test_encode_result_refuses_object_arrays():
    with pytest.raises(TypeError, match="object-dtype"):
        P.encode_result({"status": "ok",
                         "bad": np.array([{"a": 1}], dtype=object)})


def test_error_code_matrix_covers_protocol_and_router():
    for code in (P.E_BAD_FRAME, P.E_BAD_VERB, P.E_UNAUTHORIZED,
                 P.E_UNKNOWN_HANDLE, P.E_DRAINING, "over_quota",
                 "brownout_shed", "quarantined", "timeout"):
        assert code in P.ERROR_CODES


# -- layering guards (AST + fresh interpreter + package hygiene) ----------

def test_net_imports_jax_only_lazily():
    """serve/net/ must be embeddable in a client process that never
    initializes a backend: no module-level jax/mpmd/heavy imports."""
    net_dir = REPO / "mpisppy_tpu" / "serve" / "net"
    for fname in sorted(net_dir.glob("*.py")):
        mods = set()
        for node in ast.parse(fname.read_text()).body:
            if isinstance(node, ast.Import):
                mods.update(a.name for a in node.names)
            elif isinstance(node, ast.ImportFrom):
                mods.add(node.module or "")
        bad = {m for m in mods if m == "jax" or m.startswith("jax.")
               or "mpmd" in m or ".service" in m
               or ".compile_cache" in m or m.endswith("phbase")}
        assert not bad, f"{fname.name} module-level imports: {bad}"


def test_net_import_is_jax_free_in_fresh_process():
    code = ("import sys\n"
            "import mpisppy_tpu.serve.net\n"
            "import mpisppy_tpu.serve.net.gateway\n"
            "import mpisppy_tpu.serve.net.client\n"
            "import mpisppy_tpu.serve.net.protocol\n"
            "sys.exit(1 if 'jax' in sys.modules else 0)\n")
    r = subprocess.run([sys.executable, "-c", code],
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]


def test_package_hygiene_no_orphan_modules():
    """Every mpisppy_tpu package directory has an __init__.py, and no
    __pycache__ holds a compiled module whose source .py is gone —
    orphaned .pyc files are shadow-importable and resurrect reverted
    code (the serve/net precedent this PR cleans up)."""
    root = REPO / "mpisppy_tpu"
    for d in sorted(p for p in root.rglob("*") if p.is_dir()):
        if d.name == "__pycache__":
            for pyc in d.glob("*.pyc"):
                src = d.parent / (pyc.name.split(".")[0] + ".py")
                assert src.exists(), (
                    f"orphaned compiled module {pyc} (no {src.name})")
        elif list(d.glob("*.py")):
            assert (d / "__init__.py").exists(), \
                f"package dir {d} lacks __init__.py"


# -- gateway: auth, error codes, counters ----------------------------------

def test_gateway_bearer_token_auth(fresh_telemetry):
    gw = _gateway({"gateway_tokens": {"sesame": "tenant-a"},
                   "telemetry": True})
    try:
        with Client(*gw.address, token="wrong") as c:
            with pytest.raises(ClientError) as exc:
                c.health()
            assert exc.value.code == P.E_UNAUTHORIZED
        with Client(*gw.address, token="sesame") as c:
            h = c.health()
            assert "counts" in h["gateway"]
        by_code = gw.counts["rejects_by_code"]
        assert by_code[P.E_UNAUTHORIZED] == 1
    finally:
        gw.shutdown()


def test_gateway_bad_verb_and_unknown_handle():
    gw = _gateway()
    try:
        with socket.create_connection(gw.address, timeout=5) as s:
            s.settimeout(5.0)
            P.write_message(s, {"kind": "request", "verb": "explode"})
            hdr, _ = P.read_message(s)
            assert hdr["ok"] is False
            assert hdr["error_code"] == P.E_BAD_VERB
        with Client(*gw.address) as c:
            from mpisppy_tpu.serve.net.client import NetHandle
            ghost = NetHandle(999999, "ghost")
            with pytest.raises(ClientError) as exc:
                c.poll(ghost)
            assert exc.value.code == P.E_UNKNOWN_HANDLE
            with pytest.raises(ClientError) as exc:
                c.result(ghost, timeout=1)
            assert exc.value.code == P.E_UNKNOWN_HANDLE
    finally:
        gw.shutdown()


def test_gateway_maps_router_reject_to_wire_code():
    """A structured router reject (over_quota via an empty token
    bucket) surfaces as the SAME code on the wire — one error-code
    namespace across both layers."""
    gw = _gateway({"router_tenant_rate": 0.001,
                   "router_tenant_burst": 1})
    try:
        with Client(*gw.address) as c:
            batch = farmer.build_batch(3)
            h1 = c.submit(batch, FAST_OPTS, model="farmer")
            h2 = c.submit(batch, FAST_OPTS, model="farmer")
            # bucket depth 1: the second submit is rejected at admission
            r2 = c.result(h2, timeout=10)
            assert r2["status"] == "rejected"
            assert r2["reason"] == "over_quota"
            r1 = c.result(h1, timeout=300)
            assert r1["status"] == "ok"
            assert "over_quota" in gw.counts["rejects_by_code"]
    finally:
        gw.shutdown()


def test_gateway_drain_rejects_new_admission():
    gw = _gateway()
    try:
        with Client(*gw.address) as c:
            out = c.drain(deadline=0.2)
            assert out["drained_open"] == 0
            with pytest.raises(ClientError) as exc:
                c.submit(farmer.build_batch(3), FAST_OPTS)
            assert exc.value.code == P.E_DRAINING
            # health keeps flowing while draining
            assert c.health()["gateway"]["draining"] is True
    finally:
        gw.shutdown()


def test_gateway_open_mode_requires_loopback():
    """REVIEW fix: open (unauthenticated) mode + a non-loopback bind
    would hand every LAN peer tenant "default" — refused at
    construction unless explicitly overridden or authenticated."""
    with pytest.raises(ValueError, match="non-loopback"):
        Gateway(dict(GW_OPTS), host="0.0.0.0")
    # authenticated, or explicitly overridden: constructible
    Gateway({**GW_OPTS, "gateway_tokens": {"t": "a"}}, host="0.0.0.0")
    Gateway({**GW_OPTS, "gateway_open_non_loopback": True},
            host="0.0.0.0")
    Gateway(dict(GW_OPTS), host="127.0.0.1")   # loopback: fine open


def test_gateway_admin_tokens_gate_drain_and_roll():
    """REVIEW fix: drain/roll are fleet-lifecycle verbs — a tenant
    bearer token must not drain admission or restart the fleet.  With
    gateway_admin_tokens set, only those tokens pass; a configured
    deployment WITHOUT an admin table refuses the verbs entirely."""
    gw = _gateway({"gateway_tokens": {"sesame": "tenant-a"},
                   "gateway_admin_tokens": ["root-tok"]})
    try:
        with Client(*gw.address, token="sesame") as c:
            for call in (lambda: c.drain(deadline=0.1),
                         lambda: c.roll(timeout=10)):
                with pytest.raises(ClientError) as exc:
                    call()
                assert exc.value.code == P.E_UNAUTHORIZED
        assert gw.counts.get("drains", 0) == 0
        assert gw.rolls == 0
        with Client(*gw.address, token="root-tok") as c:
            assert c.drain(deadline=0.1)["drained_open"] == 0
        assert gw.counts["drains"] == 1
    finally:
        gw.shutdown()
    # authenticated mode with NO admin table: no wire path to drain
    gw = _gateway({"gateway_tokens": {"sesame": "tenant-a"}})
    try:
        with Client(*gw.address, token="sesame") as c:
            with pytest.raises(ClientError) as exc:
                c.drain(deadline=0.1)
            assert exc.value.code == P.E_UNAUTHORIZED
    finally:
        gw.shutdown()


def test_gateway_bad_frame_counted_once_and_answered():
    """REVIEW fix: a torn frame is answered with ONE well-formed
    bad_frame error frame (packed, not a raw dict) and counted exactly
    once before the gateway closes the poisoned stream."""
    gw = _gateway()
    try:
        with socket.create_connection(gw.address, timeout=5) as s:
            s.settimeout(5.0)
            # exactly magic+len sized so the server consumes it all
            # (no unread bytes -> clean FIN, not RST, on close)
            s.sendall(b"GARBAGE!" + b"\x00" * 4)
            hdr, _ = P.read_message(s)
            assert hdr["ok"] is False
            assert hdr["error_code"] == P.E_BAD_FRAME
            assert P.read_message(s) == (None, None)   # then closed
        assert gw.counts["rejects_by_code"][P.E_BAD_FRAME] == 1
    finally:
        gw.shutdown()


def test_gateway_conn_threads_pruned():
    """REVIEW fix: finished connection handlers are pruned from the
    tracking list, so the gateway doesn't grow one Thread object per
    connection ever accepted."""
    gw = _gateway()
    try:
        for _ in range(5):
            with Client(*gw.address) as c:
                c.health()
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            with Client(*gw.address) as c:
                c.health()
            with gw._lock:
                n = len(gw._conn_threads)
            if n <= 2:
                break
            time.sleep(0.05)
        assert n <= 2, f"{n} connection threads still tracked"
    finally:
        gw.shutdown()


def test_gateway_counters_stable_keys(fresh_telemetry):
    """telemetry.gateway_counters() mirrors router_counters(): stable
    keys with telemetry off (zeros) and real values with it on."""
    cold = telemetry.gateway_counters()
    expected = {"gateway_requests", "gateway_bytes_in",
                "gateway_bytes_out", "gateway_rolls", "gateway_drains",
                "cache_aot_loads", "cache_aot_load_failures",
                "cache_aot_saves", "cache_aot_export_failures",
                "cache_aot_prewarm_hits", "cache_aot_evictions",
                "client_reconnects", "client_resends",
                "client_idle_reaped",
                "gateway_active_connections", "gateway_rejects_by_code"}
    assert set(cold) == expected
    assert all(v == 0 for k, v in cold.items()
               if k != "gateway_rejects_by_code")
    assert cold["gateway_rejects_by_code"] == {}

    telemetry.reset()
    gw = _gateway({"telemetry": True,
                   "gateway_tokens": {"good": "t"}})
    try:
        with Client(*gw.address, token="good") as c:
            c.health()
        with Client(*gw.address, token="bad") as c:
            with pytest.raises(ClientError):
                c.health()
        hot = telemetry.gateway_counters()
        assert hot["gateway_requests"] == 2
        assert hot["gateway_bytes_in"] > 0
        assert hot["gateway_bytes_out"] > 0
        assert hot["gateway_rejects_by_code"] == {P.E_UNAUTHORIZED: 1}
        assert set(hot) == expected
    finally:
        gw.shutdown()


def test_client_reconnects_with_capped_jitter_backoff():
    """Kill the connection under the client: the next request
    reconnects (counted) and succeeds; a dead gateway exhausts the
    reconnect budget with ConnectionError, in bounded time."""
    gw = _gateway()
    try:
        c = Client(*gw.address, reconnect_backoff=0.01,
                   reconnect_cap=0.05, max_reconnects=3)
        assert "counts" in c.health()["gateway"]
        c._sock.close()                 # torn transport under the hood
        assert "counts" in c.health()["gateway"]
        assert c.reconnects >= 1
        c.close()
    finally:
        gw.shutdown()
    t0 = time.monotonic()
    dead = Client(*gw.address, connect_timeout=0.2,
                  reconnect_backoff=0.01, reconnect_cap=0.05,
                  max_reconnects=2)
    with pytest.raises(ConnectionError):
        dead.health()
    assert time.monotonic() - t0 < 30.0


@pytest.mark.chaos
def test_client_timeout_none_survives_slow_solve():
    """REVIEW fix: with timeout=None the SERVER decides when to answer
    (up to gateway_result_cap), so the client stretches its socket
    wait to result_cap + grace.  A solve slower than request_timeout
    must complete on the ORIGINAL connection — not trip
    socket.timeout, tear the stream, and burn the reconnect budget on
    a healthy request (stranding gateway threads on dead sockets)."""
    gw = _gateway({"chaos": {"slow_replica": 1.0}})
    try:
        with Client(*gw.address, request_timeout=0.3, result_cap=60.0,
                    max_reconnects=2) as c:
            t0 = time.monotonic()
            res = c.solve(farmer.build_batch(3), FAST_OPTS,
                          model="farmer")          # timeout=None
            assert res["status"] == "ok"
            assert time.monotonic() - t0 > 0.3     # outlived the old cap
            assert c.reconnects == 0, \
                "slow solve misread as transport failure"
    finally:
        gw.shutdown()


# -- e2e over a real socket ------------------------------------------------

def test_client_solve_bitwise_equals_ph_main():
    """ISSUE 19 acceptance: a Client.solve batch=1 result over a real
    socket is bitwise-equal to PH.ph_main on farmer — npz arrays are
    lossless and JSON doubles round-trip via shortest repr, so the
    wire adds NOTHING to the serve parity guarantee."""
    names = [f"scen{i}" for i in range(3)]
    ph = PH(dict(GOLDEN_OPTS), names, batch=farmer.build_batch(3))
    conv, eobj, trivial = ph.ph_main()

    gw = _gateway()
    try:
        with Client(*gw.address) as c:
            res = c.solve(farmer.build_batch(3), GOLDEN_OPTS,
                          scenario_names=names, model="farmer",
                          timeout=300)
        assert res["status"] == "ok"
        assert res["conv"] == conv
        assert res["eobj"] == eobj
        assert res["trivial_bound"] == trivial
        assert np.array_equal(res["xbar"], np.asarray(ph.root_xbar()))
        # goldens (tests/test_ph_farmer.py values)
        assert abs(res["eobj"] - (-108390)) < 5
        assert np.allclose(res["xbar"], [170.0, 80.0, 250.0], atol=1.0)
    finally:
        gw.shutdown()


@pytest.mark.chaos
def test_eight_concurrent_clients_chaos_exactly_once():
    """ISSUE 19 acceptance: 8 concurrent socket clients against a
    2-replica set with replica_crash + slow_replica + poison_request
    armed.  Every request resolves exactly once per idempotency key
    (a duplicate submit returns the SAME handle id), the poison
    request quarantines without collateral, p99 stays finite."""
    names = [f"scen{i}" for i in range(3)]
    ph = PH(dict(FAST_OPTS), names, batch=farmer.build_batch(3))
    g_conv, g_eobj, g_trivial = ph.ph_main()

    gw = _gateway({
        "serve_replicas": 2,
        "router_hedge_threshold": 1.0,
        "router_breaker_backoff": 0.05,
        "router_breaker_backoff_cap": 0.5,
        "chaos": {"replica_crash": 1, "slow_replica": 0.02,
                  "poison_request": True, "chaos_replica": 0},
    })
    results, errors = {}, []
    lock = threading.Lock()

    def one_client(i):
        try:
            opts = dict(FAST_OPTS)
            if i == 3:
                opts["chaos_poison"] = True
            with Client(*gw.address, jitter_seed=i) as c:
                res = c.solve(farmer.build_batch(3), opts,
                              scenario_names=names, model="farmer",
                              idempotency_key=f"key{i}", timeout=300)
                # duplicate submit with the SAME key: the router's
                # idempotency table returns the original handle
                dup = c.submit(farmer.build_batch(3), opts,
                               scenario_names=names, model="farmer",
                               idempotency_key=f"key{i}")
                with lock:
                    results[i] = (res, dup.id)
        except Exception as exc:       # pragma: no cover - diagnostics
            with lock:
                errors.append((i, repr(exc)))

    try:
        threads = [threading.Thread(target=one_client, args=(i,))
                   for i in range(8)]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        for t in threads:
            t.join(300)
        wall = time.monotonic() - t0
        assert not errors, errors
        assert len(results) == 8

        router = gw.router
        # exactly-once: 8 keys, 8 rids (dup submits resolved to the
        # SAME rid — nothing ran twice to completion)
        assert len(router._idempotency) == 8
        rids = {router._idempotency[f"key{i}"] for i in range(8)}
        assert len(rids) == 8
        for i, (res, dup_rid) in results.items():
            assert dup_rid == router._idempotency[f"key{i}"]
            if i == 3:
                assert res["status"] == "failed"
                assert "quarantined" in res["reason"]
            else:
                assert res["status"] == "ok", (i, res)
                assert res["conv"] == g_conv
                assert res["eobj"] == g_eobj
                assert res["trivial_bound"] == g_trivial

        # finite p99 under chaos; crash pruned only the targeted slot
        st = router.stats()
        assert st["p99"] is not None and np.isfinite(st["p99"])
        assert wall < 280.0
        assert st["counts"].get("quarantined", 0) == 1
        assert st["replica_restarts"] >= 1
    finally:
        gw.shutdown()


@pytest.mark.chaos
def test_roll_under_load_zero_failed_inflight(fresh_telemetry):
    """ISSUE 19 acceptance: Gateway.roll() under sustained client load
    replaces EVERY replica (each slot's incarnation advances) with
    zero failed in-flight requests, leaving a gateway.rolls counter
    and a per-slot roll_slot event trail."""
    telemetry.reset()
    gw = _gateway({"serve_replicas": 2, "telemetry": True})
    stop = threading.Event()
    outcomes, errors = [], []
    lock = threading.Lock()

    def load(i):
        try:
            with Client(*gw.address, jitter_seed=i) as c:
                k = 0
                while not stop.is_set():
                    res = c.solve(farmer.build_batch(3), FAST_OPTS,
                                  model="farmer",
                                  idempotency_key=f"load{i}-{k}",
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
        # wait for traffic, then roll through both replicas
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            with lock:
                if outcomes:
                    break
            time.sleep(0.05)
        with Client(*gw.address) as c:
            rolled = c.roll(timeout=120)
        assert rolled == 2
        # keep load flowing a beat after the roll, then stop
        time.sleep(0.5)
        stop.set()
        for t in threads:
            t.join(120)
        assert not errors, errors
        assert outcomes and all(s == "ok" for s in outcomes), \
            [s for s in outcomes if s != "ok"]
        # every slot was replaced exactly once
        for slot in range(2):
            assert gw.router.replica_set[slot].incarnation == 1
        assert gw.rolls == 1
        assert gw.counts["rolls"] == 1
        assert telemetry.gateway_counters()["gateway_rolls"] == 1
        # the per-slot event trail
        ev = telemetry.get().registry.events("gateway.roll_slot")
        assert [e["slot"] for e in ev] == [0, 1]
        assert gw.router.counts.get("rolled_replicas") == 2
    finally:
        stop.set()
        gw.shutdown()


# -- AOT executable persistence --------------------------------------------

def _two_iter0_phs():
    phs = []
    for _ in range(2):
        ph = PH(dict(FAST_OPTS), ["s0", "s1", "s2"],
                batch=farmer.build_batch(3))
        ph.Iter0()
        phs.append(ph)
    return phs


def _run(exe, args):
    import jax
    out = exe(*args)
    jax.block_until_ready(out.conv)
    return out


def _leaves_equal(a, b):
    import jax
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(la, lb))


def test_aot_persistence_warm_start_skips_trace(tmp_path, monkeypatch):
    """The tentpole measurement: first build traces + persists; a
    FRESH cache (a fresh replica / process restart stand-in) loads the
    artifact instead of re-tracing — counted, strictly faster, and
    bitwise identical."""
    from mpisppy_tpu.serve.service import stack_superstep_args

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    phs = _two_iter0_phs()
    args = stack_superstep_args(phs)

    cache1 = cc.CompileCache()
    t0 = time.monotonic()
    exe1 = cache1.get(phs[0].batch, FAST_OPTS,
                      model="farmer").batched_superstep(args)
    out1 = _run(exe1, args)
    trace_s = time.monotonic() - t0
    s1 = cache1.stats()
    assert s1["aot_saves"] == 1 and s1["aot_loads"] == 0
    files = list((tmp_path / "aot").glob("*" + cc._AOT_SUFFIX))
    assert len(files) == 1

    cache2 = cc.CompileCache()
    t0 = time.monotonic()
    exe2 = cache2.get(phs[0].batch, FAST_OPTS,
                      model="farmer").batched_superstep(args)
    out2 = _run(exe2, args)
    warm_s = time.monotonic() - t0
    s2 = cache2.stats()
    assert s2["aot_loads"] >= 1 and s2["aot_load_failures"] == 0
    assert s2["aot_saves"] == 0        # nothing re-persisted
    assert warm_s < trace_s            # cold start strictly below trace
    assert _leaves_equal(out1, out2)   # warm == traced, bitwise


def test_aot_corrupt_entry_falls_back_to_trace(tmp_path, monkeypatch):
    from mpisppy_tpu.serve.service import stack_superstep_args

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    phs = _two_iter0_phs()
    args = stack_superstep_args(phs)
    out1 = _run(cc.CompileCache().get(
        phs[0].batch, FAST_OPTS, model="farmer"
    ).batched_superstep(args), args)

    f = next((tmp_path / "aot").glob("*" + cc._AOT_SUFFIX))
    raw = bytearray(f.read_bytes())
    raw[len(raw) // 2] ^= 0xFF
    f.write_bytes(bytes(raw))

    cache = cc.CompileCache()
    out2 = _run(cache.get(phs[0].batch, FAST_OPTS,
                          model="farmer").batched_superstep(args), args)
    s = cache.stats()
    assert s["aot_load_failures"] == 1 and s["aot_loads"] == 0
    assert s["aot_saves"] == 1         # re-persisted a good artifact
    assert _leaves_equal(out1, out2)   # fallback result identical


def test_aot_fingerprint_mismatch_falls_back(tmp_path, monkeypatch):
    """A VALID file under the WRONG fingerprint (version/backend skew
    stand-in: the header fingerprint disagrees with the computed one)
    is rejected before deserialization — silent fallback, counted."""
    from mpisppy_tpu.serve.service import stack_superstep_args

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    phs = _two_iter0_phs()
    args = stack_superstep_args(phs)
    out1 = _run(cc.CompileCache().get(
        phs[0].batch, FAST_OPTS, model="farmer"
    ).batched_superstep(args), args)

    # rewrite the artifact with a foreign fingerprint in its header
    # (payload intact and CRC-consistent — ONLY the identity is wrong)
    f = next((tmp_path / "aot").glob("*" + cc._AOT_SUFFIX))
    payload = cc._aot_decode(f.read_bytes(),
                             f.name[: -len(cc._AOT_SUFFIX)])
    f.write_bytes(cc._aot_encode("0" * 64, 2, payload))

    cache = cc.CompileCache()
    out2 = _run(cache.get(phs[0].batch, FAST_OPTS,
                          model="farmer").batched_superstep(args), args)
    s = cache.stats()
    assert s["aot_load_failures"] == 1 and s["aot_loads"] == 0
    assert _leaves_equal(out1, out2)


def test_aot_dir_follows_compile_cache_root(tmp_path, monkeypatch):
    """The artifacts land under the one compile-cache root: jax's own
    JAX_COMPILATION_CACHE_DIR, never a second serve-specific path."""
    root = tmp_path / "jaxcache"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(root))
    assert cc.aot_cache_dir() == str(root / "aot")
    from mpisppy_tpu.serve.service import stack_superstep_args
    phs = _two_iter0_phs()
    args = stack_superstep_args(phs)
    cache = cc.CompileCache()
    _run(cache.get(phs[0].batch, FAST_OPTS,
                   model="farmer").batched_superstep(args), args)
    assert cache.stats()["aot_saves"] == 1
    assert len(list((root / "aot").glob("*" + cc._AOT_SUFFIX))) == 1