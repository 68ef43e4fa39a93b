"""Collective exchange fabric tests (mpmd/collective.py): the fused
all-gather/broadcast slabs behind the "collective" window backend.

Covers the Window-contract parity of CollectiveWindow (ids, checksums,
kill, chaos corruption, stale accounting), the lazy flush-on-read
commit discipline (N writes coalesce into ONE fused exchange), the
single-compile-per-geometry guarantee, bit-identical bound-trajectory
parity with the seqlock and device-mailbox backends, corrupt-window
accounting parity, and the reslice paths: fabric-level slab regrow and
the clean fallback onto device mailboxes when the regrow breaks.

Everything runs on the 8 virtual CPU devices conftest.py forces, so
the lane-sharded placements and the shard_map all-gather are real
multi-device programs, just over host memory.
"""

import ast
import os

import numpy as np
import pytest

import jax

from mpisppy_tpu import telemetry
from mpisppy_tpu.mpmd import MPMDWheel
from mpisppy_tpu.mpmd.collective import (
    HEADER_LANES, CollectiveFabric, CollectiveWindow,
    collective_window_pair)
from mpisppy_tpu.mpmd.exchange import DeviceWindow
from mpisppy_tpu.mpmd.slice_plan import slab_width
from mpisppy_tpu.spin_the_wheel import WheelSpinner

from test_mpmd_wheel import (S, RecordingHub, farmer_dicts,
                             fresh_telemetry)  # noqa: F401

pytestmark = pytest.mark.mpmd

PKG_ROOT = os.path.join(os.path.dirname(__file__), "..", "mpisppy_tpu")


def two_lane_fabric(hub_len=5, spoke_len=4, n_devices=2, **kw):
    """A sealed-geometry-ready fabric with 2 pairs on the first
    `n_devices` fleet devices: the smallest interesting lane mesh."""
    fab = CollectiveFabric(devices=jax.devices()[:n_devices], **kw)
    pairs = [fab.add_pair(hub_len, spoke_len, tag=f"p{j}")
             for j in range(2)]
    return fab, pairs


class TestSlabWidth:
    def test_rounds_to_multiple(self):
        assert slab_width([3, 7, 5]) == 7
        assert slab_width([3, 7, 5], multiple=6) == 12
        assert slab_width([], multiple=4) == 4   # degenerate: 1 lane min
        assert slab_width([1]) == 1


class TestCollectiveWindowContract:
    """CollectiveWindow must be indistinguishable from Window /
    DeviceWindow above the WindowPair seam."""

    def test_roundtrip_ids_and_prewrite_zeros(self, fresh_telemetry):
        fab, [(to_spoke, to_hub), _] = two_lane_fabric()
        # pre-first-write: zeros under id 0, and read_checked validates
        # (the header is initialized to the zero payload's checksum)
        data, wid = to_spoke.read()
        assert wid == 0 and np.array_equal(data, np.zeros(5))
        data, wid, ok, reason = to_hub.read_checked()
        assert wid == 0 and ok and reason is None
        assert to_spoke.write(np.arange(5.0)) == 1
        data, wid = to_spoke.read()
        assert wid == 1 and np.array_equal(data, np.arange(5.0))
        assert data.dtype == np.float64
        # explicit id (the regrow protocol re-posts under a chosen id)
        assert to_spoke.write(np.ones(5), write_id=7) == 7
        assert to_spoke.write_id == 7
        # lanes are independent mailboxes of the shared slab
        data, wid = to_hub.read()
        assert wid == 0 and np.array_equal(data, np.zeros(4))

    @pytest.mark.parametrize("backend", ["collective", "device"])
    def test_payload_exact_without_x64(self, backend, fresh_telemetry):
        """The TPU runs f32 (x64 off), where a float64 array put on a
        device narrows to float32: mailboxes move the payload's bit
        pattern instead, so values and CRC survive any backend."""
        vals = np.array([1.1, 2.2, 1e-300, -3.3e200, 5.5])
        with jax.enable_x64(False):
            if backend == "collective":
                fab, [(win, _), _] = two_lane_fabric()
            else:
                win = DeviceWindow(5, device=jax.devices()[0])
            win.write(vals)
            data, wid, ok, reason = win.read_checked()
        assert ok and reason is None and wid == 1
        assert data.dtype == np.float64 and np.array_equal(data, vals)

    def test_shape_mismatch(self, fresh_telemetry):
        _, [(to_spoke, _), _] = two_lane_fabric()
        with pytest.raises(ValueError, match="expects shape"):
            to_spoke.write(np.zeros(3))

    def test_kill_flushes_staged_payload(self, fresh_telemetry):
        """The seqlock contract: kill overwrites only the id — AND the
        staged generation still commits, so the reader's final pass
        sees the writer's final payload (the overlap-mode finalize
        regression: spokes must see the hub's last W's, not the last
        ones somebody happened to read before the kill)."""
        _, [(to_spoke, _), _] = two_lane_fabric()
        to_spoke.write(np.arange(5.0))
        _ = to_spoke.read()                       # commit gen 1
        to_spoke.write(np.arange(5.0) * 3)        # staged, never read
        to_spoke.send_kill()
        data, wid = to_spoke.read()
        assert wid == to_spoke.KILL
        np.testing.assert_array_equal(data, np.arange(5.0) * 3)
        # read_checked treats KILL like Window: ok, id exempt
        data, wid, ok, _ = to_spoke.read_checked()
        assert wid == to_spoke.KILL and ok

    def test_corrupt_write_detected_and_counted(self, fresh_telemetry):
        """Chaos corrupt_window parity: the perturbed payload ships
        under the TRUE checksum and only read_checked catches it."""
        fab, [(to_spoke, _), _] = two_lane_fabric()
        to_spoke.corrupt_next_write()
        to_spoke.write(np.arange(5.0))
        data, wid = to_spoke.read()               # plain read: fooled
        assert data[0] == 1.0 and wid == 1
        to_spoke.write(np.arange(5.0))
        to_spoke.corrupt_next_write()
        to_spoke.write(np.arange(5.0))
        data, wid, ok, reason = to_spoke.read_checked()
        assert not ok and "checksum mismatch" in reason
        c = telemetry.wheel_counters()
        assert c["wheel_stale_reads"] >= 1        # corrupt counts stale

    def test_stale_read_accounting(self, fresh_telemetry):
        fab, [(to_spoke, _), _] = two_lane_fabric()
        to_spoke.write(np.ones(5))
        to_spoke.read()
        to_spoke.read()                           # same id again: stale
        c = telemetry.wheel_counters()
        assert c["wheel_stale_reads"] == 1
        assert c["wheel_exchange_writes"] == 1

    def test_read_device_is_lane_slice(self, fresh_telemetry):
        _, [(to_spoke, _), (to_spoke2, _)] = two_lane_fabric()
        to_spoke.write(np.arange(5.0))
        to_spoke2.write(np.arange(5.0) + 10)
        dev, wid = to_spoke.read_device()
        assert isinstance(dev, jax.Array) and wid == 1
        np.testing.assert_array_equal(np.asarray(dev), np.arange(5.0))
        dev2, _ = to_spoke2.read_device()
        np.testing.assert_array_equal(np.asarray(dev2),
                                      np.arange(5.0) + 10)

    def test_more_lanes_than_devices_wrap(self, fresh_telemetry):
        """K lanes on fewer devices: the row count pads to a device
        multiple at exchange time and every lane still round-trips."""
        fab = CollectiveFabric(devices=jax.devices()[:2])
        pairs = [fab.add_pair(3, 3) for _ in range(3)]
        for j, (to_spoke, _) in enumerate(pairs):
            to_spoke.write(np.full(3, float(j)))
        for j, (to_spoke, _) in enumerate(pairs):
            data, wid = to_spoke.read()
            assert wid == 1
            np.testing.assert_array_equal(data, np.full(3, float(j)))

    def test_single_device_fabric(self, fresh_telemetry):
        fab = CollectiveFabric(devices=jax.devices()[:1])
        to_spoke, to_hub = fab.add_pair(2, 2)
        to_hub.write(np.array([1.0, 2.0]))
        data, wid = to_hub.read()
        assert wid == 1 and np.array_equal(data, [1.0, 2.0])


class TestFabricCommitDiscipline:
    def test_writes_coalesce_into_one_exchange(self, fresh_telemetry):
        """N staged writes across all lanes of a direction commit with
        ONE fused exchange at the first read — the whole point of the
        backend — and the byte counter reports slab bytes, not
        per-write bytes."""
        fab, pairs = two_lane_fabric(hub_len=5, spoke_len=4)
        for k in range(5):
            for to_spoke, to_hub in pairs:
                to_hub.write(np.full(4, float(k)))
        data, wid = pairs[0][1].read()            # triggers the flush
        assert wid == 5
        np.testing.assert_array_equal(data, np.full(4, 4.0))
        _ = pairs[1][1].read()                    # same generation: free
        c = telemetry.wheel_counters()
        assert c["wheel_collective_exchanges"] == 1
        assert c["wheel_exchange_writes"] == 10
        # 2 lanes x (3 header + v_pad) float64 — nothing per-write
        width = HEADER_LANES + slab_width([4, 4])
        assert c["wheel_exchange_bytes"] == 2 * width * 8
        assert c["wheel_exchange_latency_seconds"] > 0.0
        # a read with nothing newly staged exchanges nothing
        _ = pairs[0][1].read()
        assert telemetry.wheel_counters()[
            "wheel_collective_exchanges"] == 1

    def test_directions_commit_independently(self, fresh_telemetry):
        fab, [(to_spoke, to_hub), _] = two_lane_fabric()
        to_spoke.write(np.ones(5))
        to_hub.write(np.ones(4))
        to_spoke.read()
        assert telemetry.wheel_counters()[
            "wheel_collective_exchanges"] == 1    # down slab only
        to_hub.read()
        assert telemetry.wheel_counters()[
            "wheel_collective_exchanges"] == 2

    def test_sealed_after_first_write(self, fresh_telemetry):
        fab, pairs = two_lane_fabric()
        pairs[0][0].write(np.zeros(5))
        with pytest.raises(RuntimeError, match="sealed"):
            fab.add_pair(5, 4)

    def test_pair_factory_requires_fabric(self):
        with pytest.raises(RuntimeError, match="shared CollectiveFabric"):
            collective_window_pair(4, 4)

    def test_staged_payload_no_device_work(self, fresh_telemetry):
        fab, [(to_spoke, _), _] = two_lane_fabric()
        to_spoke.write(np.arange(5.0))
        data, wid = fab.staged_payload(to_spoke)
        assert wid == 1
        np.testing.assert_array_equal(data, np.arange(5.0))
        assert telemetry.wheel_counters()[
            "wheel_collective_exchanges"] == 0    # nothing exchanged

    def test_describe_json_safe(self, fresh_telemetry):
        import json
        fab, pairs = two_lane_fabric()
        pairs[0][1].write(np.ones(4))
        pairs[0][1].read()
        d = json.loads(json.dumps(fab.describe()))
        assert d["backend"] == "collective" and d["lanes"] == 2
        assert d["slab_bytes"]["to_hub"] > 0


class TestSingleCompile:
    def test_one_trace_per_geometry(self, fresh_telemetry):
        """The fused gather traces ONCE for a slab geometry no matter
        how many supersteps run — steady state never recompiles."""
        fab, pairs = two_lane_fabric()
        for k in range(8):
            for to_spoke, to_hub in pairs:
                to_hub.write(np.full(4, float(k)))
                to_spoke.write(np.full(5, float(k)))
            for to_spoke, to_hub in pairs:
                to_hub.read()
                to_spoke.read()
        assert fab._up.traces == 1                # one gather compile
        assert fab.trace_count == 2               # + the bcast placement
        assert telemetry.wheel_counters()[
            "wheel_collective_exchanges"] == 16


class TestRegrowAndFallback:
    def test_regrow_carries_payload_under_old_wid(self, fresh_telemetry):
        """Fabric-level reslice support: the hub->spoke slab regrows to
        the post-reslice width, every lane's last payload re-staged —
        truncated/zero-extended, CRC recomputed — under its OLD
        write_id, and the next read commits the new geometry with one
        exchange that still validates."""
        fab, pairs = two_lane_fabric(hub_len=6)
        pairs[0][0].write(np.arange(6.0), write_id=9)
        pairs[1][0].write(np.arange(6.0) * 2, write_id=4)
        pairs[0][0].read()
        fab.regrow_to_spoke(8)
        for (to_spoke, _), wid_want, base in ((pairs[0], 9, 1.0),
                                              (pairs[1], 4, 2.0)):
            assert to_spoke.length == 8
            data, wid, ok, reason = to_spoke.read_checked()
            assert wid == wid_want and ok, reason
            np.testing.assert_array_equal(
                data, np.r_[np.arange(6.0) * base, 0.0, 0.0])
        # shrink truncates
        fab.regrow_to_spoke(3)
        data, wid, ok, _ = pairs[1][0].read_checked()
        assert wid == 4 and ok
        np.testing.assert_array_equal(data, np.arange(3.0) * 2)

    def test_regrow_retraces_but_only_once(self, fresh_telemetry):
        fab, pairs = two_lane_fabric(hub_len=6)
        pairs[0][0].write(np.ones(6))
        pairs[0][0].read()
        before = fab.trace_count
        fab.regrow_to_spoke(9)
        pairs[0][0].read()
        pairs[1][0].read()
        pairs[0][0].write(np.ones(9))
        pairs[0][0].read()
        # bcast direction: geometry change costs no jit retrace (it is
        # a replicated placement), trace_count stays flat
        assert fab.trace_count == before

    @pytest.mark.chaos
    def test_device_loss_reslice_regrows_collective_slab(
            self, fresh_telemetry):
        """End-to-end regression for the regrow path: a chaos device
        loss prunes the Lagrangian slice, the reslice barrier grows the
        hub (pad 6 -> 7) and the surviving pair's hub->spoke lane is
        resized in place — still a CollectiveWindow — under its old
        write_id, and the wheel finishes with finite bounds."""
        hub_dict, spoke_dicts = farmer_dicts(
            spoke_chaos={"device_loss": 1})
        ws = MPMDWheel(hub_dict, spoke_dicts, lockstep=True)
        ws.spin()
        assert ws.exchange_backend_used == "collective"
        assert len(ws.supervisor.reslice_log) == 1
        new_S = ws.spcomm.opt.batch.num_scens
        assert new_S == 7
        K = ws.spcomm.opt.batch.num_nonants
        surviving = ws.supervisor.spokes[1].pair
        assert isinstance(surviving.to_spoke, CollectiveWindow)
        assert surviving.to_spoke.length == new_S * K
        assert np.isfinite(ws.BestInnerBound)
        assert np.isfinite(ws.BestOuterBound)

    @pytest.mark.chaos
    def test_regrow_failure_falls_back_to_device_mailboxes(
            self, fresh_telemetry, monkeypatch):
        """When the fabric-level regrow breaks, the surviving pairs
        swap cleanly onto DeviceWindow mailboxes (payloads re-posted
        under their old ids straight from the staging slab) and the
        wheel finishes on the per-pair backend."""
        monkeypatch.setattr(
            CollectiveFabric, "regrow_to_spoke",
            lambda self, n: (_ for _ in ()).throw(
                RuntimeError("injected regrow failure")))
        hub_dict, spoke_dicts = farmer_dicts(
            spoke_chaos={"device_loss": 1})
        ws = MPMDWheel(hub_dict, spoke_dicts, lockstep=True)
        ws.spin()
        assert ws.exchange_backend_used == "collective"
        assert len(ws.supervisor.reslice_log) == 1
        surviving = ws.supervisor.spokes[1].pair
        assert isinstance(surviving.to_spoke, DeviceWindow)
        assert isinstance(surviving.to_hub, DeviceWindow)
        new_S = ws.spcomm.opt.batch.num_scens
        K = ws.spcomm.opt.batch.num_nonants
        assert surviving.to_spoke.length == new_S * K
        assert np.isfinite(ws.BestInnerBound)
        assert np.isfinite(ws.BestOuterBound)
        reg = fresh_telemetry.registry
        assert reg._counters["wheel.collective_fallbacks"].value == 1


class TestExchangeParityCollective:
    def test_collective_vs_device_bound_trajectory(self):
        """The fused fabric is pure transport: the interleaved wheel's
        per-iteration bound trajectory on farmer must be BIT-IDENTICAL
        through device mailboxes and the collective slabs (same float64
        vectors, same deterministic inline schedule)."""
        traces = {}
        for backend in ("device", "collective"):
            hub_dict, spoke_dicts = farmer_dicts(hub_class=RecordingHub)
            ws = WheelSpinner(hub_dict, spoke_dicts, mode="interleaved",
                              exchange_backend=backend)
            ws.spin()
            assert ws.exchange_backend_used == backend
            traces[backend] = np.array(ws.spcomm.bound_trace)
        a, b = traces["device"], traces["collective"]
        assert a.shape == b.shape and len(a) > 0
        assert np.array_equal(a, b)
        assert np.isfinite(a[-1]).all()

    def test_mpmd_lockstep_backend_parity(self, fresh_telemetry):
        """Acceptance check at the MPMDWheel level: the disjoint-slice
        lockstep wheel produces bit-identical trajectories AND
        identical stale-read/write accounting on both on-device
        backends (the schedule is deterministic, so the accounting is
        too)."""
        runs = {}
        for backend in ("device", "collective"):
            telemetry.reset()
            telemetry.configure(True)
            hub_dict, spoke_dicts = farmer_dicts(
                hub_class=RecordingHub,
                opt_overrides={"telemetry": True},
                hub_opts={"window_backend": backend})
            ws = MPMDWheel(hub_dict, spoke_dicts, lockstep=True)
            ws.spin()
            assert ws.exchange_backend_used == backend
            runs[backend] = (np.array(ws.spcomm.bound_trace),
                             telemetry.wheel_counters())
        telemetry.reset()
        (ta, ca), (tb, cb) = runs["device"], runs["collective"]
        assert ta.shape == tb.shape and len(ta) > 0
        assert np.array_equal(ta, tb)
        assert ca["wheel_stale_reads"] == cb["wheel_stale_reads"]
        assert ca["wheel_exchange_writes"] == cb["wheel_exchange_writes"]
        # only the fused backend runs collectives
        assert ca["wheel_collective_exchanges"] == 0
        assert cb["wheel_collective_exchanges"] > 0

    @pytest.mark.chaos
    def test_corrupt_window_accounting_parity(self):
        """corrupt_window chaos through the slab header lane: the
        collective backend detects, counts and prunes EXACTLY like the
        device mailboxes — the integrity contract survives the fused
        transport bit-for-bit."""
        runs = {}
        for backend in ("device", "collective"):
            telemetry.reset()
            telemetry.configure(True)
            hub_dict, spoke_dicts = farmer_dicts(
                spoke_chaos={"corrupt_window": 1},
                opt_overrides={"PHIterLimit": 12, "telemetry": True},
                hub_opts={"max_corrupt_reads": 3,
                          "window_backend": backend})
            ws = MPMDWheel(hub_dict, spoke_dicts, lockstep=True)
            ws.spin()
            hub = ws.spcomm
            runs[backend] = (np.asarray(hub.corrupt_reads).copy(),
                             list(hub.failed_spokes),
                             telemetry.wheel_counters())
        telemetry.reset()
        (ra, fa, ca), (rb, fb, cb) = runs["device"], runs["collective"]
        np.testing.assert_array_equal(ra, rb)
        assert [n for n, _ in fa] == [n for n, _ in fb] \
            == ["LagrangianOuterBound"]
        assert "corrupt window reads" in fb[0][1]
        assert ca["wheel_corrupt_reads"] == cb["wheel_corrupt_reads"] >= 3
        assert ca["wheel_reslice_events"] == cb["wheel_reslice_events"]


class TestLayering:
    def test_cylinders_never_import_collective(self):
        """The satellite's sharper form of the mpmd layering guard:
        cylinders/ must not name mpmd.collective anywhere, even inside
        function bodies."""
        cyl_dir = os.path.join(PKG_ROOT, "cylinders")
        for fn in sorted(os.listdir(cyl_dir)):
            if not fn.endswith(".py"):
                continue
            tree = ast.parse(open(os.path.join(cyl_dir, fn)).read())
            for node in ast.walk(tree):
                mods = []
                if isinstance(node, ast.Import):
                    mods = [a.name for a in node.names]
                elif isinstance(node, ast.ImportFrom):
                    mods = [node.module or ""]
                for m in mods:
                    assert "collective" not in m.split("."), \
                        f"cylinders/{fn} imports mpmd.collective"

    def test_counters_stable_when_disabled(self):
        telemetry.reset()
        c = telemetry.wheel_counters()
        assert c["wheel_collective_exchanges"] == 0
        assert c["wheel_exchange_bytes"] == 0
