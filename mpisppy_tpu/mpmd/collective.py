"""CollectiveExchange — one fused collective per superstep instead of
K per-pair mailbox hops.

The device-mailbox backend (exchange.DeviceWindow) moves every
hub<->spoke vector through its own `device_put`: K transfers plus K
blocking syncs per superstep.  Here ALL cylinder outbound vectors pack
into two pre-allocated `(K_pad, H + V_pad)` slabs — one per direction —
laid out over a `cyl` lane axis of a parallel.mesh.ScenarioMesh (one
lane row per hub<->spoke pair), and each superstep moves each slab with
ONE fused device program:

  * spokes->hub: the staged slab is placed lane-sharded (each lane's
    rows land on that spoke's device) and a single jitted
    `shard_map(all_gather)` over the `cyl` axis replicates the full
    slab everywhere — `mesh.fused_cyl_all_gather`, with the staged
    input buffer donated so XLA reuses it in place of a fresh
    allocation (the exchange itself never round-trips through the
    host);
  * hub->spokes: one replicated placement of the staged slab — the
    broadcast — through the `parallel.distributed.lane_transport` seam
    (plain device_put single-process; per-process shard materialization
    once a multihost PR wires DCN lanes in).

Slab layout (header lane).  Row j of a slab is lane j's mailbox:

    [ write_id | crc32 | payload_len | payload ... zero pad to V_pad ]

The three header columns carry the seqlock metadata IN the slab, so
PR 10's `read_checked` integrity contract — monotone write-id, CRC32
over the float64 payload bytes, corrupt-read prune budget — survives
the fused transport bit-for-bit: a reader recomputes the CRC on the
payload it sliced out of the gathered slab and validates it against
the header, exactly as it would against a DeviceWindow's stamped
checksum.  (Write-ids and CRC32 values are exact in float64: both are
< 2**53.)

Commit discipline (lazy flush-on-read): `write()` only stages into the
host slab under a lock and bumps the slab's staged generation — cheap,
and safe from any controller thread.  The FIRST read that observes a
staged generation beyond the committed one triggers the one fused
exchange for the whole direction; every other read in that generation
is a local slice of the committed replicated slab.  Double buffering
falls out of immutability: the previously committed device slab stays
readable while the next exchange builds its successor, and the
reference swaps under the slab lock only after the new slab is
resident.  A fabric-level exchange lock serializes the two directions'
device programs — two multi-device collectives must never be in
flight concurrently from different threads (the XLA rendezvous
deadlock the SolverService backend lock exists for).

Latency accounting: the measured region is `block_until_ready` on the
exchange's output slab ONLY — staging, placement dispatch and the
post-exchange host materialization all happen outside the timed
window, so `wheel.exchange_seconds` reports the collective itself, not
hidden host syncs.

Kill/termination polls (`write_id`, `got_kill_signal`) read a host-side
mirror and never touch the device — same rule as DeviceWindow.

jax stays import-lazy here (AST-guarded by tests): importing
mpisppy_tpu.mpmd to register the backend must not initialize the
accelerator runtime.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from .. import telemetry as _telemetry
from ..resilience.bounds import PayloadGuard, payload_checksum
from .exchange import device_f64, from_words, to_words
from .slice_plan import slab_width

HEADER_LANES = 3                   # [write_id, crc32, payload_len]
_H_WID, _H_CRC, _H_LEN = 0, 1, 2

KILL = -1


class _Slab:
    """One direction's slab: host staging buffer + committed device /
    host snapshots + generation counters.  `kind` picks the device
    program: "gather" (spokes->hub all-gather) or "bcast" (hub->spokes
    replicated placement)."""

    def __init__(self, fabric, name, kind):
        self.fabric = fabric
        self.name = name
        self.kind = kind
        self.lens = []             # payload length per lane
        self.windows = []          # CollectiveWindow per lane
        self.lock = threading.Lock()
        self.stage = None          # (K_pad, HEADER_LANES + v_pad) host
        self.v_pad = 0
        self.wid = []              # host write_id mirror per lane
        self.staged_gen = 0
        self.committed_gen = 0
        self.dev = None            # committed device slab (replicated)
        self.host = None           # committed host copy of `dev`
        self.traces = 0            # device-program trace count

    # -- geometry ---------------------------------------------------------
    def alloc(self):
        """Build the staging buffer for the current lane lengths
        (called under the slab lock at the first write; the row count
        is padded to a lane-device multiple at exchange time).  Headers
        are initialized to the pre-first-write contract (id 0, CRC of
        the zero payload), so a read before any write validates exactly
        like a fresh Window."""
        self.v_pad = slab_width(self.lens, self.fabric.pad_to)
        stage = np.zeros((len(self.lens), HEADER_LANES + self.v_pad))
        for lane, n in enumerate(self.lens):
            stage[lane, _H_CRC] = payload_checksum(np.zeros(n))
            stage[lane, _H_LEN] = n
        self.stage = stage

    @property
    def nbytes(self):
        return 0 if self.stage is None else int(self.stage.nbytes)


class CollectiveWindow:
    """Drop-in for cylinders.spcommunicator.Window backed by one lane
    row of a CollectiveFabric slab.  The full Window surface — write /
    read / read_checked / read_device / write_id / send_kill /
    corrupt_next_write / close — with the seqlock's id semantics, so
    nothing above the WindowPair seam can tell the backends apart."""

    KILL = KILL

    def __init__(self, fabric, slab, lane, length, tag=None):
        self.fabric = fabric
        self.lane = int(lane)
        self.length = int(length)
        self.tag = tag
        self._slab = slab
        self._last_read_wid = 0
        self._corrupt_next = False
        self._pguard = PayloadGuard()

    @property
    def write_id(self):
        with self._slab.lock:
            return self._slab.wid[self.lane]

    def write(self, values, write_id=None):
        """Stage `values` under the next (or given) write_id.  No
        device traffic here — the fused exchange runs at the first
        read of this staged generation (module docstring)."""
        values = np.asarray(values, dtype=np.float64)
        if values.shape != (self.length,):
            raise ValueError(
                f"window expects shape ({self.length},), "
                f"got {values.shape}")
        chk = payload_checksum(values)
        if self._corrupt_next:
            # chaos corrupt_window: ship a perturbed payload under the
            # checksum of the true values (read_checked must catch it)
            self._corrupt_next = False
            values = values.copy()
            values[0] += 1.0
        slab = self._slab
        with slab.lock:
            if slab.stage is None:
                slab.alloc()
            new_id = (slab.wid[self.lane] + 1 if write_id is None
                      else int(write_id))
            row = slab.stage[self.lane]
            row[HEADER_LANES:HEADER_LANES + self.length] = values
            row[_H_WID] = new_id
            row[_H_CRC] = chk
            row[_H_LEN] = self.length
            slab.wid[self.lane] = new_id
            slab.staged_gen += 1
        self.fabric._c_writes.inc()
        return new_id

    def _snapshot(self):
        """(payload copy, mirror wid, header wid, header crc) — fused
        exchange first if this lane's slab has staged traffic.  The
        KILL sentinel lives in the host mirror only (the seqlock
        contract: kill overwrites the id, the payload stays the last
        one written) — staged generations still flush, so a reader's
        final pass sees the writer's final payload, not the last one
        somebody happened to read."""
        slab = self._slab
        with slab.lock:
            wid = slab.wid[self.lane]
        self.fabric.ensure_fresh(slab)
        with slab.lock:
            host = slab.host
            if host is None:
                data = np.zeros(self.length)
                return data, wid, 0, payload_checksum(data)
            row = host[self.lane]
            data = row[HEADER_LANES:HEADER_LANES + self.length].copy()
            return data, wid, int(row[_H_WID]), int(row[_H_CRC])

    def _account(self, wid, ok=True):
        if wid != self.KILL:
            if not ok or (wid == self._last_read_wid and wid > 0):
                self.fabric._c_stale.inc()
            self._last_read_wid = wid

    def read(self):
        """(host data copy, write_id) — one committed snapshot, with
        the window-level stale-read accounting of DeviceWindow.read."""
        data, wid, hdr_wid, _ = self._snapshot()
        wid = wid if wid == self.KILL else hdr_wid
        self._account(wid)
        return data, wid

    def read_checked(self):
        """(data, write_id, ok, reason) — one snapshot validated
        against the slab's header lane (checksum + monotone write_id
        via PayloadGuard); corrupt snapshots also count as stale, like
        DeviceWindow.read_checked."""
        data, wid, hdr_wid, crc = self._snapshot()
        wid = wid if wid == self.KILL else hdr_wid
        ok, reason = self._pguard.check(data, wid, crc)
        self._account(wid, ok=ok)
        return data, wid, ok, reason

    def read_device(self):
        """(device-resident payload, write_id) without a host copy —
        a lane slice of the committed replicated slab, for consumers
        that feed the vector straight into a jitted program."""
        slab = self._slab
        with slab.lock:
            wid = slab.wid[self.lane]
        self.fabric.ensure_fresh(slab)
        with slab.lock:
            if slab.dev is None:
                import jax
                return jax.numpy.zeros(self.length), wid
            return (device_f64(slab.dev[
                self.lane,
                2 * HEADER_LANES:2 * (HEADER_LANES + self.length)]), wid)

    def corrupt_next_write(self):
        """Chaos hook (corrupt_window mode) — see Window."""
        self._corrupt_next = True

    def send_kill(self):
        with self._slab.lock:
            self._slab.wid[self.lane] = self.KILL

    def close(self):
        """Interface parity with Window/DeviceWindow; slab buffers are
        shared fabric state and die with the fabric."""


class CollectiveFabric:
    """The shared exchange fabric of one wheel: all hub<->spoke pairs
    as lane rows of two slabs (module docstring).

    `devices` — one lane-mesh device per row; the MPMD wheel passes
    each spoke slice's first device (so the gather input rows land on
    the slices that produced them), the shared-mesh WheelSpinner passes
    a prefix of the hub mesh.  More lanes than devices wrap: K_pad
    rounds the row count up to a device multiple.  `pad_multiple`
    rounds the slab payload width (slice_plan.slab_width), keeping the
    regrown width aligned with the plan's padding quantum after a
    reslice."""

    def __init__(self, devices=None, pad_multiple=1, tag="fabric"):
        self.devices = None if devices is None else list(devices)
        self.pad_to = max(int(pad_multiple), 1)
        self.tag = tag
        tel = _telemetry.get()
        self._c_writes = tel.counter("wheel.exchange_writes")
        self._c_bytes = tel.counter("wheel.exchange_bytes")
        self._c_stale = tel.counter("wheel.stale_reads")
        self._c_coll = tel.counter("wheel.collective_exchanges")
        self._h_latency = tel.histogram("wheel.exchange_seconds")
        # serializes the fused device programs across directions and
        # threads: two in-flight multi-device collectives can deadlock
        # in the XLA rendezvous (the SolverService backend-lock rule)
        self._xlock = threading.Lock()
        self._down = _Slab(self, "to_spoke", kind="bcast")
        self._up = _Slab(self, "to_hub", kind="gather")
        self._mesh = None
        self._transport = None
        self._gather = None
        self._sealed = False

    # -- wiring -----------------------------------------------------------
    @property
    def n_lanes(self):
        return len(self._down.lens)

    @property
    def trace_count(self):
        """Total device-program traces (the single-compile assertion:
        one per slab geometry — regrow retraces, steady state never)."""
        return self._up.traces + self._down.traces

    @property
    def windows(self):
        """Every CollectiveWindow of the fabric, both directions."""
        return self._down.windows + self._up.windows

    def add_pair(self, hub_length, spoke_length, tag=None):
        """Register one hub<->spoke pair as lane row `n_lanes` of both
        slabs; returns (to_spoke, to_hub) CollectiveWindows.  All pairs
        must be wired before the first exchange seals the geometry."""
        if self._sealed or self._down.stage is not None \
                or self._up.stage is not None:
            raise RuntimeError(
                "collective fabric is sealed: all pairs must be wired "
                "before the first write fixes the slab geometry")
        lane = self.n_lanes
        down, up = self._down, self._up
        down.lens.append(int(hub_length))
        up.lens.append(int(spoke_length))
        down.wid.append(0)
        up.wid.append(0)
        t = tag if tag is not None else f"{self.tag}.lane{lane}"
        to_spoke = CollectiveWindow(self, down, lane, hub_length,
                                    tag=f"{t}.to_spoke")
        to_hub = CollectiveWindow(self, up, lane, spoke_length,
                                  tag=f"{t}.to_hub")
        down.windows.append(to_spoke)
        up.windows.append(to_hub)
        return to_spoke, to_hub

    # -- geometry / device programs --------------------------------------
    def _seal(self):
        """First-exchange geometry fix: trim the lane device list and
        build the 2-D (cyl x scen) lane mesh + transport."""
        if self._sealed:
            return
        if self.n_lanes == 0:
            raise RuntimeError("collective fabric has no lanes")
        import jax

        from ..parallel.distributed import lane_transport
        from ..parallel.mesh import ScenarioMesh

        devs = self.devices if self.devices is not None else jax.devices()
        devs = list(devs)[:max(1, min(len(list(devs)), self.n_lanes))]
        self.devices = devs
        self._mesh = ScenarioMesh(devices=devs, n_cyl=len(devs))
        self._transport = lane_transport(self._mesh)
        self._sealed = True

    def _run_program(self, slab, snap):
        """Dispatch the slab's fused device program on a staged
        snapshot; returns the committed replicated device slab.  The
        jitted gather is built once per geometry (slab.traces counts
        retraces); the bcast is the transport seam's replicated
        placement and traces nothing."""
        if slab.kind == "gather":
            if self._gather is None:
                def on_trace():
                    slab.traces += 1
                self._gather = self._mesh.fused_cyl_all_gather(
                    on_trace=on_trace)
            x = self._transport.sharded(snap)      # lane rows -> lanes
            return self._gather(x)                 # donates x
        slab.traces = max(slab.traces, 1)          # geometry "trace"
        return self._transport.replicated(snap)    # the broadcast

    def ensure_fresh(self, slab):
        """Commit any staged generation of `slab` with ONE fused
        exchange.  Reads in an already-committed generation return
        immediately; concurrent readers serialize on the exchange lock
        and the loser finds the winner's commit."""
        with slab.lock:
            if slab.staged_gen <= slab.committed_gen:
                return
        with self._xlock:
            with slab.lock:
                gen = slab.staged_gen
                if gen <= slab.committed_gen:
                    return
                self._seal()
                # snapshot under the lock: writers may stage into the
                # buffer while the async transfer below still reads it
                snap = slab.stage.copy()
            # the lane mesh shards slab rows over `cyl`: pad the row
            # count to a device multiple (zero rows, write_id 0)
            r = len(self.devices)
            k = snap.shape[0]
            k_pad = ((k + r - 1) // r) * r
            if k_pad != k:
                snap = np.concatenate(
                    [snap, np.zeros((k_pad - k, snap.shape[1]))])
            # the device moves the rows' uint32 words (exchange.to_words)
            out = self._run_program(slab, to_words(snap))
            t0 = time.perf_counter()
            out.block_until_ready()
            self._h_latency.observe(time.perf_counter() - t0)
            self._c_coll.inc()
            self._c_bytes.inc(snap.nbytes)
            host = from_words(out)    # host mirror, outside the timing
            with slab.lock:
                if gen > slab.committed_gen:
                    # the OLD slab.dev stays alive (and readable) until
                    # the last reader drops it — the double buffer
                    slab.dev = out
                    slab.host = host
                    slab.committed_gen = gen

    # -- reslice support --------------------------------------------------
    def staged_payload(self, win):
        """(last staged payload, mirror wid) for one window, straight
        from the staging buffer — no device work, safe even when the
        fused program is broken (the fallback path reads through
        this)."""
        slab = win._slab
        with slab.lock:
            wid = slab.wid[win.lane]
            if slab.stage is None:
                return np.zeros(win.length), wid
            row = slab.stage[win.lane]
            n = min(win.length, int(row[_H_LEN]) or win.length)
            out = np.zeros(win.length)
            out[:n] = row[HEADER_LANES:HEADER_LANES + n]
            return out, wid

    def regrow_to_spoke(self, new_len):
        """Regrow the hub->spoke slab to the post-reslice `(S*K,)`
        width: every lane's last staged payload is re-staged — CRC
        recomputed for the truncated/zero-extended bytes — under its
        OLD write_id (a fresh id would regress below the spoke's
        last_hub_id and freeze its freshness check), and the next read
        commits the new geometry with one exchange.  All-or-nothing:
        the new stage is built on the side and swapped in at the end,
        so a failure leaves the old slab intact for the device-mailbox
        fallback."""
        new_len = int(new_len)
        down = self._down
        with self._xlock, down.lock:
            k_rows = down.stage.shape[0] if down.stage is not None \
                else len(down.lens)
            v_pad = slab_width([new_len] * max(1, len(down.lens)),
                               self.pad_to)
            stage = np.zeros((k_rows, HEADER_LANES + v_pad))
            for lane, old_n in enumerate(down.lens):
                wid = down.wid[lane]
                payload = np.zeros(new_len)
                if down.stage is not None and wid not in (0, KILL):
                    row = down.stage[lane]
                    n = min(new_len, int(row[_H_LEN]) or old_n)
                    payload[:n] = row[HEADER_LANES:HEADER_LANES + n]
                stage[lane, _H_WID] = 0 if wid == KILL else wid
                stage[lane, _H_CRC] = payload_checksum(payload)
                stage[lane, _H_LEN] = new_len
                stage[lane, HEADER_LANES:HEADER_LANES + new_len] = payload
            # commit the new geometry
            down.lens = [new_len] * len(down.lens)
            down.v_pad = v_pad
            down.stage = stage
            down.dev = None
            down.host = None
            for win in down.windows:
                win.length = new_len
                # DeviceWindow regrow swaps in FRESH windows, so the
                # re-read of a re-posted id is not stale there either
                win._last_read_wid = 0
            down.committed_gen = down.staged_gen
            down.staged_gen += 1

    def describe(self):
        """JSON-safe summary for logs / bench output."""
        return {"backend": "collective", "lanes": self.n_lanes,
                "devices": [str(d) for d in (self.devices or [])],
                "slab_bytes": {"to_spoke": self._down.nbytes,
                               "to_hub": self._up.nbytes},
                "traces": self.trace_count}


def collective_window_pair(hub_length, spoke_length, fabric=None,
                           tag=None):
    """WindowPair factory for the "collective" backend (registered by
    mpisppy_tpu.mpmd): each pair becomes one lane row of the wheel's
    shared CollectiveFabric, passed through `backend_kwargs` — the
    wheel builds ONE fabric and hands every pair the same instance."""
    if fabric is None:
        raise RuntimeError(
            "the 'collective' backend needs a shared CollectiveFabric: "
            "pass window_backend_kwargs={i: {'fabric': fabric}} per "
            "spoke (spin_the_wheel.WheelSpinner and mpmd.MPMDWheel "
            "wire this automatically)")
    return fabric.add_pair(hub_length, spoke_length, tag=tag)
