"""DeviceExchange — versioned device-resident mailboxes.

The host seqlock (cylinders/spcommunicator.Window, runtime/exchange.cpp)
keeps every bound/xhat/W vector in host memory; each exchange is a
device->host copy on the writer and a host read on the reader.  Here
the mailbox payload LIVES on a device of the READER's slice: the writer
pays one `jax.device_put` (a cross-slice ICI/DCN hop when writer and
reader occupy different submeshes — arXiv:2412.14374's MPMD transfer
pattern), and the reader's consumption is a local device read.  The
seqlock's atomicity falls out of immutability: a write materializes a
fresh committed array and swaps the (payload, write_id) reference pair
under a lock, so a concurrent `read()` sees either the old or the new
snapshot, never a torn one.

Versioning is EXACTLY the seqlock contract (monotone write_ids,
`write_id == -1` means terminate), so hubs/spokes detect stale reads
with the same id comparisons they use against the host windows —
nothing above the WindowPair seam can tell the backends apart.

This module keeps jax imports lazy (guarded by the AST check in
tests/test_mpmd_wheel.py): importing mpisppy_tpu.mpmd to register the
backend must not initialize the accelerator runtime.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from .. import telemetry as _telemetry
from ..resilience.bounds import PayloadGuard, payload_checksum


# Mailboxes move the float64 payload's BIT PATTERN as uint32 words.
# Without jax_enable_x64 — the TPU's f32 default — a float64 array put
# on a device is narrowed to float32, which broke every payload's CRC
# (reproduced on CPU with x64 off, PR 21); words cross any backend
# exactly.

def to_words(values):
    """float64 array (..., n) -> its uint32 words (..., 2n)."""
    return np.ascontiguousarray(values, dtype=np.float64).view(np.uint32)


def from_words(words):
    """uint32 words (..., 2n) fetched from a device -> float64 (..., n)."""
    return np.ascontiguousarray(np.asarray(words),
                                dtype=np.uint32).view(np.float64)


def device_f64(words):
    """Device-side from_words for read_device consumers (a float64
    device array needs jax_enable_x64)."""
    import jax
    import jax.numpy as jnp
    return jax.lax.bitcast_convert_type(
        words.reshape(words.shape[:-1] + (-1, 2)), jnp.float64)


class DeviceWindow:
    """Drop-in for cylinders.spcommunicator.Window whose payload is a
    committed device array.

    `device=None` lets jax pick (single-slice wheels); an explicit
    device pins the mailbox onto the reader's slice so writes carry the
    data across the slice boundary and reads stay local."""

    KILL = -1

    def __init__(self, length: int, device=None, tag: str | None = None):
        self.length = int(length)
        self.device = device
        self.tag = tag
        self._lock = threading.Lock()
        self._wid = 0                  # host-side mirror: write_id
        # polls (got_kill_signal every loop tick) must not sync the device
        tel = _telemetry.get()
        self._c_writes = tel.counter("wheel.exchange_writes")
        self._c_bytes = tel.counter("wheel.exchange_bytes")
        self._c_stale = tel.counter("wheel.stale_reads")
        self._h_latency = tel.histogram("wheel.exchange_seconds")
        self._last_read_wid = 0
        self._corrupt_next = False
        self._pguard = PayloadGuard()
        # pre-first-write reads must match Window: zeros with id 0
        self._checksum = payload_checksum(np.zeros(self.length))
        self._payload = self._put(np.zeros(self.length, dtype=np.float64))

    def _put(self, values):
        import jax
        return jax.device_put(to_words(values), self.device)

    @property
    def write_id(self):
        with self._lock:
            return self._wid

    def write(self, values, write_id=None):
        """Post `values` with the next (or given) write_id.  The
        transfer is timed into wheel.exchange_seconds and blocks until
        the payload is resident — the reference-swap below must never
        publish an array whose transfer can still fail."""
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
        t0 = time.perf_counter()
        arr = self._put(values)
        arr.block_until_ready()
        self._h_latency.observe(time.perf_counter() - t0)
        self._c_writes.inc()
        self._c_bytes.inc(values.nbytes)
        with self._lock:
            new_id = self._wid + 1 if write_id is None else int(write_id)
            self._payload = arr
            self._wid = new_id
            self._checksum = chk
            return new_id

    def read(self):
        """(host data copy, write_id) — one atomic snapshot, with
        window-level stale-read accounting (a repeat of the id last
        handed out here counts into wheel.stale_reads)."""
        with self._lock:
            arr, wid = self._payload, self._wid
        if wid != self.KILL:
            if wid == self._last_read_wid and wid > 0:
                self._c_stale.inc()
            self._last_read_wid = wid
        return from_words(arr), wid

    def read_checked(self):
        """(data, write_id, ok, reason) — one snapshot, integrity
        validated (checksum + monotone write_id, PayloadGuard).
        Corrupt snapshots are also counted as stale for the window's
        own traffic accounting."""
        with self._lock:
            arr, wid, chk = self._payload, self._wid, self._checksum
        data = from_words(arr)
        ok, reason = self._pguard.check(data, wid, chk)
        if wid != self.KILL:
            if not ok or (wid == self._last_read_wid and wid > 0):
                self._c_stale.inc()
            self._last_read_wid = wid
        return data, wid, ok, reason

    def corrupt_next_write(self):
        """Chaos hook (corrupt_window mode) — see Window."""
        self._corrupt_next = True

    def read_device(self):
        """(device-resident payload, write_id) without a host copy —
        for consumers that feed the vector straight into a jitted
        program on the reader's slice (float64: needs x64)."""
        with self._lock:
            return device_f64(self._payload), self._wid

    def send_kill(self):
        with self._lock:
            self._wid = self.KILL

    def close(self):
        """Interface parity with Window/NativeWindow; the device buffer
        is garbage-collected with the last reference."""


def device_window_pair(hub_length, spoke_length, hub_device=None,
                       spoke_device=None, tag=None):
    """WindowPair factory for the "device" backend (registered by
    mpisppy_tpu.mpmd): each direction's mailbox sits on the RECEIVING
    slice — to_spoke on the spoke's device, to_hub on the hub's — so
    every write is the cross-slice hop and every read is local."""
    to_spoke = DeviceWindow(hub_length, device=spoke_device,
                            tag=None if tag is None else f"{tag}.to_spoke")
    to_hub = DeviceWindow(spoke_length, device=hub_device,
                          tag=None if tag is None else f"{tag}.to_hub")
    return to_spoke, to_hub
