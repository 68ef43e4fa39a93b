"""Test harness config: force an 8-virtual-device CPU platform with
float64 so the sharding/collective layer is exercised without TPU
hardware — the analog of the reference's `mpiexec -np N` single-box
test tier (reference: run-mpitests.py, mpisppy/tests/straight_tests.py).

The CPU backend is pinned before the first backend initialization, so a
machine with a TPU still runs the tests on the CPU.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

from mpisppy_tpu.utils.platform import ensure_cpu_backend  # noqa: E402

ensure_cpu_backend(force=True)

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running tests excluded from the tier-1 "
        "`-m 'not slow'` selection")
    config.addinivalue_line(
        "markers", "chaos: deterministic fault-injection tests "
        "(resilience layer); these RUN under tier-1's `-m 'not slow'`")
    config.addinivalue_line(
        "markers", "telemetry: observability-layer tests (tracing, "
        "metrics, trace export); these RUN under tier-1's "
        "`-m 'not slow'`")
    config.addinivalue_line(
        "markers", "serve: solver-as-a-service layer tests (compile "
        "cache, coalescing, admission control, parity); these RUN "
        "under tier-1's `-m 'not slow'`")
    config.addinivalue_line(
        "markers", "pdhg: adaptive-work solver tests (KKT-triggered "
        "restarts, compaction, inexactness ladder, trace-safety "
        "guard); these RUN under tier-1's `-m 'not slow'`")
    config.addinivalue_line(
        "markers", "precision: mixed-precision hot-loop tests "
        "(hot_dtype, promotion, sparse matvecs, dtype-aware MFU); "
        "these RUN under tier-1's `-m 'not slow'`")
    config.addinivalue_line(
        "markers", "streaming: minibatch randomized-PH streaming tests "
        "(ScenarioSource blocks, double-buffered stream, adaptive "
        "sampler, StreamingPH parity/checkpoint); these RUN under "
        "tier-1's `-m 'not slow'`")
    config.addinivalue_line(
        "markers", "mpmd: device-resident MPMD wheel tests (slice "
        "plans, device mailboxes, seqlock parity, slice supervision) "
        "on the faked 8-device fleet; these RUN under tier-1's "
        "`-m 'not slow'`")
    config.addinivalue_line(
        "markers", "storage: durable shard-store tests (checksummed "
        "corpus, readahead, quarantine + certified-gap accounting, "
        "storage-cursor resume); these RUN under tier-1's "
        "`-m 'not slow'`")
    config.addinivalue_line(
        "markers", "net: network front-door tests (wire protocol, "
        "gateway/client over real sockets, AOT executable persistence, "
        "rolling restart); these RUN under tier-1's `-m 'not slow'`")
    config.addinivalue_line(
        "markers", "procserve: process-replica fleet tests (OS-process "
        "workers over loopback sockets, SIGKILL fault paths, DRR "
        "dispatch fairness, AOT prewarm/eviction) with a CPU-safe "
        "small process count; these RUN under tier-1's `-m 'not slow'`")
