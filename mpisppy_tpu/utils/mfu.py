"""FLOP accounting / MFU estimation for the batched solver kernel.

The reference measures nothing hardware-level (its solves cross a
process boundary into Gurobi); this build's stated bar is knowing how
far the superstep runs from chip peak, so the solve engine
(spopt.SPOpt.solve_loop) accumulates matvec FLOPs here and bench.py
reports `mfu` and `iters_per_sec`.

Peak numbers are dtype-aware:

- TPU: per-chip dense bf16 matmul peaks from Google Cloud's published
  specs, keyed by the `device_kind` string the chip itself reports.
  A device kind missing from the table is an error, never a default.
  The MXU has no published f32 rate; f32 is modeled at half the bf16
  rate, and f64 (emulated, no native datapath) at f32 / 10 — rough but
  non-null denominators.
- CPU: estimated from the host core count x a nominal frequency x
  SIMD FLOPs/cycle per dtype (AVX2-class FMA defaults: 32 f32, 16
  f64 FLOPs per core-cycle; bf16 has no wide CPU datapath and falls
  back to the f32 rate).  Override with env CPU_PEAK_FLOPS.  The
  estimate is coarse: treat CPU MFU as a relative signal, never as a
  device metric.
"""

from __future__ import annotations

import os

# device_kind -> (bf16_peak, f32_peak) FLOP/s per chip.  bf16 peaks:
# Google Cloud TPU documentation, per-chip "Peak compute per chip
# (bf16)" of each generation's system-architecture page (v4 275, v5e
# 197, v5p 459, v6e 918 TFLOP/s); f32 is the modeled half rate.
_PEAKS = {
    "TPU v4": (275e12, 137.5e12),
    "TPU v5 lite": (197e12, 98.5e12),      # v5e
    "TPU v5": (459e12, 229.5e12),          # v5p
    "TPU v6 lite": (918e12, 459e12),       # v6e
}

# TPUs emulate f64 in software well below the f32 rate; /10 keeps the
# denominator honest enough to compare runs without overstating peak
_F64_SLOWDOWN = 10.0

# SIMD FLOPs per core-cycle for the CPU estimate (AVX2 + 2xFMA class:
# 2 ports x 8 lanes x 2 flops for f32, half the lanes for f64)
_CPU_FLOPS_PER_CYCLE = {"float32": 32.0, "float64": 16.0,
                        "bfloat16": 32.0}
_CPU_NOMINAL_HZ = 2.5e9


def _dtype_name(dtype):
    s = str(dtype)
    if "bf16" in s or "bfloat16" in s:
        return "bfloat16"
    if "64" in s:
        return "float64"
    return "float32"


def cpu_peak_flops(dtype="float32"):
    """Estimated aggregate peak FLOP/s of this host for `dtype`.
    Override with env CPU_PEAK_FLOPS (total, not per-core)."""
    env = os.environ.get("CPU_PEAK_FLOPS")
    if env:
        return float(env)
    cores = os.cpu_count() or 1
    per_cycle = _CPU_FLOPS_PER_CYCLE[_dtype_name(dtype)]
    return cores * _CPU_NOMINAL_HZ * per_cycle


def device_peak_flops(device=None, dtype="float32"):
    """Peak FLOP/s for `device` (default: jax.devices()[0]) at
    `dtype`.  Override with env TPU_PEAK_FLOPS (wins on every backend)
    or CPU_PEAK_FLOPS (hosts).  Raises ValueError for an accelerator
    whose device_kind is not in _PEAKS."""
    env = os.environ.get("TPU_PEAK_FLOPS")
    if env:
        return float(env)
    if device is None:
        import jax
        device = jax.devices()[0]
    if device.platform == "cpu":
        return cpu_peak_flops(dtype)
    kind = getattr(device, "device_kind", "")
    peaks = _PEAKS.get(kind)
    if peaks is None:
        raise ValueError(
            f"no published peak for device kind {kind!r} in "
            f"utils/mfu._PEAKS (known: {sorted(_PEAKS)}); add its "
            f"per-chip peak with its source, or set TPU_PEAK_FLOPS")
    name = _dtype_name(dtype)
    if name == "bfloat16":
        return peaks[0]
    if name == "float64":
        return peaks[1] / _F64_SLOWDOWN
    return peaks[1]


def pdhg_flops(iters, S, M, N, check_every=40, density=1.0):
    """FLOPs of `iters` PDHG iterations over an (S, M, N) batch.

    Per inner iteration: two batched matvecs (A^T y and A x~), 2*S*M*N
    mult-adds each -> 4*S*M*N FLOP counting mul+add separately is
    2*(2*S*M*N)*2... we count 1 FLOP per multiply and per add:
    each matvec = 2*M*N*S FLOP, so 4*S*M*N per iteration, plus the KKT
    check (2 more matvecs) every `check_every` iterations.

    density: nnz fraction of the constraint block when the matvecs run
    through the BCOO sparse path (ir.SparseSplitA) — sparse products
    only touch stored entries, so the dense model is debited by it.
    Dense matvecs pass the default 1.0.
    """
    per_iter = 4.0 * S * M * N * density
    checks = 4.0 * S * M * N * density / max(check_every, 1)
    return float(iters) * (per_iter + checks)


def mfu(flops, wall_seconds, device=None, dtype="float32"):
    """Model FLOP utilization in [0, 1], or None when wall time is
    degenerate.  The peak denominator is dtype-aware (see
    device_peak_flops) and defined on every backend, CPU included."""
    peak = device_peak_flops(device, dtype)
    if peak is None or wall_seconds <= 0:
        return None
    return flops / wall_seconds / peak


def record_to_registry(registry, flops, wall_seconds, kernel_iters=None,
                       device=None, dtype="float32"):
    """Mirror the accumulated FLOP/wall/MFU numbers into the telemetry
    registry as gauges, so hardware utilization shows up in metrics
    snapshots (telemetry/metrics.py write_jsonl) and not only in
    bench.py's final JSON.  No-op on a disabled registry — callers may
    invoke it unconditionally from hot paths."""
    if not getattr(registry, "enabled", False):
        return
    registry.gauge("mfu.kernel_flops").set(flops)
    registry.gauge("mfu.solve_wall_seconds").set(wall_seconds)
    if kernel_iters is not None:
        registry.gauge("mfu.kernel_iters").set(kernel_iters)
        if wall_seconds > 0:
            registry.gauge("mfu.iters_per_sec").set(
                kernel_iters / wall_seconds)
    u = mfu(flops, wall_seconds, device, dtype)
    if u is not None:
        registry.gauge("mfu.mfu").set(u)
