"""Separate-process cylinder deployment over the native seqlock exchange.

Reference counterpart: `WheelSpinner._make_comms` + `sputils.spin_the_wheel`
launching hub and spokes as distinct MPI programs on a strata_comm
(reference mpisppy/spin_the_wheel.py:219-237); the cylinders exchange
through one-sided RMA windows.

Here each spoke runs as its own OS process (its own Python/JAX runtime)
and dials into the hub's mmap-file windows (runtime/exchange.cpp — the
same seqlock protocol the in-process modes use, see
cylinders/spcommunicator.py).  This is the single-box stand-in for the
multi-host DCN layout: process boundary + shared-memory gateway instead
of host boundary + network gateway, with identical wire semantics
(write_id freshness, kill = write_id -1, torn reads impossible by
seqlock retry).

Because a live jitted optimizer cannot cross an exec boundary, a spoke
process reconstructs its problem from a declarative spec:

    spec = {
      "batch": {"module": "mpisppy_tpu.models.farmer",
                "builder": "build_batch",
                "kwargs": {"num_scens": 30}},
      "opt_class":   "mpisppy_tpu.utils.xhat_eval:Xhat_Eval",
      "spoke_class": "mpisppy_tpu.cylinders.lagrangian_bounder:"
                     "LagrangianOuterBound",
      "opt_options": {...}, "spoke_options": {...},
      "scenario_names": [...],
      "windows": {"prefix": "/tmp/run/pair0",
                  "hub_length": N, "spoke_length": M},
    }

The hub process creates (and owns/resets) the window files BEFORE
spawning, so attachers never race the initialization.
"""

from __future__ import annotations

import importlib
import json
import os
import subprocess
import sys

import numpy as np


def _resolve(path: str):
    mod, _, name = path.partition(":")
    return getattr(importlib.import_module(mod), name)


class SpokeHandle:
    """Hub-side stand-in for a spoke that lives in another process.

    Carries only the wiring metadata the hub needs (spoke type, display
    char, window lengths); `step()` is a no-op because the real work
    happens across the process boundary.  The incumbent solution of an
    inner-bound spoke comes back through a side file written at spoke
    finalize (`<prefix>.sol.npy`) — scalar bounds travel through the
    window itself.
    """

    def __init__(self, spoke_class, send_length: int, receive_length: int,
                 sol_path: str | None = None):
        self.converger_spoke_types = spoke_class.converger_spoke_types
        self.converger_spoke_char = spoke_class.converger_spoke_char
        self.provides_cuts = getattr(spoke_class, "provides_cuts", False)
        self.spoke_name = spoke_class.__name__
        self._send_length = int(send_length)
        self._receive_length = int(receive_length)
        self._sol_path = sol_path
        self.pair = None
        self.proc = None

    def send_length(self):
        return self._send_length

    def receive_length(self):
        return self._receive_length

    def step(self):
        return False

    @property
    def best_solution(self):
        if self._sol_path and os.path.exists(self._sol_path):
            # the spoke writes via tmp-file + os.replace, so the file
            # is never torn; a malformed file (disk full, manual edit)
            # degrades to "no solution" rather than crashing finalize
            try:
                return np.load(self._sol_path)
            except (OSError, ValueError, EOFError):
                return None
        return None

    def finalize(self):
        return None


def spawn_spoke(spec: dict, workdir: str, tag: str,
                env_overrides: dict | None = None) -> subprocess.Popen:
    """Launch `python -m mpisppy_tpu.cylinders.proc <specfile>`.

    The child inherits the parent's environment; by default it is pinned
    to the CPU backend so spoke processes never contend for the single
    accelerator (on a real multi-host pod each process owns its chips
    and this override is dropped)."""
    specfile = os.path.join(workdir, f"spoke_{tag}.json")
    with open(specfile, "w") as f:
        json.dump(spec, f)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_overrides or {})
    # child needs the package importable exactly as the parent sees it
    pkg_root = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    env["PYTHONPATH"] = pkg_root + os.pathsep + env.get("PYTHONPATH", "")
    log_path = os.path.join(workdir, f"spoke_{tag}.log")
    with open(log_path, "w") as log:
        # Popen dups the fd; closing the parent-side handle immediately
        # avoids leaking one fd per spoke in long-lived hub processes
        p = subprocess.Popen(
            [sys.executable, "-m", "mpisppy_tpu.cylinders.proc",
             specfile],
            env=env, cwd=workdir, stdout=log, stderr=subprocess.STDOUT)
    p.log_path = log_path
    return p


def run_spoke_from_spec(specfile: str) -> int:
    """Worker entry: reconstruct the spoke and serve until killed."""
    from ..utils.platform import ensure_cpu_backend
    ensure_cpu_backend()
    import jax

    with open(specfile) as f:
        spec = json.load(f)
    # the pin is deliberate (spawn_spoke): say so in the spoke's log
    print(f"spoke {os.path.basename(specfile)}: jax backend "
          f"{jax.default_backend()} "
          f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS', '')!r})",
          flush=True)

    from .spcommunicator import WindowPair

    # activate this child's telemetry BEFORE building the optimizer so
    # every configure_from_options(None) call below picks it up; the
    # spoke's spans/metrics land in its own files (real pid = own trace
    # row) which the hub merges after shutdown (spin_the_wheel.py)
    from .. import telemetry as _telemetry
    tel_cfg = spec.get("telemetry")
    tel = _telemetry.configure(tel_cfg) if tel_cfg else _telemetry.get()

    bs = spec["batch"]
    builder = getattr(importlib.import_module(bs["module"]), bs["builder"])
    batch = builder(**bs.get("kwargs", {}))
    pad_to = bs.get("pad_to")
    if pad_to and pad_to > batch.num_scens:
        # match the hub's device-padded scenario count so the flattened
        # W/nonant window vectors reshape identically on both sides
        from ..ir import pad_scenarios
        batch = pad_scenarios(batch, pad_to)
    opt_cls = _resolve(spec["opt_class"])
    spoke_cls = _resolve(spec["spoke_class"])
    opt = opt_cls(spec.get("opt_options", {}),
                  spec["scenario_names"], batch=batch)
    spoke = spoke_cls(opt, options=spec.get("spoke_options"))
    w = spec["windows"]
    spoke.pair = WindowPair(w["hub_length"], w["spoke_length"],
                            backend="native", path_prefix=w["prefix"],
                            attach=True)
    spoke.main()
    sol = getattr(spoke, "best_solution", None)
    if sol is not None:
        # atomic publish: the hub may read at any moment (spoke-exit
        # re-pairing), so it must never observe a half-written file.
        # np.save on a FILE OBJECT keeps the name verbatim (the path
        # form would append .npy to the .tmp suffix).
        import io

        from ..resilience.checkpoint import atomic_write
        final = w["prefix"] + ".sol.npy"
        buf = io.BytesIO()
        np.save(buf, np.asarray(sol))
        atomic_write(final, buf.getvalue())
    spoke.finalize()
    if tel.enabled:
        tp = tel.config.get("trace_path")
        if tp:
            tel.write_trace(tp)
        mp = tel.config.get("metrics_path")
        if mp:
            tel.write_metrics(mp)
    return 0


if __name__ == "__main__":
    sys.exit(run_spoke_from_spec(sys.argv[1]))
