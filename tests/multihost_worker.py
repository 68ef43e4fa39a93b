"""Worker for tests/test_multihost.py: one of N processes in a
jax.distributed CPU 'multi-host' run.

Each process owns 2 virtual CPU devices; the global mesh spans
N_PROCS x 2 devices.  Runs farmer PH (Iter0 + iterations) on the
GLOBAL mesh — the consensus segment-sum reduces across the process
boundary — and prints one JSON line with the trajectory so the parent
test can assert (a) both processes agree and (b) the numbers match a
single-process run of the same instance.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=2").strip()

# pin the CPU backend before the first backend init (same rule as
# tests/conftest.py)
from mpisppy_tpu.utils.platform import ensure_cpu_backend  # noqa: E402

ensure_cpu_backend(force=True)

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)

from jax.experimental import multihost_utils  # noqa: E402

from mpisppy_tpu.parallel import distributed  # noqa: E402


def main():
    coord, nprocs, pid = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
    distributed.init_multihost(coordinator_address=coord,
                               num_processes=nprocs, process_id=pid)
    assert jax.process_count() == nprocs
    mesh = distributed.global_mesh()
    assert mesh.size == 2 * nprocs
    assert mesh.multihost

    from mpisppy_tpu.models import farmer
    from mpisppy_tpu.opt.ph import PH

    S = 8
    ph = PH({"defaultPHrho": 1.0, "PHIterLimit": 5, "convthresh": 0.0,
             "pdhg_eps": 1e-7,
             # np.asarray of a sharded global array is per-process;
             # the certified gather path is host-local by design and
             # exercised in the single-process tiers
             "iter0_certify": False},
            [f"scen{i}" for i in range(S)],
            batch=farmer.build_batch(S), mesh=mesh)
    ph.Iter0()
    convs = [ph.ph_iteration() for _ in range(5)]
    lag = ph.lagrangian_bound()
    out = {
        "pid": pid,
        "devices": mesh.size,
        "process_count": jax.process_count(),
        "trivial_bound": float(ph.trivial_bound),
        "convs": [float(c) for c in convs],
        "lagrangian": float(lag),
        "xbar0": [float(v) for v in multihost_utils.process_allgather(
            ph.state.xbar, tiled=True)[0][:3]],
    }
    print("RESULT " + json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
