"""Device mesh + sharding layer — the TPU-native replacement for the
reference's MPI communicator plumbing (SURVEY.md §2.3).

The reference splits COMM_WORLD into per-tree-node communicators
(spbase.py:333-375) and reduces numpy buffers with comm.Allreduce
(phbase.py:83-87).  Here the scenario axis is a named mesh axis: batches
are placed with a NamedSharding over axis "scen", every consensus
reduction is a sum over that axis inside one jit-compiled program, and
XLA lowers the reductions to ICI collectives (psum / reduce-scatter)
automatically under GSPMD.  Multi-host DCN scaling follows the same
code path — parallel.distributed.init_multihost wires the processes
into one runtime (jax.distributed), after which this mesh spans the
GLOBAL device list and the same program's collectives cross process
boundaries (exercised by tests/test_multihost.py on a 2-process CPU
fleet).

The n_devices=1 case IS the serial mock (reference mpisppy/MPI.py:19-82
_MockMPIComm): the same program compiles to a single-device executable
with the collectives elided.
"""

from __future__ import annotations

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ir import ScenarioBatch, pad_scenarios


class ScenarioMesh:
    """A 1-D (or 2-D cylinder x scenario) device mesh for scenario
    parallelism — the analog of the reference's rank grid
    (spin_the_wheel.py:219-237 _make_comms).

    Multi-host: after parallel.distributed.init_multihost(),
    jax.devices() returns the GLOBAL device list and this same mesh
    spans every process — placement then goes through
    jax.make_array_from_callback (each process materializes only its
    addressable shards) and XLA lowers the consensus reductions to
    cross-process collectives over DCN, the analog of the reference's
    inter-node MPI traffic (SURVEY.md §2.3)."""

    def __init__(self, devices=None, axis_name="scen", n_cyl=None,
                 cyl_axis="cyl"):
        if devices is None:
            devices = jax.devices()
        self.devices = list(devices)
        self.axis_name = axis_name
        self.n_cyl = int(n_cyl) if n_cyl else None
        self.cyl_axis = cyl_axis if self.n_cyl else None
        if self.n_cyl:
            # 2-D cylinder x scenario grid: one row per cylinder, the
            # scenario axis within each row (the reference's rank grid,
            # spin_the_wheel.py:219-237 _make_comms).  Batches shard on
            # the scenario axis only, so each cylinder row holds a full
            # scenario-sharded copy
            if len(self.devices) % self.n_cyl:
                raise ValueError(
                    f"{len(self.devices)} devices do not split into "
                    f"{self.n_cyl} cylinder rows")
            grid = np.array(self.devices).reshape(self.n_cyl, -1)
            self.mesh = Mesh(grid, (cyl_axis, axis_name))
        else:
            self.mesh = Mesh(np.array(self.devices), (axis_name,))
        # single-process fast path keeps plain device_put
        self.multihost = jax.process_count() > 1

    def _put(self, arr, sharding):
        if not self.multihost:
            return jax.device_put(arr, sharding)
        host = np.asarray(arr)
        return jax.make_array_from_callback(
            host.shape, sharding, lambda idx: host[idx])

    @property
    def size(self):
        return len(self.devices)

    @property
    def scen_size(self):
        """Extent of the scenario axis — the padding quantum for
        shard_batch.  Equals `size` on a 1-D mesh; on a 2-D cylinder x
        scenario mesh each cylinder row holds `size // n_cyl` scenario
        shards."""
        return self.size // self.n_cyl if self.n_cyl else self.size

    def submesh(self, devices, axis_name=None):
        """A fresh 1-D ScenarioMesh over a subset of this mesh's
        devices — the building block of mpmd.SlicePlan (each cylinder
        gets its own disjoint submesh)."""
        devs = list(devices)
        if not devs:
            raise ValueError("submesh needs at least one device")
        missing = [d for d in devs if d not in self.devices]
        if missing:
            raise ValueError(
                f"devices {missing} are not part of this mesh")
        return ScenarioMesh(devs, axis_name=axis_name or self.axis_name)

    def slice_axis(self, axis=None):
        """Split the cylinder axis of a 2-D mesh into one 1-D
        ScenarioMesh per cylinder row.  The returned submeshes are
        pairwise disjoint and together cover this mesh's device list
        (guarded by tests/test_mpmd_wheel.py).  A 1-D mesh is its own
        single slice."""
        if axis is not None and self.cyl_axis is not None \
                and axis != self.cyl_axis:
            raise ValueError(
                f"mesh has cylinder axis {self.cyl_axis!r}, not {axis!r}")
        if not self.n_cyl:
            return [self]
        per_row = len(self.devices) // self.n_cyl
        return [self.submesh(self.devices[r * per_row:(r + 1) * per_row])
                for r in range(self.n_cyl)]

    def batch_sharding(self):
        """Sharding for (S, ...) scenario-leading arrays."""
        return NamedSharding(self.mesh, P(self.axis_name))

    def replicated(self):
        return NamedSharding(self.mesh, P())

    def shard_batch(self, batch: ScenarioBatch) -> ScenarioBatch:
        """Pad S to a device multiple (zero-probability dummies — the
        sharding analog of the reference's ragged rank slices,
        sputils.py:804-812) and place each leaf: scenario-leading arrays
        sharded on "scen", shared metadata replicated."""
        S = batch.num_scens
        n = self.scen_size
        Spad = ((S + n - 1) // n) * n
        batch = pad_scenarios(batch, Spad)
        shard = self.batch_sharding()
        repl = self.replicated()
        # explicit field -> axis-0-sharded map (field names, not shape
        # heuristics: nonant_idx is (K,) and K can equal Spad)
        scen_leading = {
            "c", "qdiag", "A", "row_lo", "row_hi", "lb", "ub",
            "obj_const", "integer_mask", "node_of", "prob", "var_prob",
        }

        def place(path, leaf):
            if leaf is None:
                return None
            arr = jax.numpy.asarray(leaf)
            name = path[-1].name if hasattr(path[-1], "name") else None
            if name == "A" and arr.shape[0] == 1:
                # shared constraint matrix (ir.ScenarioBatch.shared_A):
                # replicated, not sharded — every device multiplies its
                # scenario shard against the same (M, N) matrix
                return self._put(arr, repl)
            if name == "vals":
                # SplitA per-scenario delta values, (S, nnz): the only
                # scenario-leading leaf inside a split-native A (its
                # shared/rows/cols are replicated metadata below)
                return self._put(arr, shard)
            if name in scen_leading:
                return self._put(arr, shard)
            if name == "stage_cost_c":  # (n_stages, S, N)
                return self._put(
                    arr, NamedSharding(self.mesh, P(None, self.axis_name)))
            return self._put(arr, repl)

        return jax.tree_util.tree_map_with_path(place, batch)

    def shard_like_batch(self, arr):
        """Place an (S, ...) array with the batch sharding."""
        return self._put(np.asarray(arr), self.batch_sharding())

    def replicate(self, arr):
        return self._put(np.asarray(arr), self.replicated())

    def lane_sharding(self):
        """Sharding for (K, ...) lane-leading slabs on a 2-D cylinder
        mesh: rows split over the `cyl` axis, one block of lanes per
        cylinder row — the placement of the collective exchange
        fabric's staged slab (mpmd/collective.py)."""
        if not self.n_cyl:
            raise ValueError(
                "lane_sharding needs a 2-D cylinder mesh (n_cyl)")
        return NamedSharding(self.mesh, P(self.cyl_axis))

    def fused_cyl_all_gather(self, on_trace=None, donate=True):
        """ONE jitted collective for the whole exchange: shard_map of
        `jax.lax.all_gather` over the `cyl` axis, turning a
        lane-sharded (K, V) slab into a fully replicated copy on every
        lane device — the spokes->hub direction of the MPMD wheel's
        collective fabric.  `donate=True` donates the staged input
        buffer to the program (the slab never detours through a fresh
        host allocation); `on_trace` fires at trace time only, the hook
        behind the single-compile-per-geometry assertion.
        check_vma=False: with out_specs=P() the all-gather's output IS
        replicated over `cyl`, but shard_map's varying-axes check
        cannot infer that on a one-lane fabric and rejects the specs."""
        if not self.n_cyl:
            raise ValueError(
                "fused_cyl_all_gather needs a 2-D cylinder mesh (n_cyl)")
        axis = self.cyl_axis

        def gather(x):
            if on_trace is not None:
                on_trace()
            return jax.lax.all_gather(x, axis, axis=0, tiled=True)

        fn = jax.shard_map(gather, mesh=self.mesh, in_specs=P(axis),
                           out_specs=P(), check_vma=False)
        jfn = jax.jit(fn, in_shardings=self.lane_sharding(),
                      out_shardings=self.replicated(),
                      donate_argnums=(0,) if donate else ())
        if not donate:
            return jfn

        def call(x):
            # a replicated output is larger than any per-device input
            # shard, so XLA may find nothing to alias the donation to
            # (it still frees the staged buffer); silence that per-call
            # compile-time warning, it is expected here
            import warnings
            with warnings.catch_warnings():
                warnings.filterwarnings(
                    "ignore", message="Some donated buffers were not usable")
                return jfn(x)

        return call


def local_mesh():
    """Mesh over whatever devices are visible (1 TPU chip, or N forced
    CPU devices under XLA_FLAGS=--xla_force_host_platform_device_count)."""
    return ScenarioMesh()
