"""Shape-bucketed compile cache for the serve layer.

Requests are assigned to a BUCKET — the tuple of everything that
determines the lowered PH superstep computation: model identity
(name + static var/nonant names), scenario count, stage dims, constraint
matrix kind, dtype, backend, and the solver config.  Two requests in
the same bucket differ only in ARRAY VALUES (scenario data, rho,
bounds, tolerance), which are all traced arguments of
`phbase.ph_superstep` — so one compiled executable serves both, and a
group of them can run as one vmap-batched execution.

Per bucket this module holds:
  * the canonical `PDHGSolver` (built once via `from_options`, so its
    shared solve jit — ops.pdhg.shared_solve_jit — is warm for every
    PH constructed for requests in the bucket);
  * `superstep` — the thread-shared jitted superstep
    (`phbase.fused_superstep`): the identical lowered computation a
    standalone `PH.ph_main` runs (same pure function, same solver
    config, same shapes), which makes the serve batch=1 result
    bitwise-identical to a standalone run;
  * per-batch-width AOT executables of `vmap(ph_superstep)` for the
    coalesced B>1 path, built through `jax.export` (see below).

AOT persistence to disk
-----------------------
Each batched executable is additionally serialized via
`jax.export.export(...).serialize()` into
`<compile cache root>/aot/<fingerprint>.mtaot` (`aot_cache_dir()`), under a
fingerprint covering the full `bucket_key` PLUS batch width, jax and
jaxlib versions, backend, argument treedef, and the x64 flag — the
things that can silently change the traced program between processes.
A fresh replica (`warm_from` on a new incarnation, a rolling restart,
a cold process) deserializes the artifact instead of re-tracing: the
Python-level trace of `vmap(ph_superstep)` — the dominant cold-start
cost — is skipped entirely.  Validation mirrors the MTSHARD1 shard
discipline (streaming/store.py): magic + header JSON + payload CRC32
checked on every load, and ANY mismatch — torn file, foreign
fingerprint, version skew — falls back silently to tracing, counted in
`cache.aot_load_failures`.  Loads that succeed count
`cache.aot_loads`, saves `cache.aot_saves` (telemetry counters when
enabled, plain ints always — `telemetry.gateway_counters()`).

Both the trace and the warm path execute `jax.jit(exported.call)` over
the SAME exported artifact shape (flat array leaves in, flat leaves
out), so a warm-started replica's batched results are identical to a
freshly-traced one's — the fallback is behaviorally invisible.

The cache also counts `serve.compile_cache.{hit,miss}` per REQUEST —
the acceptance signal "N concurrent same-shape requests, one
compilation".  Wire-up to jax's own persistent XLA cache is
`utils.platform.enable_compile_cache`, called from
`SolverService.start`; the jax.export layer above it persists the
*traced program*, which jax's cache does not.
"""

from __future__ import annotations

import hashlib
import json
import os
import struct
import threading
import time
import zlib

from .. import global_toc
from .. import telemetry as _telemetry

AOT_MAGIC = b"MTAOTX1\0"
AOT_FORMAT = 1
_AOT_SUFFIX = ".mtaot"


def width_bucket(n, floor=1):
    """Next power-of-two >= max(n, floor): THE width-bucketing rule.

    Used by the serve layer's batch coalescing and by
    `ops.pdhg.PDHGSolver.solve_compacted` when it gathers unconverged
    survivors into a smaller slab — quantizing widths to powers of two
    bounds the number of distinct compiled executables at log2(S) per
    bucket instead of one per observed width."""
    n = max(int(n), int(floor), 1)
    return 1 << (n - 1).bit_length()


def solver_config(options):
    """The bucket's solver-config component: the same hashable key the
    process-wide jit registries use (PDHGSolver.config_key of the
    solver `from_options` would build)."""
    from ..ops.pdhg import PDHGSolver
    return PDHGSolver.from_options(options).config_key()


def bucket_key(batch, options=None, model=None, backend=None):
    """Shape-bucket key for one request.

    `model` defaults to the batch's static var/nonant names — a
    structural fingerprint that separates models which happen to share
    shapes; pass an explicit model name to pin it symbolically."""
    import jax

    if backend is None:
        backend = jax.default_backend()
    ident = model if model is not None else (
        batch.var_names, batch.tree.nonant_names)
    akind = ("split" if batch.split_A
             else "shared" if batch.shared_A else "dense")
    return (
        ident,
        int(batch.num_scens),
        int(batch.num_vars),
        int(batch.num_rows),
        int(batch.num_nonants),
        int(batch.tree.num_nodes),
        akind,
        str(batch.c.dtype),
        str(backend),
        solver_config(options),
        # prep STRUCTURE flag: split-vs-dense prepared matrices change
        # the argument treedef, so they cannot share an executable
        bool((options or {}).get("no_split_prep", False)),
    )


# -- AOT disk layer --------------------------------------------------------

def aot_cache_dir():
    """The on-disk AOT executable directory: `aot/` under the one
    compile-cache root (utils.platform.compile_cache_root — jax's own
    JAX_COMPILATION_CACHE_DIR when set, else <checkout>/.jax_cache)."""
    from ..utils.platform import compile_cache_root
    return os.path.join(compile_cache_root(), "aot")


def aot_fingerprint(key, B, treedef_repr):
    """The cache key of one persisted executable: sha256 over the full
    bucket key + batch width + jax/jaxlib versions + backend + argument
    treedef + x64 flag.  Anything that can change the traced program
    between processes is in here — a mismatch means "trace, don't
    load"."""
    import jax
    try:
        import jaxlib.version
        jaxlib_v = jaxlib.version.__version__
    except Exception:                  # pragma: no cover - old layouts
        jaxlib_v = "unknown"
    ident = (repr(key), int(B), jax.__version__, jaxlib_v,
             str(jax.default_backend()), str(treedef_repr),
             bool(jax.config.jax_enable_x64))
    return hashlib.sha256(repr(ident).encode("utf-8")).hexdigest()


def _aot_encode(fingerprint, B, payload):
    """One persisted executable's byte image: magic + header JSON +
    serialized jax.export payload, CRC-stamped like an MTSHARD1
    shard."""
    import jax
    header = {
        "aot_format": AOT_FORMAT,
        "fingerprint": fingerprint,
        "batch_width": int(B),
        "jax_version": jax.__version__,
        "backend": str(jax.default_backend()),
        "payload_len": len(payload),
        "payload_crc32": zlib.crc32(payload) & 0xFFFFFFFF,
    }
    hjson = json.dumps(header, sort_keys=True).encode("utf-8")
    return AOT_MAGIC + struct.pack("<I", len(hjson)) + hjson + payload


def _aot_decode(data, fingerprint):
    """Validate + strip one persisted executable; raises ValueError on
    ANY mismatch (torn, foreign, corrupt, fingerprint/format skew)."""
    if len(data) < len(AOT_MAGIC) + 4:
        raise ValueError("truncated AOT file")
    if data[:len(AOT_MAGIC)] != AOT_MAGIC:
        raise ValueError("bad AOT magic")
    (hlen,) = struct.unpack(
        "<I", data[len(AOT_MAGIC):len(AOT_MAGIC) + 4])
    hstart = len(AOT_MAGIC) + 4
    if hstart + hlen > len(data):
        raise ValueError("truncated AOT header")
    header = json.loads(data[hstart:hstart + hlen].decode("utf-8"))
    if int(header.get("aot_format", -1)) != AOT_FORMAT:
        raise ValueError(f"AOT format {header.get('aot_format')!r}")
    if header.get("fingerprint") != fingerprint:
        raise ValueError("AOT fingerprint mismatch")
    payload = data[hstart + hlen:]
    if len(payload) != int(header.get("payload_len", -1)):
        raise ValueError("AOT payload length mismatch")
    crc = zlib.crc32(payload) & 0xFFFFFFFF
    if crc != int(header.get("payload_crc32", -1)):
        raise ValueError("AOT payload CRC mismatch")
    return payload


# -- boot-time prewarm + artifact lifecycle --------------------------------
#
# A process replica boots, calls `prewarm()`, and every artifact in the
# shared aot/ dir is deserialized ONCE into this fingerprint-keyed
# resident set; `_aot_load` consults it before touching the disk, so
# the first request of every previously-seen (bucket, width) runs warm
# without a per-request open+deserialize.  The registry is process-
# global on purpose — the artifacts are keyed by full fingerprint, so a
# stale entry can never satisfy a lookup it shouldn't.

_PREWARM_LOCK = threading.Lock()
_PREWARMED = {}                        # fingerprint -> jax.export.Exported


def prewarm(directory=None):
    """Load the full AOT artifact set into the resident prewarm
    registry.  `directory` defaults to `aot_cache_dir()` (a missing
    directory → no-op, returns 0).  Undecodable/foreign files are skipped and counted in
    `cache.aot_load_failures`.  Returns the number of artifacts
    resident after the sweep."""
    from jax import export as jax_export
    d = directory if directory is not None else aot_cache_dir()
    if not d or not os.path.isdir(d):
        return 0
    tel = _telemetry.get()
    for fname in sorted(os.listdir(d)):
        if not fname.endswith(_AOT_SUFFIX):
            continue
        fp = fname[:-len(_AOT_SUFFIX)]
        with _PREWARM_LOCK:
            if fp in _PREWARMED:
                continue
        try:
            with open(os.path.join(d, fname), "rb") as f:
                payload = _aot_decode(f.read(), fp)
            exported = jax_export.deserialize(payload)
        except Exception as exc:
            tel.counter("cache.aot_load_failures").inc()
            global_toc(f"WARNING: prewarm rejected {fname}: {exc}")
            continue
        with _PREWARM_LOCK:
            _PREWARMED[fp] = exported
    with _PREWARM_LOCK:
        return len(_PREWARMED)


def clear_prewarmed():
    """Drop the resident prewarm registry (tests)."""
    with _PREWARM_LOCK:
        _PREWARMED.clear()


def prune_aot_dir(max_age_s=None, max_total_bytes=None, directory=None):
    """Bound the on-disk aot/ artifact set: evict entries older than
    `max_age_s` (by mtime), then oldest-first until the directory is
    under `max_total_bytes`.  Both limits None → no-op.  Evictions
    count in `cache.aot_evictions`; returns the number removed.
    Concurrent writers are fine — a racing delete is just skipped."""
    d = directory if directory is not None else aot_cache_dir()
    if not d or not os.path.isdir(d):
        return 0
    if max_age_s is None and max_total_bytes is None:
        return 0
    entries = []
    for fname in os.listdir(d):
        if not fname.endswith(_AOT_SUFFIX):
            continue
        path = os.path.join(d, fname)
        try:
            st = os.stat(path)
        except OSError:
            continue
        entries.append((st.st_mtime, st.st_size, path))
    entries.sort()                      # oldest first
    now = time.time()
    doomed = []
    if max_age_s is not None:
        cutoff = now - float(max_age_s)
        doomed = [e for e in entries if e[0] < cutoff]
        entries = [e for e in entries if e[0] >= cutoff]
    if max_total_bytes is not None:
        total = sum(e[1] for e in entries)
        while entries and total > int(max_total_bytes):
            e = entries.pop(0)
            doomed.append(e)
            total -= e[1]
    tel = _telemetry.get()
    removed = 0
    for _, _, path in doomed:
        try:
            os.remove(path)
        except OSError:
            continue
        removed += 1
        tel.counter("cache.aot_evictions").inc()
    if removed:
        global_toc(f"AOT cache pruned: {removed} artifact(s) evicted")
    return removed


class _BatchedRunner:
    """One batch width's executable: flat leaves through the exported
    artifact, pytree structure restored at the edges.  Callable exactly
    like the jitted vmap it replaces (same 9 positional superstep args,
    same PHState out) — `service._run_batched` can't tell warm from
    traced, which is the point."""

    def __init__(self, call, out_treedef):
        self._call = call
        self._out_treedef = out_treedef

    def __call__(self, *args):
        import jax
        leaves = jax.tree_util.tree_leaves(args)
        out = self._call(*leaves)
        return jax.tree_util.tree_unflatten(self._out_treedef,
                                            list(out))


class CompiledBucket:
    """One bucket's executables (see module docstring).  Built lazily
    by the service's single dispatch thread, so `fused_superstep`'s
    thread-local registry resolves to that thread's wrapper; the
    bucket object itself is only ever driven from the dispatch
    thread (sequentially across worker restarts)."""

    def __init__(self, key, options, owner=None):
        from ..ops.pdhg import PDHGSolver
        from ..phbase import fused_superstep
        self.key = key
        self.solver = PDHGSolver.from_options(options)
        self.superstep = fused_superstep(self.solver)
        self._batched = {}            # B -> _BatchedRunner
        self._lock = threading.Lock()
        self._owner = owner
        self.aot_compiles = 0

    def _aot_account(self, what):
        tel = self._owner._tel if self._owner is not None \
            else _telemetry.get()
        tel.counter(f"cache.{what}").inc()
        if self._owner is not None:
            with self._owner._lock:
                setattr(self._owner, what,
                        getattr(self._owner, what) + 1)

    def _aot_load(self, path, fingerprint):
        """Deserialize a persisted executable, or None (counted) when
        the file is absent, torn, corrupt, or fingerprint-skewed —
        the silent-fallback half of the AOT contract.  A boot-time
        `prewarm()` hit short-circuits the disk entirely."""
        from jax import export as jax_export
        with _PREWARM_LOCK:
            exported = _PREWARMED.get(fingerprint)
        if exported is not None:
            self._aot_account("aot_prewarm_hits")
            self._aot_account("aot_loads")
            return exported
        if not os.path.exists(path):
            return None
        try:
            with open(path, "rb") as f:
                payload = _aot_decode(f.read(), fingerprint)
            exported = jax_export.deserialize(payload)
        except Exception as exc:
            self._aot_account("aot_load_failures")
            global_toc("WARNING: AOT cache entry rejected "
                       f"({os.path.basename(path)}): {exc}")
            return None
        self._aot_account("aot_loads")
        return exported

    def _aot_save(self, path, fingerprint, B, exported):
        from ..resilience.checkpoint import atomic_write
        try:
            data = _aot_encode(fingerprint, B, exported.serialize())
            os.makedirs(os.path.dirname(path), exist_ok=True)
            atomic_write(path, data)
        except Exception as exc:       # pragma: no cover - disk full &c
            global_toc(f"WARNING: AOT cache write failed: {exc}")
            return
        self._aot_account("aot_saves")

    def batched_superstep(self, example_args):
        """The executable of `vmap(ph_superstep)` over a leading
        request axis for batch width B (from the stacked
        `example_args`: the superstep's 9 positional args, each leaf
        with a leading B axis) — deserialized from the AOT disk cache
        when a matching artifact exists, traced (once per width) and
        persisted otherwise."""
        B = int(example_args[1].shape[0])     # rho: (B, S, K)
        with self._lock:
            runner = self._batched.get(B)
        if runner is not None:
            return runner
        runner = self._build_runner(B, example_args)
        with self._lock:
            if B not in self._batched:
                self._batched[B] = runner
                self.aot_compiles += 1
        return self._batched[B]

    def _build_runner(self, B, example_args):
        import functools

        import jax
        from jax import export as jax_export

        from ..phbase import ph_superstep

        tu = jax.tree_util
        args = tuple(example_args)
        leaves, in_treedef = tu.tree_flatten(args)
        # superstep out = a PHState shaped like the (stacked) state in
        out_treedef = tu.tree_structure(args[0])
        fp = aot_fingerprint(self.key, B, repr(in_treedef))
        path = os.path.join(aot_cache_dir(), fp + _AOT_SUFFIX)
        exported = self._aot_load(path, fp)
        if exported is not None:
            return _BatchedRunner(jax.jit(exported.call), out_treedef)

        # trace path: export the flat-leaf wrapper (custom pytrees like
        # PHState/ScenarioBatch don't cross jax.export's serialization
        # boundary — positional array leaves do), then run THROUGH the
        # exported artifact so warm and traced replicas execute the
        # same program shape
        def flat_fn(*flat):
            a = tu.tree_unflatten(in_treedef, list(flat))
            out = jax.vmap(
                functools.partial(ph_superstep, self.solver))(*a)
            return tuple(tu.tree_leaves(out))

        try:
            exported = jax_export.export(jax.jit(flat_fn))(*leaves)
        except Exception as exc:
            # un-exportable program: plain AOT lower+compile, no disk
            # persistence for this bucket (counted so it's visible)
            self._aot_account("aot_export_failures")
            global_toc(f"WARNING: jax.export failed for bucket "
                       f"(B={B}): {exc!r}; falling back to "
                       "lower().compile() without persistence")
            fn = jax.jit(jax.vmap(
                functools.partial(ph_superstep, self.solver)))
            return fn.lower(*args).compile()
        self._aot_save(path, fp, B, exported)
        return _BatchedRunner(jax.jit(exported.call), out_treedef)


class CompileCache:
    """Bucket table + per-request hit/miss accounting + the AOT disk
    layer's load/save/failure counts."""

    def __init__(self, tel=None):
        self._tel = tel if tel is not None else _telemetry.get()
        self._buckets = {}
        self._lock = threading.RLock()
        self.hits = 0
        self.misses = 0
        self.aot_loads = 0
        self.aot_load_failures = 0
        self.aot_saves = 0
        self.aot_export_failures = 0
        self.aot_prewarm_hits = 0

    def get(self, batch, options=None, model=None):
        """The CompiledBucket for one request (building it on first
        sight of the bucket).  Counts one hit or miss per call — call
        it once per request, not once per dispatch group."""
        key = bucket_key(batch, options, model=model)
        with self._lock:
            entry = self._buckets.get(key)
            if entry is None:
                entry = CompiledBucket(key, options, owner=self)
                self._buckets[key] = entry
                self.misses += 1
                self._tel.counter("serve.compile_cache.miss").inc()
            else:
                self.hits += 1
                self._tel.counter("serve.compile_cache.hit").inc()
        return entry

    def stats(self):
        with self._lock:
            return {"hits": self.hits, "misses": self.misses,
                    "buckets": len(self._buckets),
                    "aot_loads": self.aot_loads,
                    "aot_load_failures": self.aot_load_failures,
                    "aot_saves": self.aot_saves,
                    "aot_export_failures": self.aot_export_failures,
                    "aot_prewarm_hits": self.aot_prewarm_hits}


_MERGE_KEYS = ("hits", "misses", "buckets", "aot_loads",
               "aot_load_failures", "aot_saves",
               "aot_export_failures", "aot_prewarm_hits")


def merged_stats_dicts(stat_dicts):
    """Aggregate already-materialized `CompileCache.stats()` dicts —
    the form process replicas report over the wire (the cache object
    lives in the worker process; only its stats cross the socket)."""
    out = {k: 0 for k in _MERGE_KEYS}
    out["caches"] = 0
    for s in stat_dicts:
        if not s:
            continue
        for k in _MERGE_KEYS:
            out[k] += int(s.get(k, 0))
        out["caches"] += 1
    return out


def merged_stats(caches):
    """Aggregate `CompileCache.stats()` across a replica set (each
    replica owns its own cache handle, so per-replica stats only tell
    half the story).  `buckets` sums the PER-CACHE bucket counts: the
    same logical shape bucket compiled in two replicas IS two
    compilations — the fault-isolation price the replica split pays
    (which the AOT disk layer now refunds: the second replica LOADS
    what the first traced), and the signal this aggregate exists to
    expose."""
    return merged_stats_dicts(c.stats() for c in caches)
