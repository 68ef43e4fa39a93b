"""Compiles for a DESCRIBED TPU v5e (v5e:2x2), no chip attached: the
PDHG solves of the benchmark's cells and their controls at their real
sizes, through the TPU compiler that ships with jaxlib.  What the chip's
compiler refuses — a block shape off the (8, 128) tiling, a program
over 16 GB — fails here at no chip time.

The topology is described inside a module fixture, never at import:
only one process may load libtpu, and every xdist worker imports this
file.  Each program is built and compiled once per module (`programs`)
and shared by the tests that read it.  The chip runs float32 without
x64, so every program is traced with x64 off, as the benchmark does."""

import json
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from mpisppy_tpu.models import farmer, sslp
from mpisppy_tpu.ops.pdhg import PDHGSolver, prepare_split_native
from mpisppy_tpu.opt.ef import ExtensiveForm
from mpisppy_tpu.opt.ph import PH
from mpisppy_tpu.phbase import PHState, fused_superstep

HBM_BYTES = 16 * 10**9          # one v5e chip
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile is written to the persistent cache but
    # cannot be read back without a chip: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _traffic(name):
    with open(os.path.join(REPO, "benchmark", "traffic",
                           f"{name}.json")) as f:
        return json.load(f)["options"]


def _spec(sharding):
    """Array leaves -> ShapeDtypeStructs on `sharding`; the pytrees'
    static parts (a SplitA's diagonal runs) stay as built."""
    def one(a):
        dt = a.dtype if hasattr(a, "dtype") else jnp.asarray(a).dtype
        return jax.ShapeDtypeStruct(np.shape(a), dt, sharding=sharding)
    return lambda tree: jax.tree.map(one, tree)


def _farmer_1000x1000_ph(spec):
    """The reference instance, farmer S=1000 x crops_multiplier=1000
    split-native in f32 (6001 x 12000 per scenario): one capped
    per-scenario solve, its prep traced from shapes."""
    b = farmer.build_batch(1000, crops_multiplier=1000, dtype=np.float32,
                           split=True)
    A = spec(b.A)
    prep = spec(jax.eval_shape(prepare_split_native, A, spec(b.row_lo),
                               spec(b.row_hi)))
    solver = PDHGSolver(max_iters=1000, eps=1e-5)
    vec = spec((b.c, b.qdiag, b.lb, b.ub, b.obj_const))
    x0, y0 = spec(b.c), spec(b.row_lo)
    eps = spec(jnp.zeros((), jnp.float32))
    return jax.jit(solver._solve_impl).lower(
        prep, *vec, x0, y0, None, eps)


def _ef(build, traffic, hot=None):
    """One cell's consensus EF solve as `solve_extensive_form` calls it:
    ExtensiveForm's own prep (balanced or Ruiz, built from the data),
    probability-weighted objective, zero warm starts."""
    def lower(spec):
        opts = _traffic(traffic)
        if hot:
            opts["pdhg_hot_dtype"] = hot
        b = build()
        ef = ExtensiveForm(opts, [f"s{i}" for i in range(b.num_scens)],
                           batch=b)
        b, p = ef.batch, ef.batch.prob[:, None]
        c = b.c * p
        args = (ef.prep, c, b.qdiag * p, b.lb, b.ub,
                b.obj_const * b.prob, jnp.zeros_like(c),
                jnp.zeros_like(b.row_lo), ef.consensus)
        return jax.jit(ef.solver._solve_impl).lower(
            *spec(args), None, None)
    return lower


def _farmer_2048x100():
    return farmer.build_batch(2048, crops_multiplier=100,
                              dtype=np.float32, split=True)


def _sslp_5_25_50():
    return sslp.build_batch(50, instance="sslp_5_25", dtype=np.float32)


def _farmer_2048x100_ph(spec):
    """The staged `ph_tol` cell's superstep at 2048 x crops_multiplier
    100, fed a superstep's own output state (the window's steady
    state)."""
    b = _farmer_2048x100()
    ph = PH(_traffic("ph_tol"), [f"s{i}" for i in range(b.num_scens)],
            batch=b)
    b = ph.batch
    S, N = b.c.shape
    M, K = b.row_lo.shape[1], b.nonant_idx.shape[0]

    def z(*shape):
        return jnp.zeros(shape, b.c.dtype)

    i0 = jnp.zeros((), jnp.int32)
    state = PHState(x=z(S, N), y=z(S, M), W=z(S, K), xbar=z(S, K),
                    xsqbar=z(S, K), obj=z(S), dual_obj=z(S), conv=z(),
                    it=i0, solve_iters=i0, active_frac=z(),
                    solve_restarts=i0, promoted=i0)
    fn = fused_superstep(ph.solver)
    rest = (ph.rho, ph.W_on, ph.prox_on, ph.lb_eff, ph.ub_eff,
            ph.superstep_eps, ph.prep, b)
    state = jax.eval_shape(fn, state, *rest)
    return fn.lower(*spec((state,) + rest))


PROGRAMS = {
    "farmer_1000x1000_ph": _farmer_1000x1000_ph,
    "farmer_2048x100_ef": _ef(_farmer_2048x100, "ef_gap4"),
    "farmer_2048x100_ef_bf16x": _ef(_farmer_2048x100, "ef_gap4", "bf16x"),
    "sslp_5_25_50_ef": _ef(_sslp_5_25_50, "ef_gap"),
    "sslp_5_25_50_ef_bf16x": _ef(_sslp_5_25_50, "ef_gap", "bf16x"),
    "farmer_2048x100_ph": _farmer_2048x100_ph,
}
EF_PROGRAMS = [k for k in PROGRAMS if "_ef" in k]


@pytest.fixture(scope="module")
def programs(one_chip):
    """name -> the compiled program for one chip, compiled on first
    use."""
    spec, done = _spec(one_chip), {}

    def get(name):
        if name not in done:
            with jax.enable_x64(False):
                done[name] = PROGRAMS[name](spec).compile()
        return done[name]
    return get


@pytest.mark.parametrize("name", list(PROGRAMS))
def test_pdhg_solve_compiles_at_reference_size(programs, name):
    """The jitted PDHG solve of each configuration fits one chip's
    HBM."""
    m = programs(name).memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert 0 < used < HBM_BYTES, used


def _computations(hlo):
    """Compiled HLO text -> {computation name: its instruction lines}."""
    comps, cur = {}, None
    for line in hlo.splitlines():
        m = re.match(r"(?:ENTRY )?%([\w.\-]+) .*\{$", line.rstrip())
        if m:
            cur = comps.setdefault(m.group(1), [])
        elif cur is not None:
            cur.append(line)
    return comps


_CALLS = re.compile(r"(?:calls|body|condition|to_apply|"
                    r"branch_computations)=\{?([%\w.\-, ]+)\}?")
_SCATTER = re.compile(r"(?<![%\w.\-])scatter\(")


def _loop_computations(comps):
    """Every computation a `while` body reaches: the loop bodies and
    the fusions, conditions and reducers they call."""
    todo = [m.group(1) for lines in comps.values() for line in lines
            for m in re.finditer(r" while\(.*?body=%([\w.\-]+)", line)]
    seen = set()
    while todo:
        c = todo.pop()
        if c in seen or c not in comps:
            continue
        seen.add(c)
        for line in comps[c]:
            for m in _CALLS.finditer(line):
                todo += [n.strip().lstrip("%") for n in
                         m.group(1).split(",") if n.strip()]
    return seen


@pytest.mark.parametrize("name", EF_PROGRAMS)
def test_ef_loop_body_has_no_scatter(programs, name):
    """The consensus EF solve's loop holds no scatter: the consensus sum
    is a dense masked column sum (ir.segment_total) and a SplitA's delta
    is multiplied by static diagonal runs (ir.delta_runs)."""
    comps = _computations(programs(name).as_text())
    loop = _loop_computations(comps)
    assert loop, "no while loop in the compiled solve"
    hits = sorted(c for c in loop
                  if any(_SCATTER.search(line) for line in comps[c]))
    assert not hits, hits
