"""The consensus reduction (ir.node_segment_sum, pdhg.consensus_reduction)
against a plain numpy segment sum, in its dense form and, above
ir.DENSE_SEGMENT_MAX, its scatter form; and the lowered consensus
solve, whose PDHG loop body holds no scatter and no gather.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpisppy_tpu import ir, telemetry
from mpisppy_tpu.models import hydro, sslp
from mpisppy_tpu.ops.pdhg import ConsensusSpec, consensus_reduction

FORMS = ("dense", "scatter")


@pytest.fixture(autouse=True)
def _telemetry_on():
    telemetry.configure({"enabled": True, "phase_timing": False})
    yield
    telemetry.reset()


def _two_stage():
    return np.zeros((6, 3), np.int32), 1, 7, np.array([0, 2, 5])


def _hydro():
    b = hydro.build_batch()
    return (np.asarray(b.tree.node_of), b.tree.num_nodes, b.num_vars,
            np.asarray(b.nonant_idx))


def _lp_multi(k=3):
    # opt/mip._lp_multi: k stacked copies, node ids offset per copy
    node_of, nn, n, na = _hydro()
    return (np.concatenate([node_of + i * nn for i in range(k)]), k * nn,
            n, na)


def _above_the_limit():
    # slot 0 in the root, every other slot in one of 50 nodes of 12
    # scenarios, under a node count whose one-hot would hold
    # S * K * nn > DENSE_SEGMENT_MAX elements
    S, K, nn = 600, 8, 60000
    node_of = np.zeros((S, K), np.int32)
    node_of[:, 1:] = 1 + (np.arange(S) % 50)[:, None]
    assert S * K * nn > ir.DENSE_SEGMENT_MAX
    return node_of, nn, K + 2, np.arange(1, K + 1)


def _segsum_ref(node_of, v):
    """(S, K) -> each entry's (node, slot) segment total, in float64."""
    out = np.empty_like(v, np.float64)
    for k in range(v.shape[1]):
        for n in np.unique(node_of[:, k]):
            rows = node_of[:, k] == n
            out[rows, k] = v[rows, k].astype(np.float64).sum()
    return out


@pytest.mark.parametrize("case, limit, form", [
    (_two_stage, None, "dense"),
    (_hydro, None, "dense"),
    (_lp_multi, None, "dense"),
    (_above_the_limit, None, "scatter"),
    # one node builds no one-hot: dense whatever the limit
    (_two_stage, 0, "dense"),
], ids=["two_stage", "hydro", "lp_multi_k3", "above_the_limit",
        "one_node_above_the_limit"])
def test_reduction_matches_numpy_segment_sum(case, limit, form,
                                             monkeypatch):
    if limit is not None:
        monkeypatch.setattr(ir, "DENSE_SEGMENT_MAX", limit)
    node_of, nn, n, na = case()
    S, K = node_of.shape
    rng = np.random.default_rng(7)
    g = rng.uniform(1.0, 2.0, (S, n)).astype(np.float32)
    other = np.setdiff1d(np.arange(n), na)[0]     # a non-nonant column
    g[S // 2, other] = np.nan
    g[S // 3, na[-1]] = np.inf            # the last slot's deepest node
    v = g[:, na]

    assert ir.segment_total(node_of, nn)[1] == form
    ref = _segsum_ref(node_of, v)
    # node_of traced, as PH passes it, and a constant the compiler folds
    for got in (
            jax.jit(lambda no, x: ir.node_segment_sum(no, nn)[1](x))(
                jnp.asarray(node_of), jnp.asarray(v)),
            jax.jit(ir.node_segment_sum(node_of, nn)[1])(jnp.asarray(v))):
        got = np.asarray(got)
        np.testing.assert_allclose(got, ref, rtol=1e-5)
        # the inf fills its own segment and nothing else
        np.testing.assert_array_equal(np.isinf(got), np.isinf(ref))
        assert np.isinf(got).sum() == (node_of[:, -1]
                                       == node_of[S // 3, -1]).sum()

    @jax.jit
    def reduce(spec, x):
        csum, cavg, wz = consensus_reduction(spec, x.shape, x.dtype)
        return csum(x), cavg(x), wz

    spec = ConsensusSpec(node_of=jnp.asarray(node_of),
                         nonant_idx=jnp.asarray(na), num_nodes=nn)
    cs, ca, wz = (np.asarray(a) for a in reduce(spec, jnp.asarray(g)))
    counts = _segsum_ref(node_of, np.ones((S, K)))
    ref_s = g.astype(np.float64)
    ref_s[:, na] = _segsum_ref(node_of, g[:, na])
    ref_a = ref_s.copy()
    ref_a[:, na] /= counts
    ref_w = np.ones((S, n))
    ref_w[:, na] = 1.0 / counts
    for got, want in ((cs, ref_s), (ca, ref_a), (wz, ref_w)):
        np.testing.assert_allclose(got, want, rtol=1e-5)
    # the NaN in a non-nonant column stays in its one entry, the inf
    # in its segment
    for got in (cs, ca):
        assert np.argwhere(np.isnan(got)).tolist() == [[S // 2, other]]

    # one count per traced consensus reduction, in its form's counter
    r = telemetry.get().registry
    assert {f: r.counter(f"pdhg.consensus_{f}").value for f in FORMS} == {
        f: int(f == form) for f in FORMS}


def _ops(op):
    """Every operation nested in an MLIR operation, depth first."""
    for region in op.regions:
        for block in region.blocks:
            for o in block.operations:
                yield o.operation
                yield from _ops(o.operation)


def test_no_scatter_or_gather_in_the_consensus_loop_body():
    from mpisppy_tpu.ops.pdhg import shared_solve_jit
    from mpisppy_tpu.opt.ef import ExtensiveForm
    names = [f"scen{i}" for i in range(4)]
    ef = ExtensiveForm({"pdhg_eps": 1e-6}, names,
                       batch=sslp.build_batch(4))
    b = ef.batch
    (n_s, n), m = b.c.shape, ef.prep.A.shape[1]
    module = shared_solve_jit(ef.solver).lower(
        ef.prep, b.c, b.qdiag, b.lb, b.ub, jnp.zeros(n_s),
        jnp.zeros((n_s, n)), jnp.zeros((n_s, m)), ef.consensus, None,
        None).compiler_ir("stablehlo")
    body = [(op.name, str(op.location)) for op in _ops(module.operation)
            if "/while/body/" in str(op.location)]
    assert any(re.search(r"\bpdhg_consensus/", loc) for _, loc in body)
    assert [(name, loc) for name, loc in body
            if re.search(r"scatter|gather", name)] == []
    r = telemetry.get().registry
    assert r.counter("pdhg.consensus_dense").value == 1
    assert r.counter("pdhg.consensus_scatter").value == 0
