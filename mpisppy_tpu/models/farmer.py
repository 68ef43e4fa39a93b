"""Farmer — the canonical scalable 2-stage stress model.

Same mathematical model and stochastic data as the reference
(mpisppy/tests/examples/farmer.py:93-232): a farmer allocates
`500 * crops_multiplier` acres among 3*crops_multiplier crops
(first stage), then after the random yield realizes, buys/sells to meet
cattle-feed requirements (second stage).  Scenario `scen{i}` uses base
yields for i%3 in {below, average, above}, plus a U[0,1) perturbation
from RandomState(i + seedoffset) when i >= 3 (matching the reference's
`farmerstream` seeding at farmer.py:60,159-165 so golden objective
values carry over).

Known golden value: the classic 3-scenario continuous farmer EF
objective is -108390 (Birge & Louveaux; asserted at 2 sig figs in the
reference test suite, mpisppy/tests/test_ef_ph.py).

Variable layout per scenario (N = 4 * ncrops):
    [0:ncrops)            DevotedAcreage      (nonant, stage 1)
    [ncrops:2*ncrops)     QuantitySubQuotaSold
    [2*ncrops:3*ncrops)   QuantitySuperQuotaSold
    [3*ncrops:4*ncrops)   QuantityPurchased

Rows (M = 2*ncrops + 1): cattle-feed requirement (>=), limit-sold (<=),
total acreage (<=).  The quota bound is a variable box bound (the
reference's EnforceQuotas range constraint, farmer.py:207-210).
"""

from __future__ import annotations

import numpy as np

from ..ir import ScenarioBatch, TreeInfo
from ..model import LinearModel

INF = float("inf")

_BASE_YIELD = {
    "below": np.array([2.0, 2.4, 16.0]),
    "average": np.array([2.5, 3.0, 20.0]),
    "above": np.array([3.0, 3.6, 24.0]),
}
_YIELD_BY_MOD3 = [_BASE_YIELD["below"], _BASE_YIELD["average"],
                  _BASE_YIELD["above"]]

_PLANTING_COST = np.array([150.0, 230.0, 260.0])
_SUB_PRICE = np.array([170.0, 150.0, 36.0])
_SUPER_PRICE = np.array([0.0, 0.0, 10.0])
_CATTLE_REQ = np.array([200.0, 240.0, 0.0])
_PURCHASE_PRICE = np.array([238.0, 210.0, 100000.0])
_QUOTA = np.array([100000.0, 100000.0, 6000.0])
_CROP_NAMES = ["WHEAT", "CORN", "SUGAR_BEETS"]


def scenario_yields(scennum, crops_multiplier=1, seedoffset=0):
    """Per-crop yields for scenario `scennum`, matching the reference's
    RNG protocol (farmer.py:60,159-165): base by scennum%3, plus one
    rand() per crop (CROPS iteration order WHEAT_i, CORN_i, BEETS_i
    interleaved per multiplier group) when scennum // 3 != 0."""
    base = np.tile(_YIELD_BY_MOD3[scennum % 3], crops_multiplier)
    if scennum // 3 != 0:
        rng = np.random.RandomState(scennum + seedoffset)
        base = base + rng.rand(3 * crops_multiplier)
    return base


def scenario_block(indices, crops_multiplier=1, use_integer=False,
                   seedoffset=0, sense=1, dtype=np.float64,
                   split="auto") -> ScenarioBatch:
    """Vectorized batch builder over an ARBITRARY index set: constructs
    exactly the scenarios named by `indices` (the host-side
    'scenario_creator loop' collapsed — reference spbase.py:255-273
    builds models one-by-one; here model build is a numpy broadcast).
    Scenario i's data depends only on its GLOBAL index (yields from
    RandomState(i + seedoffset)), so blocks are pure functions of their
    index set — the `streaming.GeneratorSource` contract.  Block
    probabilities are block-uniform (each block is a valid sampled
    batch on its own); `build_batch` is the contiguous full-universe
    special case.

    split: store A split-native (ir.SplitA — one shared (M, N) matrix
    plus the 2*nc per-scenario yield coefficients) instead of the dense
    (S, M, N) tensor.  "auto" switches when the dense tensor would
    exceed ~1 GB: the TRUE baseline-size instance (S=1000,
    crops_multiplier=1000 — reference
    paperruns/scripts/farmer/ef_1000_1000.out) is ~288 GB dense f32 and
    only exists split-native."""
    idx = np.asarray(indices, dtype=np.int64)
    nc = 3 * crops_multiplier
    N = 4 * nc
    M = 2 * nc + 1
    S = idx.size
    if split == "auto":
        split = S * M * N * np.dtype(dtype).itemsize > 1 << 30

    yields = np.stack([
        scenario_yields(int(i), crops_multiplier, seedoffset) for i in idx
    ]).astype(dtype)                                      # (S, nc)

    iac = np.arange(nc)
    isub = nc + iac
    isup = 2 * nc + iac
    ipur = 3 * nc + iac

    row_lo = np.full((S, M), -INF, dtype=dtype)
    row_hi = np.full((S, M), INF, dtype=dtype)
    r = np.arange(nc)
    r2 = nc + r
    # cattle feed: yield*x + purchased - sub - super >= req (rows 0..nc)
    row_lo[:, r] = np.tile(_CATTLE_REQ, crops_multiplier)
    # limit sold: sub + super - yield*x <= 0   (rows nc..2nc)
    row_hi[:, r2] = 0.0
    # total acreage  (last row)
    row_hi[:, -1] = 500.0 * crops_multiplier
    delta_rows = np.concatenate([r, r2]).astype(np.int32)
    delta_cols = np.concatenate([iac, iac]).astype(np.int32)
    if split:
        from ..ir import SplitA
        shared = np.zeros((M, N), dtype=dtype)
        shared[r, ipur] = 1.0
        shared[r, isub] = -1.0
        shared[r, isup] = -1.0
        shared[r2, isub] = 1.0
        shared[r2, isup] = 1.0
        shared[-1, iac] = 1.0
        # the yield slots (r x iac, r2 x iac) stay ZERO in shared; the
        # per-scenario values live in vals at (delta_rows, delta_cols)
        A = SplitA(shared=shared, rows=delta_rows, cols=delta_cols,
                   vals=np.concatenate([yields, -yields],
                                       axis=1)).with_runs()
    else:
        A = np.zeros((S, M, N), dtype=dtype)
        A[:, r, iac] = yields
        A[:, r, ipur] = 1.0
        A[:, r, isub] = -1.0
        A[:, r, isup] = -1.0
        A[:, r2, isub] = 1.0
        A[:, r2, isup] = 1.0
        A[:, r2, iac] = -yields
        A[:, -1, iac] = 1.0

    lb = np.zeros((S, N), dtype=dtype)
    ub = np.full((S, N), INF, dtype=dtype)
    total_acreage = 500.0 * crops_multiplier
    ub[:, iac] = total_acreage
    # Implied (presolve-style) finite bounds — provably inactive at some
    # optimum, so objective values are unchanged, and they make EVERY
    # variable box finite, which turns the PDHG dual objective into an
    # exact Lagrangian value for any dual iterate (spopt.Ebound validity
    # without certification):
    #  * sales: the limit-sold row gives sub+sup <= yield*x <= yield*total
    #  * purchases: sub+sup <= yield*x implies the feed row stays
    #    satisfied when purchases are lowered to the requirement, and
    #    purchase cost > 0, so an optimal purchase never exceeds req
    # The 2x margin keeps the boxes STRICTLY inactive (never degenerate
    # with the rows they were derived from), so dual solutions — and
    # everything built on them (cross-scenario cuts, reduced-cost
    # fixing) — are unchanged.
    sale_cap = 2.0 * yields * total_acreage                # (S, nc)
    ub[:, isub] = np.minimum(np.tile(_QUOTA, crops_multiplier), sale_cap)
    ub[:, isup] = sale_cap
    ub[:, ipur] = 2.0 * np.tile(_CATTLE_REQ + 1.0, crops_multiplier)

    c = np.zeros((S, N), dtype=dtype)
    c[:, iac] = np.tile(_PLANTING_COST, crops_multiplier)
    c[:, isub] = -np.tile(_SUB_PRICE, crops_multiplier)
    c[:, isup] = -np.tile(_SUPER_PRICE, crops_multiplier)
    c[:, ipur] = np.tile(_PURCHASE_PRICE, crops_multiplier)
    stage_cost_c = np.zeros((2, S, N), dtype=dtype)
    stage_cost_c[0][:, iac] = np.tile(_PLANTING_COST, crops_multiplier)
    stage_cost_c[1] = c.copy()
    stage_cost_c[1][:, iac] = 0.0
    if sense < 0:
        c = -c
        stage_cost_c = -stage_cost_c

    integer_mask = np.zeros((S, N), dtype=bool)
    if use_integer:
        integer_mask[:, iac] = True

    crop_names = [f"{nm}{g}" for g in range(crops_multiplier)
                  for nm in _CROP_NAMES]
    var_names = (
        tuple(f"DevotedAcreage[{n}]" for n in crop_names)
        + tuple(f"QuantitySubQuotaSold[{n}]" for n in crop_names)
        + tuple(f"QuantitySuperQuotaSold[{n}]" for n in crop_names)
        + tuple(f"QuantityPurchased[{n}]" for n in crop_names))

    tree = TreeInfo(
        node_of=np.zeros((S, nc), np.int32),
        prob=np.full((S,), 1.0 / S, dtype=dtype),
        num_nodes=1,
        stage_of=(1,) * nc,
        nonant_names=var_names[:nc],
        scen_names=tuple(f"scen{int(i)}" for i in idx),
    )
    # the ONLY scenario-varying matrix entries are the 2*nc yield
    # coefficients (feed rows r x iac, limit-sold rows r2 x iac);
    # declaring them (model_meta below) lets SPOpt build the ir.SplitA
    # fast path (shared matmul + nnz scatter instead of an (S, M, N)
    # batched GEMV) even when A is stored dense
    return ScenarioBatch(
        c=c, qdiag=np.zeros((S, N), dtype=dtype),
        A=A, row_lo=row_lo, row_hi=row_hi, lb=lb, ub=ub,
        obj_const=np.zeros((S,), dtype=dtype),
        nonant_idx=iac.astype(np.int32),
        integer_mask=integer_mask,
        tree=tree,
        stage_cost_c=stage_cost_c,
        var_names=var_names,
        model_meta={"A_delta_idx": (delta_rows, delta_cols)},
    )


def build_batch(num_scens, crops_multiplier=1, use_integer=False,
                seedoffset=0, sense=1, dtype=np.float64,
                split="auto") -> ScenarioBatch:
    """The full scenario universe [0, num_scens) — `scenario_block`
    over the contiguous index range (bit-identical to the historical
    builder: scenario data is a function of the global index only)."""
    return scenario_block(np.arange(num_scens),
                          crops_multiplier=crops_multiplier,
                          use_integer=use_integer, seedoffset=seedoffset,
                          sense=sense, dtype=dtype, split=split)


def scenario_source(num_scens, cfg=None):
    """streaming.ScenarioSource over the farmer universe — blocks are
    built split-native by default so the shared constraint matrix is
    never replicated per block (override with cfg["split"])."""
    cfg = dict(cfg or {})
    kw = {
        "crops_multiplier": int(cfg.get("crops_multiplier", 1)),
        "use_integer": bool(cfg.get(
            "use_integer", cfg.get("farmer_with_integers", False))),
        "seedoffset": int(cfg.get("start_seed", cfg.get("seedoffset", 0))),
        "sense": int(cfg.get("sense", 1)),
        "split": cfg.get("split", True),
    }
    from ..streaming import GeneratorSource
    return GeneratorSource(
        "farmer", int(num_scens),
        lambda idx: scenario_block(idx, **kw),
        name_fn=lambda i: f"scen{i}")


def export_corpus(path, num_scens, shard_width=64, cfg=None):
    """Persist the farmer scenario universe as a durable shard corpus
    (streaming/store.py): checksummed fixed-width shard files a
    `ShardSource` can stream back without this module's generator.
    Returns the corpus path."""
    from ..streaming import write_corpus
    return write_corpus(
        scenario_source(num_scens, cfg), path, shard_width,
        meta={"name_format": "scen{i}"})


def scenario_creator(scenario_name, use_integer=False, sense=1,
                     crops_multiplier=1, num_scens=None, seedoffset=0):
    """Single-scenario creator through the declarative LinearModel API —
    the exact analog of the reference's scenario_creator contract
    (farmer.py:25-91).  `build_batch` is the fast path; this exists for
    API parity and to exercise the modeling layer."""
    scennum = int("".join(ch for ch in scenario_name if ch.isdigit()) or 0)
    nc = 3 * crops_multiplier
    y = scenario_yields(scennum, crops_multiplier, seedoffset)
    m = LinearModel(sense=sense)
    total = 500.0 * crops_multiplier
    ac = m.add_vars("DevotedAcreage", nc, lb=0.0, ub=total,
                    integer=use_integer)
    # same implied finite bounds as build_batch (see there for the
    # optimality argument)
    sub = m.add_vars("QuantitySubQuotaSold", nc, lb=0.0,
                     ub=np.minimum(np.tile(_QUOTA, crops_multiplier),
                                   2.0 * y * total))
    sup = m.add_vars("QuantitySuperQuotaSold", nc, lb=0.0,
                     ub=2.0 * y * total)
    pur = m.add_vars("QuantityPurchased", nc, lb=0.0,
                     ub=2.0 * np.tile(_CATTLE_REQ + 1.0, crops_multiplier))
    req = np.tile(_CATTLE_REQ, crops_multiplier)
    for i in range(nc):
        m.add_constr({ac[i]: y[i], pur[i]: 1.0, sub[i]: -1.0,
                      sup[i]: -1.0}, lo=req[i])
    for i in range(nc):
        m.add_constr({sub[i]: 1.0, sup[i]: 1.0, ac[i]: -y[i]}, hi=0.0)
    m.add_constr({ac[i]: 1.0 for i in range(nc)}, hi=total)
    m.add_cost(1, {ac[i]: np.tile(_PLANTING_COST, crops_multiplier)[i]
                   for i in range(nc)})
    m.add_cost(2, {
        **{pur[i]: np.tile(_PURCHASE_PRICE, crops_multiplier)[i]
           for i in range(nc)},
        **{sub[i]: -np.tile(_SUB_PRICE, crops_multiplier)[i]
           for i in range(nc)},
        **{sup[i]: -np.tile(_SUPER_PRICE, crops_multiplier)[i]
           for i in range(nc)},
    })
    m.set_nonants([ac], stage=1)
    prob = 1.0 / num_scens if num_scens else 1.0
    return m.lower(prob=prob, name=scenario_name)


# ---- amalgamator-contract helpers (reference farmer.py:237-268) ----------

def scenario_names_creator(num_scens, start=None):
    start = start or 0
    return [f"scen{i}" for i in range(start, start + num_scens)]


def kw_creator(options):
    return {
        # CLI flag name is farmer_with_integers (inparser_adder);
        # programmatic callers may pass use_integer directly
        "use_integer": options.get(
            "use_integer", options.get("farmer_with_integers", False)),
        "crops_multiplier": options.get("crops_multiplier", 1),
        "num_scens": options.get("num_scens", None),
    }


def inparser_adder(cfg):
    cfg.num_scens_required()
    cfg.add_to_config("crops_multiplier",
                      description="number of crops is 3x this", domain=int,
                      default=1)
    cfg.add_to_config("farmer_with_integers",
                      description="integer acreage variant", domain=bool,
                      default=False)


def batch_creator(cfg_or_kwargs, num_scens=None):
    """Build the full ScenarioBatch from kwargs (fast vectorized path)."""
    kw = dict(cfg_or_kwargs)
    n = num_scens or kw.pop("num_scens", None)
    kw.pop("num_scens", None)
    return build_batch(n, **kw)


def scenario_denouement(rank, scenario_name, result):
    pass
