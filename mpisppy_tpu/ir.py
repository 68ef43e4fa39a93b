"""Array problem IR — the TPU-native replacement for the Pyomo scenario layer.

In the reference, a scenario is a Pyomo ConcreteModel produced by a user
`scenario_creator` callback, with tree metadata attached as
`_mpisppy_node_list` / `_mpisppy_probability`
(reference: mpisppy/spbase.py:505-522, mpisppy/scenario_tree.py:44).
Solvers then consume the Pyomo model out-of-process.

Here a scenario is lowered ONCE at creation time to dense arrays

    minimize   c @ x + 0.5 * x @ diag(qdiag) @ x + obj_const
    subject to row_lo <= A @ x <= row_hi
               lb <= x <= ub

and N scenarios are stacked into a `ScenarioBatch` pytree with a leading
scenario axis — the "DP axis" of stochastic programming
(SURVEY.md §2.10).  Everything downstream (PH, bounds, xhat evaluation)
is a vmapped/sharded computation over that axis.

Shapes must agree across scenarios in one batch (pad rows with free
bounds if a scenario has fewer constraints).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np


def _register(cls, data_fields, meta_fields):
    jax.tree_util.register_dataclass(
        cls, data_fields=data_fields, meta_fields=meta_fields
    )
    return cls


@dataclasses.dataclass(frozen=True)
class TreeInfo:
    """Scenario-tree metadata for one batch (reference: scenario_tree.py:44
    ScenarioNode + sputils._ScenTree at sputils.py:745).

    Nonanticipative ("nonant") variables are the per-scenario slots that
    must agree across scenarios sharing a tree node.  They are laid out
    stage-major inside each scenario's x-vector via `nonant_idx`.

    node_of[s, j] = global node id owning nonant slot j of scenario s.
    For a two-stage problem every entry is 0 (the ROOT node).
    Per-node consensus (Compute_Xbar) is a segment-sum over node ids —
    the TPU analog of the reference's per-tree-node MPI communicators
    (spbase.py:333-375).
    """

    # (S, K) int32: global node id per scenario per nonant slot
    node_of: Any
    # (S,) float: unconditional scenario probability
    prob: Any
    # number of distinct nodes (static, for segment_sum sizing)
    num_nodes: int = 1
    # (K,) int32 stage (1-based) of each nonant slot; static metadata
    stage_of: Any = None
    # names for reporting (static)
    nonant_names: tuple = ()
    scen_names: tuple = ()


_register(
    TreeInfo,
    data_fields=("node_of", "prob"),
    meta_fields=("num_nodes", "stage_of", "nonant_names", "scen_names"),
)


@dataclasses.dataclass(frozen=True)
class ScenarioBatch:
    """A batch of S lowered scenario subproblems (leading axis = scenario).

    The lowering replaces the reference's per-iteration Pyomo objective
    mutation (phbase.py:585-699 attach_Ws_and_prox/attach_PH_to_objective):
    PH's W and prox terms enter as pure array arguments to the solver
    kernel, never touching this static problem data.
    """

    c: Any          # (S, N) linear objective
    qdiag: Any      # (S, N) diagonal quadratic objective (0 for LP)
    A: Any          # (S, M, N) constraints
    row_lo: Any     # (S, M)
    row_hi: Any     # (S, M)
    lb: Any         # (S, N)
    ub: Any         # (S, N)
    obj_const: Any  # (S,)
    nonant_idx: Any  # (K,) int32 — same layout for all scenarios
    integer_mask: Any  # (S, N) bool
    tree: TreeInfo
    # (n_stages, S, N): per-stage objective coefficient split, for
    # FirstStageCost-style reporting (reference cost_expression per node);
    # optional — None when not provided.
    stage_cost_c: Any = None
    # (S, K) per-(scenario, nonant-slot) probabilities for consensus
    # averaging — the reference's variable_probability feature
    # (spbase.py:394 _mpisppy_variable_probability); None = use the
    # scenario probabilities uniformly across slots.
    var_prob: Any = None
    var_names: tuple = ()   # static, length N (reporting only)
    # model-specific static metadata (e.g. UC's min-up/down window
    # tables) — carried so helpers never re-derive structure baked
    # into A; preserved by pad/densify (dataclasses.replace)
    model_meta: Any = None

    @property
    def num_scens(self):
        return self.c.shape[0]

    @property
    def shared_A(self):
        """True when ONE constraint matrix serves every scenario (the
        uncertainty lives in row bounds / objective only): A is stored
        (1, M, N) and ops use ir.bmatvec's matmul fast path."""
        return self.A.shape[0] == 1 and self.c.shape[0] > 1

    @property
    def split_A(self):
        """True when A is stored split-native (ir.SplitA: shared part +
        per-scenario sparse delta) — the representation for instances
        too large to ever materialize (S, M, N) densely (true-size
        farmer: crops_multiplier=1000 is ~288 GB dense f32)."""
        return isinstance(self.A, SplitA)

    def densify(self):
        """Materialize a per-scenario A from a shared or split one (for
        code paths that index A by scenario, e.g. the MIP dive)."""
        if self.split_A:
            S, M, N = self.A.shape
            if S * M * N > 500_000_000:
                raise MemoryError(
                    f"densify() of a split-native batch would build a "
                    f"{S}x{M}x{N} tensor; this code path (dense "
                    f"per-scenario A) does not support instances of "
                    f"this size")
            return dataclasses.replace(self, A=self.A.to_dense())
        if not self.shared_A:
            return self
        A = jnp.broadcast_to(self.A[0][None],
                             (self.num_scens,) + self.A.shape[1:])
        return dataclasses.replace(self, A=A)

    @property
    def num_vars(self):
        return self.c.shape[1]

    @property
    def num_rows(self):
        return self.A.shape[1]

    @property
    def num_nonants(self):
        return self.nonant_idx.shape[0]

    @property
    def prob(self):
        return self.tree.prob

    def nonants(self, x):
        """Extract nonant slots from a (..., N) solution -> (..., K)."""
        return jnp.take(x, self.nonant_idx, axis=-1)

    def objective(self, x):
        """Per-scenario objective value of a (S, N) primal point -> (S,)."""
        return (
            jnp.sum(self.c * x, axis=-1)
            + 0.5 * jnp.sum(self.qdiag * x * x, axis=-1)
            + self.obj_const
        )


_register(
    ScenarioBatch,
    data_fields=(
        "c", "qdiag", "A", "row_lo", "row_hi", "lb", "ub", "obj_const",
        "nonant_idx", "integer_mask", "tree", "stage_cost_c", "var_prob",
        "model_meta",
    ),
    meta_fields=("var_names",),
)


@dataclasses.dataclass(frozen=True)
class SplitA:
    """Constraint batch in shared + sparse-delta form:

        A(s) = shared  +  scatter((rows, cols) -> vals[s])

    The TPU-native representation for families whose MATRIX uncertainty
    touches only a few coordinates per scenario (farmer: the per-crop
    yield coefficients — 2*n_crops entries out of M*N).  The batched
    matvec then runs as ONE (S, N) x (N, M) matmul on the MXU plus an
    nnz-sized scatter, instead of an (S, M, N) batched GEMV: per-
    iteration HBM traffic drops from S*M*N to M*N + S*nnz — the same
    trick as ScenarioBatch.shared_A (row-bound uncertainty), extended
    to matrix uncertainty.  `shared` stores ZEROS at the delta
    positions, so the scatter ADD needs no masking.

    Models declare the delta coordinate set via
    model_meta["A_delta_idx"] = (rows, cols); SPOpt then builds the
    split PreparedBatch (ops/pdhg.prepare_batch_split) while batch.A
    itself stays dense for the code paths that index it by scenario
    (MIP dives, Benders cuts, Schur assembly).
    """

    shared: Any   # (M, N) scenario-independent part (0 at delta slots)
    rows: Any     # (nnz,) int32 row of each per-scenario entry
    cols: Any     # (nnz,) int32 column of each per-scenario entry
    vals: Any     # (S, nnz) per-scenario values at (rows, cols)

    @property
    def shape(self):
        return (self.vals.shape[0],) + tuple(self.shared.shape)

    @property
    def ndim(self):
        return 3

    @property
    def dtype(self):
        return self.shared.dtype

    def to_dense(self):
        S = self.vals.shape[0]
        A = jnp.broadcast_to(self.shared[None],
                             (S,) + tuple(self.shared.shape))
        return A.at[:, self.rows, self.cols].add(self.vals)

    def astype(self, dt):
        """Cast shared + per-scenario values (the mixed-precision hot
        loop's storage cast, ops/pdhg hot_dtype); the int coordinate
        arrays are untouched.  Subclass-preserving."""
        return dataclasses.replace(
            self, shared=self.shared.astype(dt),
            vals=self.vals.astype(dt))

    def scale_shared(self, row_mult, col_mult):
        """shared <- diag(row_mult) @ shared @ diag(col_mult), in
        whatever representation `shared` uses (dense here; coordinate
        data in SparseSplitA)."""
        return self.shared * row_mult[:, None] * col_mult[None, :]


_register(SplitA, data_fields=("shared", "rows", "cols", "vals"),
          meta_fields=())


@dataclasses.dataclass(frozen=True)
class SparseSplitA(SplitA):
    """SplitA whose SHARED block is a `jax.experimental.sparse.BCOO`
    matrix instead of a dense (M, N) array.

    When the shared block itself is sparse (UC/network families: each
    row touches a handful of variables), the dense (S, N) x (N, M)
    matmul of the SplitA fast path still pays M*N FLOPs per scenario
    for mostly-zero entries.  Storing the shared block as BCOO routes
    `bmatvec`/`bmatvec_t` through the sparse dot_general rules, so the
    per-iteration cost drops from O(S*M*N) to O(S*nnz(shared) +
    S*nnz(delta)).  The per-scenario delta stays in (rows, cols, vals)
    scatter form, identical to SplitA — gather/compaction
    (`ops/pdhg._gather_prep`, `solve_compacted`) and scenario padding
    touch only `vals` and work unchanged.

    Built by `sparsify_split` when the shared density is below the
    solver's `sparse_threshold` knob (dense above it)."""

    def to_dense(self):
        S = self.vals.shape[0]
        sh = self.shared.todense()
        A = jnp.broadcast_to(sh[None], (S,) + tuple(sh.shape))
        return A.at[:, self.rows, self.cols].add(self.vals)

    def astype(self, dt):
        from jax.experimental import sparse as jsparse
        sh = jsparse.BCOO((self.shared.data.astype(dt),
                           self.shared.indices),
                          shape=self.shared.shape)
        return dataclasses.replace(self, shared=sh,
                                   vals=self.vals.astype(dt))

    def scale_shared(self, row_mult, col_mult):
        from jax.experimental import sparse as jsparse
        i = self.shared.indices
        data = self.shared.data * row_mult[i[:, 0]] * col_mult[i[:, 1]]
        return jsparse.BCOO((data, i), shape=self.shared.shape)

    @property
    def shared_nnz_frac(self):
        """Stored-element fraction of the shared block (the density the
        sparse_threshold knob gates on; bench JSON `shared_nnz_frac`)."""
        M, N = self.shared.shape
        return float(self.shared.nse) / float(max(M * N, 1))


_register(SparseSplitA, data_fields=("shared", "rows", "cols", "vals"),
          meta_fields=())


def shared_density(A):
    """Nonzero fraction of a SplitA's shared block (1.0 for non-split
    operators — dense batched A never routes sparse)."""
    if isinstance(A, SparseSplitA):
        return A.shared_nnz_frac
    if not isinstance(A, SplitA):
        return 1.0
    sh = np.asarray(A.shared)
    return float(np.count_nonzero(sh)) / float(max(sh.size, 1))


def sparsify_split(A, threshold):
    """Convert a dense-shared SplitA to a SparseSplitA when its shared
    block's density is below `threshold` (host-side, once per prep —
    never inside a trace).  Returns `A` unchanged when the threshold is
    off (<= 0), the density is at/above it, or `A` is not a SplitA."""
    if threshold is None or float(threshold) <= 0.0:
        return A
    if not isinstance(A, SplitA) or isinstance(A, SparseSplitA):
        return A
    dens = shared_density(A)
    if dens >= float(threshold):
        return A
    from jax.experimental import sparse as jsparse
    sh = np.asarray(A.shared)
    nse = max(int(np.count_nonzero(sh)), 1)
    bcoo = jsparse.BCOO.fromdense(jnp.asarray(A.shared), nse=nse)
    return SparseSplitA(shared=bcoo, rows=A.rows, cols=A.cols,
                        vals=A.vals)


class Static:
    """Wrap a non-array value (string, tuple of names, ...) so it can
    ride inside `model_meta` (a DATA pytree field): the wrapper
    registers as a pytree node with NO array leaves — the value is
    auxiliary data, invisible to tree_map / jit tracing / sharding."""

    __slots__ = ("value",)

    def __init__(self, value):
        self.value = value

    def __repr__(self):
        return f"Static({self.value!r})"

    def __eq__(self, other):
        return isinstance(other, Static) and self.value == other.value

    def __hash__(self):
        return hash(self.value)


jax.tree_util.register_pytree_node(
    Static, lambda s: ((), s.value), lambda aux, _: Static(aux))


def delta_idx(batch):
    """The batch's declared sparse matrix-uncertainty coordinates
    (model_meta["A_delta_idx"] -> (rows, cols) numpy int arrays), or
    None.  ONE accessor for the contract so every consumer (SPOpt prep,
    the xhat reduced-system builder, bundling's remap) reads it the
    same way."""
    meta = batch.model_meta
    if not isinstance(meta, dict):
        return None
    return meta.get("A_delta_idx")


# Batched matvecs run at HIGHEST precision.  On a TPU the default
# precision computes an f32 matmul in one bf16 pass; there the golden
# farmer drive's PDHG solves never met their tolerance (every solve ran
# to max_iters, PH stalled at conv 0.26) while HIGHEST reproduced the
# CPU golden (chip run, PR 21).  The CPU backend ignores the flag.
_PREC = jax.lax.Precision.HIGHEST


def bmatvec(A, x):
    """Batched A @ x: A (SA, M, N) with SA == S or SA == 1 (shared
    constraint matrix), or a SplitA; x (S, N) -> (S, M).

    The shared-A case is the TPU-native fast path for model families
    whose uncertainty lives in the ROW BOUNDS only (UC wind, many
    two-stage demand models): one (M, N) matrix turns the batched
    matvec into a real (S, N) x (N, M) matmul on the MXU and cuts the
    constraint-tensor memory by S.  SplitA extends the same fast path
    to sparse MATRIX uncertainty (shared matmul + nnz scatter).  With
    a SparseSplitA the shared product routes through
    jax.experimental.sparse's dot_general rules (the `@` below
    dispatches on the BCOO type), dropping the dense M*N FLOPs per
    scenario to nnz(shared)."""
    if isinstance(A, SplitA):
        out = (x @ A.shared.T if isinstance(A, SparseSplitA)
               else jnp.matmul(x, A.shared.T, precision=_PREC))
        return out.at[:, A.rows].add(A.vals * jnp.take(x, A.cols, axis=1))
    if A.shape[0] == 1:
        return jnp.matmul(x, A[0].T, precision=_PREC)
    return jnp.einsum("smn,sn->sm", A, x, precision=_PREC)


def bmatvec_t(A, y):
    """Batched A^T @ y: A (SA, M, N) or SplitA, y (S, M) -> (S, N)."""
    if isinstance(A, SplitA):
        out = (y @ A.shared if isinstance(A, SparseSplitA)
               else jnp.matmul(y, A.shared, precision=_PREC))
        return out.at[:, A.cols].add(A.vals * jnp.take(y, A.rows, axis=1))
    if A.shape[0] == 1:
        return jnp.matmul(y, A[0], precision=_PREC)
    return jnp.einsum("smn,sm->sn", A, y, precision=_PREC)


def node_segment_sum(node_of, num_nodes):
    """Per-(tree node, nonant slot) segment reduction.

    This is THE consensus primitive: the (node, slot) pair is one
    segment key (flatid = node_of * K + slot), and a reduction over a
    (S, K) array scatter-adds into the nn*K segments then gathers back
    to scenario layout.  Used by both PH's xbar averaging
    (phbase.compute_xbar — the analog of the reference's per-tree-node
    Allreduce, phbase.py:27-107) and the EF consensus solver's
    shared-variable adjoint (ops/pdhg.ConsensusSpec).

    Returns (flatid (S, K) int32, segsum) where segsum(v: (S, K))
    -> (S, K) holds each element's segment total.
    """
    K = node_of.shape[1]
    cols = jnp.broadcast_to(jnp.arange(K)[None, :], node_of.shape)
    flatid = node_of * K + cols
    fl = flatid.reshape(-1)
    size = num_nodes * K

    def segsum(v):
        z = jnp.zeros((size,), v.dtype).at[fl].add(v.reshape(-1))
        return z[flatid]

    return flatid, segsum


def stack_scenarios(scens, scen_names=None):
    """Stack a list of single-scenario dicts/batches (S=1 each) into one
    ScenarioBatch.  Mirrors SPBase._create_scenarios looping the user's
    scenario_creator (reference spbase.py:255-273), then normalizes
    probabilities the way _compute_unconditional_node_probabilities does
    (spbase.py:378-392).
    """
    if not scens:
        raise ValueError("no scenarios to stack")
    first = scens[0]
    if any(s.num_vars != first.num_vars or s.num_rows != first.num_rows
           for s in scens):
        raise ValueError(
            "all scenarios in a batch must share (num_rows, num_vars); "
            "pad constraint rows with free bounds to equalize"
        )
    # nonant layout must be identical — the consensus average pairs slot
    # j across scenarios (reference counterpart: _verify_nonant_lengths,
    # spbase.py:150)
    ref_idx = np.asarray(first.nonant_idx)
    for s in scens[1:]:
        if not np.array_equal(np.asarray(s.nonant_idx), ref_idx):
            raise ValueError(
                "all scenarios must declare the same nonant variable "
                "layout (indices and order)")

    def cat(field):
        return jnp.concatenate([getattr(s, field) for s in scens], axis=0)

    prob = jnp.concatenate([s.tree.prob for s in scens])
    total = jnp.sum(prob)
    prob = prob / total
    node_of = jnp.concatenate([s.tree.node_of for s in scens], axis=0)
    num_nodes = max(s.tree.num_nodes for s in scens)
    names = tuple(scen_names) if scen_names is not None else tuple(
        n for s in scens for n in (s.tree.scen_names or ("?",) * s.num_scens)
    )
    tree = TreeInfo(
        node_of=node_of,
        prob=prob,
        num_nodes=num_nodes,
        stage_of=first.tree.stage_of,
        nonant_names=first.tree.nonant_names,
        scen_names=names,
    )
    stage_cost_c = None
    if first.stage_cost_c is not None:
        stage_cost_c = jnp.concatenate(
            [s.stage_cost_c for s in scens], axis=1)
    var_prob = None
    if first.var_prob is not None:
        var_prob = cat("var_prob")
    return ScenarioBatch(
        c=cat("c"), qdiag=cat("qdiag"), A=cat("A"),
        row_lo=cat("row_lo"), row_hi=cat("row_hi"),
        lb=cat("lb"), ub=cat("ub"), obj_const=cat("obj_const"),
        nonant_idx=first.nonant_idx,
        integer_mask=cat("integer_mask"),
        tree=tree,
        stage_cost_c=stage_cost_c,
        var_prob=var_prob,
        var_names=first.var_names,
    )


def pad_scenarios(batch: ScenarioBatch, to: int) -> ScenarioBatch:
    """Pad a batch with zero-probability dummy scenarios so S divides the
    device count.  The sharding layer requires equal shards per device —
    the analog of the reference's contiguous scenario slices per rank
    (sputils.py:804-812), which tolerate ragged slice sizes; we instead
    pad and let probability-0 entries vanish from every reduction.
    """
    S = batch.num_scens
    if to <= S:
        return batch
    padn = to - S

    def padfield(v, fill=0.0):
        pad_shape = (padn,) + v.shape[1:]
        return jnp.concatenate([v, jnp.full(pad_shape, fill, v.dtype)], axis=0)

    tree = batch.tree
    # pads get their own dummy tree node: probability-0 keeps them out
    # of every xbar average, and a distinct node id keeps them out of
    # EF consensus groups (where membership is structural, not
    # probability-weighted — a pad in ROOT would drag its tiny [0,1]
    # pad box into the shared first-stage variable)
    new_tree = TreeInfo(
        node_of=padfield(tree.node_of, tree.num_nodes),
        prob=padfield(tree.prob, 0.0),
        num_nodes=tree.num_nodes + 1,
        stage_of=tree.stage_of,
        nonant_names=tree.nonant_names,
        scen_names=tree.scen_names + tuple(
            f"_pad{i}" for i in range(padn)),
    )
    # Dummy scenarios: feasible-by-construction (free rows, unit box).
    # A shared constraint matrix needs no padding — pads reuse it under
    # free row bounds (any box point satisfies free rows).  The same
    # free-row argument keeps a model_meta["A_delta_idx"] declaration
    # sound: split prep gives a zero-padded scenario the SHARED matrix
    # instead of its literal zero matrix, which only free rows (and
    # prob 0) make harmless — pad_scenarios must never emit pads with
    # finite row bounds.
    if isinstance(batch.A, SplitA):
        # a zero-padded scenario gets the SHARED matrix plus ZERO
        # deltas — harmless under the free row bounds + prob 0 below
        # (same argument as the shared-A case); dataclasses.replace
        # keeps a SparseSplitA sparse
        A_pad = dataclasses.replace(batch.A,
                                    vals=padfield(batch.A.vals))
    else:
        A_pad = batch.A if batch.shared_A else padfield(batch.A)
    return ScenarioBatch(
        c=padfield(batch.c),
        qdiag=padfield(batch.qdiag),
        A=A_pad,
        row_lo=padfield(batch.row_lo, -np.inf),
        row_hi=padfield(batch.row_hi, np.inf),
        lb=padfield(batch.lb),
        ub=padfield(batch.ub, 1.0),
        obj_const=padfield(batch.obj_const),
        nonant_idx=batch.nonant_idx,
        integer_mask=padfield(batch.integer_mask, False),
        tree=new_tree,
        stage_cost_c=None if batch.stage_cost_c is None else jnp.pad(
            batch.stage_cost_c, ((0, 0), (0, padn), (0, 0))),
        var_prob=None if batch.var_prob is None
        else padfield(batch.var_prob, 0.0),
        var_names=batch.var_names,
        model_meta=batch.model_meta,
    )
