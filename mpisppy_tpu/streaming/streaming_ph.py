"""StreamingPH — minibatch randomized PH over a ScenarioSource.

Randomized PH (PAPERS.md, arXiv:2009.12186) converges when each
iteration updates only a SAMPLED block of scenarios against the
current consensus; adaptive-sampling PH (arXiv:2407.20944) grows the
sample with a statistical gap estimate and stops when the BM/BPL rule
certifies it.  This driver composes both over the streaming stack:

  * device residency is ONE block of `block_width` scenarios — the
    pow2 `serve.compile_cache.width_bucket` of `stream_block_size`
    (rounded to a device-mesh multiple), so every superstep hits the
    per-shape jit caches and peak device scenario residency never
    exceeds the configured width (asserted in tests/test_streaming.py);
  * the FULL-universe algorithm state — W (S, K), last nonant values,
    the solved mask, warm starts — lives host-resident in numpy;
  * per superstep: consume the prefetched block (its host build and
    host->device transfer overlapped the previous solve), immediately
    draw + prefetch the next one, solve the block's PH subproblems
    against host W and the global consensus xbar, then apply the
    randomized W/xbar correction on the host for the sampled rows only;
  * every `stream_check_every` supersteps the consensus candidate is
    scored by `ciutils.gap_estimators` on a fresh estimator sample
    (disjoint seed region, exactly SeqSampling's discipline) and fed
    to the `SamplingRule`: stop certified, or grow the active sample.

Superstep order of operations is what makes crash-resume bit-equal
(the streamed analog of resilience/checkpoint.py's PH contract): the
next block is drawn from the sampler RNG at the START of superstep k
and the certification (RNG-free, seed-cursor driven) runs INSIDE the
superstep, so the checkpoint written after superstep k captures
post-draw RNG state + the pending index set + post-certification
cursors — resume re-prefetches the pending block (blocks are pure
functions of their index set) and replays superstep k+1 onward
bit-for-bit.

Scope: two-stage sources (root-node consensus).  Multistage streaming
needs node-id-stable cross-block consensus — the per-block node
relabeling of `source.gather_block` deliberately breaks that, so the
constructor rejects multistage sources loudly.
"""

from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np

from .. import global_toc
from ..confidence_intervals import ciutils
from ..confidence_intervals.seqsampling import SamplingRule
from ..ir import pad_scenarios
from ..ops.pdhg import reprep_row_bounds
from ..parallel.mesh import ScenarioMesh
from ..phbase import PHBase, PHState, ph_objective_arrays
from ..serve.compile_cache import width_bucket
from .sampler import AdaptiveSampler
from .stream import ScenarioStream


class _StreamCertifier:
    """ph_converger-API adapter: `is_converged()` is True once the
    certification step (run inside ph_iteration, so it precedes the
    checkpoint) has recorded a certified CI."""

    def __init__(self, sph):
        self.sph = sph

    def is_converged(self):
        return self.sph.certified is not None


class StreamingPH(PHBase):
    """Randomized PH with adaptive sampling over a ScenarioSource.

    Options (beyond PHBase's):
      stream_block_size   — real scenarios per sampled block (default 64)
      stream_seed         — sampler RNG seed; the gap-estimator seed
                            region is stream_seed + 10_000_000 (the
                            SeqSampling disjointness discipline)
      stream_check_every  — supersteps between gap certifications (5)
      stream_warm_bytes   — host warm-start store budget (default 1 GiB;
                            0 disables block warm starts)
      stopping_criterion  — "BM" (default) or "BPL"
      BM_*/BPL_*/n0min    — SamplingRule knobs (seqsampling.py)
    """

    def __init__(self, options, source, module=None, mesh=None,
                 extensions=None, extension_kwargs=None):
        o = dict(options or {})
        # the certified rule is the stopping criterion; PH's consensus
        # threshold would otherwise end the loop uncertified
        o.setdefault("convthresh", 0.0)
        # transient-build resilience: source_retries wraps the source
        # in a capped-backoff retry loop (resilience/supervisor ladder)
        # before ANY block builds — the template block included
        retries = int(o.get("source_retries", 0))
        if retries > 0:
            from ..resilience.chaos import ChaosInjector
            from .source import RetryingSource
            source = RetryingSource(
                source, retries=retries,
                backoff=float(o.get("source_backoff", 0.05)),
                backoff_cap=float(o.get("source_backoff_cap", 5.0)),
                chaos=ChaosInjector.from_options(o.get("chaos")),
                jitter=float(o.get("source_jitter", 0.25)),
                jitter_seed=o.get("source_jitter_seed"))
        self.source = source
        self.module = module
        self.total_scens = int(source.total_scens)
        mesh = mesh if mesh is not None else ScenarioMesh()
        block = max(1, min(int(o.get("stream_block_size", 64)),
                           self.total_scens))
        w = width_bucket(block, floor=mesh.size)
        self.block_width = ((w + mesh.size - 1) // mesh.size) * mesh.size

        self.rule = SamplingRule(
            o, stochastic_sampling=bool(o.get("stochastic_sampling",
                                              False)),
            stopping_criterion=o.get("stopping_criterion", "BM"))
        self.sampler = AdaptiveSampler(
            self.rule, self.total_scens, block_size=block,
            seed=int(o.get("stream_seed", 0)))

        # template block: scenarios [0, block) padded to the compiled
        # width — defines every per-superstep shape (solver, prep, rho)
        tmpl_idx = np.arange(block)
        raw = source.block(tmpl_idx)
        # check the RAW block: pad_scenarios adds a dummy pad node
        if raw.tree.num_nodes > 1:
            raise NotImplementedError(
                "StreamingPH consensus is two-stage (root node) only: "
                "block-local node relabeling breaks cross-block node "
                "identity for multistage trees")
        tmpl = pad_scenarios(raw, self.block_width)
        super().__init__(o, list(source.names(tmpl_idx)), batch=tmpl,
                         mesh=mesh, extensions=extensions,
                         extension_kwargs=extension_kwargs)

        K = self.batch.num_nonants
        S = self.total_scens
        hdt = np.dtype(np.asarray(tmpl.c).dtype)
        self._host_dtype = hdt
        self.W_host = np.zeros((S, K), hdt)       # full-S dual weights
        self.x_na_host = np.zeros((S, K), hdt)    # last nonant values
        self.solved = np.zeros(S, bool)           # ever-solved mask
        self.xbar_host = np.zeros(K, hdt)         # root consensus
        self._rho_host = float(self.options.get("defaultPHrho", 1.0))
        warm_bytes = int(self.options.get("stream_warm_bytes", 1 << 30))
        need = (S * (self.batch.num_vars + self.batch.num_rows)
                * hdt.itemsize)
        self._warm_host = (
            (np.zeros((S, self.batch.num_vars), hdt),
             np.zeros((S, self.batch.num_rows), hdt))
            if 0 < need <= warm_bytes else None)

        self._check_every = max(1, int(
            self.options.get("stream_check_every", 5)))
        self._est_seed = int(self.options.get("stream_seed", 0)) \
            + 10_000_000
        self._est_history = []
        self.certified = None
        self._pending_indices = None
        self._cur_prob = None
        self.peak_block_scens = 0
        self.convobject = _StreamCertifier(self)

        def _transfer(blk):
            return self.mesh.shard_batch(
                pad_scenarios(blk, self.block_width))

        self.stream = ScenarioStream(source, transfer=_transfer,
                                     telemetry=self._tel)

    # -- storage plumbing --------------------------------------------------
    def _shard_store(self):
        """The ShardStore behind this run's source, unwrapping retry
        wrappers — None for generator/batch sources.  Feeds the
        certified-gap quarantine debit, the stream checkpoint's
        storage cursor, and stream_stats."""
        src = self.source
        for _ in range(8):
            store = getattr(src, "store", None)
            if store is not None:
                return store
            inner = getattr(src, "inner", None)
            if inner is None:
                return None
            src = inner
        return None

    def _prefetch(self, indices):
        """Prefetch an index set, hinting the source's readahead first
        (a shard-backed source starts its disk reads before the stream
        worker even dequeues the build)."""
        hint = getattr(self.source, "note_upcoming", None)
        if hint is not None:
            hint(indices)
        self.stream.prefetch(indices)

    # -- invalid inherited surfaces ---------------------------------------
    def check_W_bound_supported(self):
        raise NotImplementedError(
            "W-based Lagrangian bounds are not valid under randomized "
            "PH: the host-resident W is updated block-wise against a "
            "SAMPLED consensus, so the prob-weighted W does not "
            "telescope to zero over the universe; the certified BM/BPL "
            "gap CI is the streaming bound")

    # -- per-block machinery ----------------------------------------------
    def _block_prep(self, blk):
        """Prep for one padded block.  Shared-A sources (uncertainty in
        row bounds only, e.g. UC wind) reuse the template prep's Ruiz
        scaling/anorm — they depend only on the shared matrix — paying
        one `reprep_row_bounds` rescale; per-scenario-A sources rebuild
        through `_build_prep`, whose prepare_* calls jit-cache per
        (pow2) block shape."""
        if blk.shared_A and self.batch.shared_A:
            dt = self.prep.row_lo.dtype
            return reprep_row_bounds(self.prep,
                                     jnp.asarray(blk.row_lo, dt),
                                     jnp.asarray(blk.row_hi, dt))
        return self._build_prep(hot=self.solver.hot_dtype, batch=blk)

    def _block_warm(self, idx):
        if self._warm_host is None:
            return None, None
        b = idx.size
        x0 = np.zeros((self.block_width, self.batch.num_vars),
                      self._host_dtype)
        y0 = np.zeros((self.block_width, self.batch.num_rows),
                      self._host_dtype)
        x0[:b] = self._warm_host[0][idx]
        y0[:b] = self._warm_host[1][idx]
        dt = self.batch.c.dtype
        return jnp.asarray(x0, dt), jnp.asarray(y0, dt)

    def _absorb_block(self, idx, blk, res):
        """Scatter a solved block's results into the host-resident
        full-S state (pads sliced off)."""
        b = idx.size
        self.x_na_host[idx] = np.asarray(
            blk.nonants(res.x), self._host_dtype)[:b]
        self.solved[idx] = True
        if self._warm_host is not None:
            self._warm_host[0][idx] = np.asarray(
                res.x, self._host_dtype)[:b]
            self._warm_host[1][idx] = np.asarray(
                res.y, self._host_dtype)[:b]
        self.peak_block_scens = max(self.peak_block_scens,
                                    int(blk.num_scens))
        self._cur_prob = np.asarray(blk.prob)

    def _recompute_consensus(self):
        """Root consensus = mean nonant value over every solved
        scenario of the active prefix (sources are uniform-probability,
        so the sample mean IS the probability-weighted xbar)."""
        act = np.flatnonzero(self.solved[:self.sampler.active_n])
        if act.size:
            self.xbar_host = self.x_na_host[act].mean(axis=0)

    def _host_conv(self):
        """Streamed convergence metric: mean over solved active
        scenarios of ||x_na - xbar||_1 / K (the sampled analog of
        phbase.convergence_metric)."""
        act = np.flatnonzero(self.solved[:self.sampler.active_n])
        if not act.size:
            return float("inf")
        K = max(self.batch.num_nonants, 1)
        d = np.abs(self.x_na_host[act] - self.xbar_host[None, :])
        return float(d.sum(axis=1).mean() / K)

    def _install_state(self, res, blk, it):
        dt = self.batch.c.dtype
        x_na = blk.nonants(res.x)
        from ..phbase import _active_fraction
        self.state = PHState(
            x=res.x, y=res.y,
            W=jnp.zeros_like(x_na),
            xbar=jnp.broadcast_to(
                jnp.asarray(self.xbar_host, dt)[None, :], x_na.shape),
            xsqbar=jnp.zeros_like(x_na),
            obj=res.obj, dual_obj=res.dual_obj,
            conv=jnp.asarray(self.conv, dt),
            it=jnp.asarray(it, jnp.int32),
            solve_iters=res.iters,
            active_frac=_active_fraction(blk, res.converged),
            solve_restarts=jnp.sum(res.restarts))

    def _install_resumed_state(self, it):
        """Minimal PHState after a stream-checkpoint restore (the
        device-side block state is transient; only `it`/`conv` feed the
        loop) — load_stream_checkpoint calls this."""
        b = self.batch
        dt = b.c.dtype
        z = jnp.zeros
        self.state = PHState(
            x=z((b.num_scens, b.num_vars), dt),
            y=z((b.num_scens, b.num_rows), dt),
            W=z((b.num_scens, b.num_nonants), dt),
            xbar=jnp.broadcast_to(
                jnp.asarray(self.xbar_host, dt)[None, :],
                (b.num_scens, b.num_nonants)),
            xsqbar=z((b.num_scens, b.num_nonants), dt),
            obj=z((b.num_scens,), dt), dual_obj=z((b.num_scens,), dt),
            conv=jnp.asarray(self.conv, dt),
            it=jnp.asarray(it, jnp.int32))

    # -- expectations over the CURRENT block ------------------------------
    def Eobjective(self, objs):
        """Sampled E[objective]: block-uniform probabilities of the
        block the objs came from (self.batch.prob is the TEMPLATE
        block's and can disagree in real-row count)."""
        p = self._cur_prob
        if p is not None and p.shape[0] == np.shape(objs)[0]:
            return jnp.sum(jnp.asarray(p, self.batch.c.dtype) * objs)
        return super().Eobjective(objs)

    # -- Iter0: sweep the initial active sample ---------------------------
    def Iter0(self):
        self._ext("pre_iter0")
        n0 = self.sampler.active_n
        bsz = self.sampler.block_size
        global_toc(f"StreamingPH Iter0: sweeping {n0} of "
                   f"{self.total_scens} scenarios in blocks of {bsz}")
        chunks = [np.arange(i, min(i + bsz, n0))
                  for i in range(0, n0, bsz)]
        self._prefetch(chunks[0])
        dual_sum = 0.0
        res = blk = None
        for j in range(len(chunks)):
            if j + 1 < len(chunks):
                self._prefetch(chunks[j + 1])
            idx, blk = self.stream.next_block()
            res = self.solve_loop(
                warm=False, batch=blk, prep=self._block_prep(blk),
                eps=self.superstep_eps,
                dtiming=self.options.get("display_timing"))
            self._absorb_block(idx, blk, res)
            dual_sum += float(np.sum(
                np.asarray(res.dual_obj)[:idx.size]))
        self._recompute_consensus()
        act = np.flatnonzero(self.solved)
        self.W_host[act] = self._rho_host * (
            self.x_na_host[act] - self.xbar_host[None, :])
        self.conv = self._host_conv()
        # SAMPLED trivial bound: the mean no-penalty dual objective over
        # the swept sample — an ESTIMATE of the full-S trivial bound
        # (unbiased for uniform scenarios), not a deterministic bound;
        # the certified CI is the streaming run's rigorous statement
        self.trivial_bound = dual_sum / max(n0, 1)
        self.best_bound = self.trivial_bound
        self._install_state(res, blk, it=0)
        # draw + prefetch the first sampled block (RNG consumption #1)
        self._pending_indices = self.sampler.draw_block()
        self._prefetch(self._pending_indices)
        global_toc(f"StreamingPH Iter0 sampled trivial bound = "
                   f"{self.trivial_bound:.6g}, conv = {self.conv:.6g}")
        if self._tel.enabled:
            self._tel.event("stream.iter0",
                            trivial_bound=self.trivial_bound,
                            active_n=n0, conv=self.conv)
        self._ext("post_iter0")
        return self.trivial_bound

    # -- one randomized superstep -----------------------------------------
    def ph_iteration(self):
        self._ext("pre_solve_loop")
        t0 = time.time()
        k = int(self.state.it) + 1
        idx, blk = self.stream.next_block()   # drawn last superstep
        # draw + prefetch superstep k+1's block NOW so its host build
        # and transfer overlap this solve (double-buffering); growth
        # from this superstep's certification takes effect at k+2
        self._pending_indices = self.sampler.draw_block()
        self._prefetch(self._pending_indices)

        b = idx.size
        dt = self.batch.c.dtype
        W_blk = np.zeros((self.block_width, self.batch.num_nonants),
                         self._host_dtype)
        W_blk[:b] = self.W_host[idx]
        xbar_b = np.broadcast_to(
            self.xbar_host[None, :],
            (self.block_width, self.batch.num_nonants))
        c_eff, q_eff = ph_objective_arrays(
            blk, jnp.asarray(W_blk, dt), self.rho,
            jnp.asarray(xbar_b, dt),
            W_on=self.W_on, prox_on=self.prox_on)
        x0, y0 = self._block_warm(idx)
        res = self.solve_loop(
            c=c_eff, qdiag=q_eff, warm=False, batch=blk,
            prep=self._block_prep(blk), x0=x0, y0=y0,
            eps=self.superstep_eps)
        self._absorb_block(idx, blk, res)
        # randomized PH correction: consensus over ALL solved active
        # scenarios, dual update for the SAMPLED rows only
        self._recompute_consensus()
        self.W_host[idx] += self._rho_host * (
            self.x_na_host[idx] - self.xbar_host[None, :])
        self.conv = self._host_conv()
        self._install_state(res, blk, it=k)
        if self._ladder is not None:
            self._ladder_eps = min(
                self._ladder_eps,
                max(self._ladder["min"],
                    self._ladder["couple"] * self.conv))
        wall = time.time() - t0
        tel = self._tel
        if tel.enabled:
            r = tel.registry
            r.counter("ph.iterations").inc()
            r.counter("stream.supersteps").inc()
            r.histogram("ph.iteration_seconds").observe(wall)
            r.gauge("ph.conv").set(self.conv)
        self._ext("post_solve_loop")
        # certification runs INSIDE the superstep (before the
        # checkpoint in iterk_loop) so a crash-after-checkpoint resume
        # replays it with the same cursors
        if (self.module is not None and self.certified is None
                and k % self._check_every == 0):
            self._certify_step()
        return self.conv

    # -- certification (the BM/BPL stopping rule) -------------------------
    def _certify_step(self):
        nk = int(self.sampler.active_n)
        xhat = self.xbar_host.copy()
        try:
            est = ciutils.gap_estimators(
                xhat, self.module, num_scens=nk, seed=self._est_seed,
                cfg=self.options)
        except RuntimeError as e:
            global_toc(f"stream certify: candidate evaluation failed "
                       f"({e}); continuing")
            return False
        self._est_seed = int(est["seed"])
        # quarantined-corpus accounting: resampled (lost) scenario
        # mass widens the gap estimate BEFORE the stopping rule sees
        # it — a degraded corpus must work harder to certify, and the
        # reported CI carries the debit explicitly.  frac == 0 (no
        # store, or a healthy one) leaves the estimate bit-untouched.
        store = self._shard_store()
        q_frac = float(store.quarantined_frac) if store is not None \
            else 0.0
        debit = ciutils.debit_quarantined_mass(est, q_frac)
        G, s = float(est["G"]), float(est["std"])
        self._est_history.append([nk, G, s])
        self._last_zhats = float(est["zhats"])
        stop = self.sampler.observe(G, s)
        global_toc(f"stream certify: n={nk} G={G:.6g} s={s:.6g} "
                   f"stop={stop} active_n={self.sampler.active_n}"
                   + (f" quarantine_debit={debit:.6g}" if debit else ""))
        if self._tel.enabled:
            self._tel.event("stream.certify", n=nk, G=G, s=s,
                            stop=bool(stop), quarantine_debit=debit)
        if stop:
            self.certified = {
                "G": G, "s": s, "num_scens": nk,
                "CI": [0.0, self.rule.ci_upper(s) + debit],
                "zhats": self._last_zhats,
                "T": int(self.sampler.est_rounds),
                "criterion": self.rule.stopping_criterion,
                "quarantined_frac": q_frac,
                "gap_debit": debit,
            }
            return True
        return False

    # -- checkpointing (resilience/checkpoint.py stream format) -----------
    def _save_checkpoint(self, path):
        from ..resilience.checkpoint import save_stream_checkpoint
        save_stream_checkpoint(path, self)

    def restore_run_checkpoint(self, path):
        from ..resilience.checkpoint import load_stream_checkpoint
        load_stream_checkpoint(path, self)
        # blocks are pure functions of their index set: re-issuing the
        # pending prefetch rebuilds exactly the block the crashed run
        # had in flight (the storage cursor was restored first, so a
        # shard-backed source replays the same substitutions)
        self._prefetch(self._pending_indices)
        global_toc(f"StreamingPH resumed from {path} at superstep "
                   f"{int(self.state.it)} "
                   f"(active_n={self.sampler.active_n})")
        return self.trivial_bound

    # -- driver -----------------------------------------------------------
    def post_loops(self):
        """Sampled E[f(xhat)]: the last certification's zhats (the
        fixed-candidate evaluation on the estimator sample) when one
        ran, else the last block's sampled objective.  Denouement
        callbacks are skipped — the resident block's rows are a sample,
        not the universe."""
        if self.certified is not None:
            return float(self.certified["zhats"])
        if getattr(self, "_last_zhats", None) is not None:
            return float(self._last_zhats)
        return float(self.Eobjective(self.state.obj))

    def stream_main(self, finalize=True):
        """Iter0 sweep -> randomized supersteps -> certified stop.
        Mirrors PH.ph_main's resume contract: `resume_from=` a stream
        checkpoint replaces Iter0 and bit-replays the trajectory."""
        resume = self.options.get("resume_from")
        from ..resilience.checkpoint import checkpoint_exists
        if resume is not None and checkpoint_exists(resume):
            trivial = self.restore_run_checkpoint(resume)
        else:
            trivial = self.Iter0()
        self.iterk_loop()
        self.stream.close()
        closer = getattr(self.source, "close", None)
        if closer is not None:
            closer()          # stop a shard source's readahead worker
        if finalize:
            eobj = self.post_loops()
            ci = self.certified["CI"] if self.certified else None
            global_toc(f"StreamingPH done: conv={self.conv:.4e} "
                       f"E[obj]~{eobj:.6g} certified_CI={ci}")
            return self.conv, eobj, trivial
        return self.conv, None, trivial

    def stream_stats(self):
        """Streaming run facts for callers."""
        st = self.stream.stats()
        steps = int(self.state.it) if self.state is not None else 0
        out = {
            "sampled_scenarios": int(self.sampler.active_n),
            "total_scens": int(self.total_scens),
            "block_width": int(self.block_width),
            "peak_block_scens": int(self.peak_block_scens),
            "supersteps": steps,
            "blocks_per_superstep": (
                st["blocks_loaded"] / max(steps, 1)),
            "sample_growth_events": int(self.sampler.growth_events),
            "ci_gap": (list(self.certified["CI"])
                       if self.certified else None),
            "certified": self.certified,
            "est_history": list(self._est_history),
            **st,
        }
        src_stats = getattr(self.source, "stats", None)
        if src_stats is not None:
            out["storage"] = src_stats()
        return out
