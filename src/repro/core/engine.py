"""On-device multi-round scan engine (DESIGN.md §12): compile a chunk of
R federated rounds into ONE jitted, donated-buffer program.

Why
---
The eager cohort runtime (``CohortFLServer.round``, DESIGN.md §9) already
collapsed a round to O(#plans) dispatches + one device→host sync — but it
still drives the ROUND LOOP from Python: every round pays the dispatch
latency of each cohort step, the op-by-op aggregation/update chain, host
participation sampling, and a blocking ``device_get`` before the next
round may start. At the ROADMAP's "thousands of cheap rounds" scale
(FedBuff/large-cohort regimes), that per-round overhead — not FLOPs —
dominates simulated-round throughput.

What
----
:class:`ScanEngine` compiles R rounds into one program:

- ``jax.lax.scan`` over rounds; the cohorts are unrolled inside the body
  (plans are static, so each cohort keeps its own specialized step);
- participation AND deadline-drop masks are precomputed on host as
  stacked ``(R, C)`` float arrays, preserving the eager path's numpy RNG
  sequence (``default_rng([seed, step])`` per round) and its host-side
  ``T > deadline`` float64 comparison — so WHO participates is
  bit-identical to the eager path by construction;
- ``params`` / ``opt_state`` / error-feedback buffers ride the scan
  carry and the whole carry is donated (``donate_argnums=(0,)``), so the
  global model updates in place across rounds and chunks;
- per-round metrics (loss sum, Eq. (1) wall-clock as a device-side
  masked max, upload bytes, participant count) are stacked by the scan
  and synced to host ONCE per chunk;
- rounds in which nobody participates (deadline dropped everyone) apply
  no update: the carry is ``where``-selected, matching the eager path's
  skip.

Bit-identity
------------
The round body reuses the eager path's step functions verbatim
(``federated.cohort_step_fn``) and replays its aggregation/update chain
(``accumulate_cohort`` → ``finalize`` → optimizer) in the same order.
One compilation detail matters: fused into a single XLA module, the
cohort-step outputs would fuse INTO the aggregation chain and FMA
contraction changes low-order bits. ``jax.lax.optimization_barrier`` at
each cohort-step output and around the server-apply subgraph — exactly
where the eager path has dispatch boundaries — pins the compiled
arithmetic to the eager path's, and ``tests/test_engine.py`` proves
params/opt_state trajectories bit-identical across sync-wait,
sync-drop, fedavg and quant+EF scenarios, with SGD and momentum
optimizers. Known limit: Adam's bias-corrected rsqrt update compiles
with a one-ulp difference inside the scan despite the barriers
(its m/v moments stay exact); the engine-vs-eager Adam trajectory is
therefore parity-tested to 1e-6, not bitwise.

Aggregation backends
--------------------
``agg="sequential"`` (default) replays the eager accumulate/finalize
chain — bit-identical, O(#cohorts) passes over the gradient tree.
``agg="pallas"`` fuses the aggregation, picking the kernel by fleet
shape (the backend actually used is reported as ``agg_backend``):

- masked fleets (no width-sliced cohort) stack update-sums and masks on
  a tier axis and run the ``grad_aggregate`` kernel per ≥2-D leaf
  (numerator/denominator with the cohort form's separate ``w·n_part``
  denominator weights). Its fused reduction reorders the tier-axis sum,
  so this path is parity-tested to tolerance (not bitwise) against
  ``aggregation.finalize``; scalar-denominator leaves (1-D, router)
  keep the sequential formula leaf-wise. Reported ``"pallas"``.
- structured fleets (any cohort with a real width slice) run EVERY leaf
  through the prefix-block ``structured_scatter`` kernel (DESIGN.md
  §15): each tier's sub-shaped upload is a static contiguous prefix
  block of the leaf's 2-D view, and the kernel fuses numerator scatter,
  dense coverage-counted denominator and the final divide into one
  VMEM pass per leaf, accumulating in cohort order — BITWISE equal to
  the sequential ``scatter_accumulate`` chain (masked cohorts ride the
  same tier axis as full-width blocks). Reported ``"pallas_structured"``.
  A width=1.0 fleet has identity slices, no real slicing, and takes the
  masked path — bit-identical to it by construction.

Host phases
-----------
Each chunk call runs four host phases, each under a
``jax.profiler.TraceAnnotation`` that a profile (XProf, Perfetto) shows
on the calling thread beside the device's ops: ``fl.engine.masks``
(the numpy participation, deadline and fault replay),
``fl.engine.inputs`` (building the chunk's ``xs`` on the host in its
final dtypes and moving it in one transfer, the first call's carry
copy), ``fl.engine.dispatch`` (the jitted
chunk's launch) and ``fl.engine.sync`` (the state hand-back, the
chunk's one ``device_get`` and the record loop). The spans are always
on; with no profiler running the four cost a few microseconds a call.

Use it via ``simulate(scenario, rounds, engine="scan", chunk_rounds=N)``
(``core/scenario.py``) — the async and per-client runtimes fall back to
the eager loop — or construct it directly around a ``CohortFLServer``.
``benchmarks/fl_bench.py`` ``fl/engine_*`` rows measure ≥5× rounds/sec
over the eager cohort loop at 256 clients / 4 plans / 50 rounds on
XLA:CPU. On a TPU v5e the host phases above take most of a 100 000-client
chunk call (PERF.md §5).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.aggregation import (accumulate_cohort, finalize,
                                    scatter_accumulate, zeros_like_acc)
from repro.core.faults import (availability_mask, corrupt_mask,
                               corrupt_seq_mask, dropout_mask)
from repro.core.federated import (AsyncFLServer, CohortFLServer, _apply_fns,
                                  _guard_cov_active, _init_cohort_ef,
                                  _init_edge_ef, _local_param_struct,
                                  cohort_step_fn, fault_cohort_step_fn,
                                  window_groups)
from repro.core.schedule import materialize_windows
from repro.core.topology import EdgeCohort, scatter_part

AGG_BACKENDS = ("sequential", "pallas")


def _not_scannable(server) -> str | None:
    """Why ``server`` cannot run under the scan engine (None if it can)."""
    if not isinstance(server, CohortFLServer):
        return (f"{type(server).__name__} is not cohort-vectorized; the "
                "scan engine compiles CohortFLServer rounds only (the "
                "async runtime's buffered windows compile through "
                "WindowScanEngine instead, DESIGN.md §14; the per-client "
                "loop stays eager)")
    return None


@dataclass
class ScanEngine:
    """Compiles chunks of ``CohortFLServer`` rounds into one scanned,
    donated-buffer program. The server object stays the source of truth:
    the engine reads its fleet/policies, advances its ``params`` /
    ``opt_state`` / ``step`` / EF buffers, and appends eager-schema
    records to its ``history`` — ``run()`` is a drop-in replacement for
    R ``server.round()`` calls (bit-identical with the default backend).

    ``chunk_rounds=0`` compiles the whole requested run as one chunk;
    any other value bounds program length (metrics are synced and
    records materialized once per chunk). Each distinct chunk length
    compiles once and is cached by jit, so prefer chunk sizes that
    divide the round budget.
    """
    server: CohortFLServer
    chunk_rounds: int = 0
    agg: str = "sequential"
    chunks_run: int = field(default=0, init=False)
    rounds_run: int = field(default=0, init=False)
    # the last carry THIS engine produced: state it is allowed to donate
    _last_out: tuple | None = field(default=None, init=False, repr=False)

    def __post_init__(self):
        reason = _not_scannable(self.server)
        if reason:
            raise TypeError(reason)
        if self.agg not in AGG_BACKENDS:
            raise ValueError(f"agg must be one of {AGG_BACKENDS}, got {self.agg!r}")
        if self.chunk_rounds < 0:
            raise ValueError("chunk_rounds must be >= 0 (0 = one chunk per run)")
        srv = self.server
        # hierarchical fleets (DESIGN.md §16): every cohort is an edge
        # grid — the step is the cohort step vmapped over the edge axis
        # (the same program the eager reference dispatches), batches are
        # (E, cap, n, ...), and the combine chains plans x edges in
        # fixed order. The fused pallas backends have no edge axis, so
        # topology runs keep the sequential (bitwise) aggregation.
        self._topology = (len(srv.cohorts) > 0
                          and isinstance(srv.cohorts[0], EdgeCohort))
        if self._topology and self.agg != "sequential":
            raise ValueError(
                "topology fleets aggregate per (plan, edge) partial — "
                "the fused pallas backends have no edge axis; use "
                "agg='sequential'")
        # fault layer (DESIGN.md §17): upload corruption + defenses swap
        # each cohort's step for its fault twin (per-client branches with
        # the inject->guard->clip pipeline); availability/dropout faults
        # only reshape the host-precomputed masks. The fused pallas
        # backends carry no coverage column, so upload faults keep the
        # sequential (bitwise) aggregation, like topology fleets do.
        self._fault_uploads = (srv.faults is not None
                               and srv.faults.touches_uploads)
        self._guard_cov = _guard_cov_active(srv.faults)
        if self._fault_uploads and self.agg != "sequential":
            raise ValueError(
                "upload corruption/defenses aggregate with per-coordinate "
                "coverage denominators — the fused pallas backends have "
                "no coverage column; use agg='sequential'")
        if self._fault_uploads:
            self._steps = [fault_cohort_step_fn(
                srv.model.loss_fn, c.plan, srv.mode, srv.local_steps,
                srv.local_lr, srv.upload_quant, srv.faults)
                for c in srv.cohorts]
        else:
            self._steps = [cohort_step_fn(srv.model.loss_fn, c.plan,
                                          srv.mode, srv.local_steps,
                                          srv.local_lr, srv.upload_quant)
                           for c in srv.cohorts]
        if self._topology:
            self._steps = [jax.vmap(s, in_axes=(None, 0, 0, 0))
                           for s in self._steps]
        self._n_batch = [next(iter(c.data.values()))
                         .shape[2 if self._topology else 1]
                         for c in srv.cohorts]
        # each cohort's static columns of the chunk's packed per-client
        # inputs: its clients, or its grid's rows under a topology
        ends = np.cumsum([c.cap if self._topology else c.size
                          for c in srv.cohorts]).tolist()
        self._cols = [slice(a, b) for a, b in zip([0] + ends[:-1], ends)]
        # structured (width-sliced) cohorts, DESIGN.md §13: per-cohort
        # slice specs (None = masked plan) drive the in-body scatter, and
        # EF carries are allocated at each cohort's LOCAL model shapes
        self._specs = [srv.cohort_spec(ci) for ci in range(len(srv.cohorts))]
        self._local_structs = [_local_param_struct(srv.params, c.plan)
                               for c in srv.cohorts]
        self._any_structured = srv.any_structured
        # a width=1.0 plan is structured but slices nothing (identity
        # spec): only REAL slices route agg="pallas" to the prefix-block
        # kernel; identity-spec fleets keep the masked kernel path and
        # stay bit-identical to it (DESIGN.md §15)
        self._any_sliced = any(s is not None and not s.is_identity
                               for s in self._specs)
        # Eq. (1) per-client constants: host float64 for the drop masks
        # (bit-identical to the eager comparison); f32 device copies for
        # the in-program wall max and byte sums, so those two RECORD
        # fields carry f32 rounding vs the eager path's float64 host
        # arithmetic (asserted approx, not equal, in test_engine.py)
        self._times = [srv.cohort_times(ci, nb)
                       for ci, nb in enumerate(self._n_batch)]
        self._T_dev = [jnp.asarray(t["T"], jnp.float32) for t in self._times]
        self._payload_dev = [jnp.asarray(t["payload_bytes"], jnp.float32)
                             for t in self._times]
        # the raw twin of the jitted apply the eager round dispatches
        _, self._apply = _apply_fns(srv.optimizer, srv.mode, srv.server_lr)
        self._chunk = jax.jit(self._chunk_fn, donate_argnums=(0,))

    @property
    def agg_backend(self) -> str:
        """The aggregation backend this engine ACTUALLY runs (the
        observable the ``agg=`` knob maps to): ``"sequential"``, the
        masked ``"pallas"`` kernel, or the prefix-block
        ``"pallas_structured"`` kernel for width-sliced fleets."""
        if self.agg != "pallas":
            return "sequential"
        return "pallas_structured" if self._any_sliced else "pallas"

    # ------------------------------------------------------------ device

    def _aggregate_sequential(self, params, per_cohort):
        """The eager path's aggregation, replayed in cohort order:
        zero-participation cohorts contribute exact zeros (the eager loop
        skips them; adding 0.0 to a finite f32 accumulator is bitwise
        identity, property-tested). Structured cohorts scatter their
        sub-shaped update into the prefix block their slice covers,
        exactly like the eager round's ``scatter_accumulate`` call."""
        acc = zeros_like_acc(params, dense_den=(self._any_structured
                                                or self._guard_cov))
        for ci, (g_sum, masks, weight, count, cov) in enumerate(per_cohort):
            if self._topology:
                # hub combine (DESIGN.md §16): chain the per-edge partial
                # accumulators in fixed edge order — the same chain the
                # eager grid branch runs, so the result is bitwise equal
                # by construction; empty edges add exact zeros
                for e in range(self.server.cohorts[ci].n_edges):
                    acc = scatter_accumulate(
                        acc, jax.tree.map(lambda t: t[e], g_sum),
                        jax.tree.map(lambda t: t[e], masks),
                        self._specs[ci], jnp.float32(weight), count[e])
                continue
            acc = scatter_accumulate(acc, g_sum, masks, self._specs[ci],
                                     jnp.float32(weight), count, cov=cov)
        return finalize(acc)

    def _aggregate_pallas_structured(self, params, per_cohort):
        """Prefix-block fused aggregation (DESIGN.md §15): EVERY leaf
        runs the ``structured_scatter`` kernel — each cohort's sub-shaped
        (update_sum, masks) is a static prefix block of the leaf's 2-D
        view, masked cohorts ride the same tier axis as full-width
        blocks, and numerator scatter, dense denominator and divide fuse
        into one VMEM pass per leaf. Accumulation order and op shapes
        replay ``scatter_accumulate`` -> ``finalize`` exactly, so this
        backend is BITWISE, not parity (pinned in test_structured.py).

        Leaves whose (global shape, per-tier local shapes, per-tier
        mask kinds) signature repeats — the paper MLP's hidden layers
        and their biases — are STACKED and aggregated in one batched
        kernel call: the round body's aggregation cost is XLA op
        dispatch, not bytes, and batching is what puts this backend
        ahead of the sequential scatter (fl/submodel_pallas_* rows)."""
        from repro.kernels.structured_scatter.ops import (
            structured_scatter, structured_scatter_batched)
        leaves_p, treedef = jax.tree_util.tree_flatten(params)
        leaves_g = [jax.tree.leaves(g) for (g, _, _, _, _) in per_cohort]
        leaves_m = [jax.tree.leaves(m) for (_, m, _, _, _) in per_cohort]
        wn = jnp.asarray([w for (_, _, w, _, _) in per_cohort], jnp.float32)
        # the denominator column rounds w·n_part one multiply early,
        # exactly like scatter_accumulate's ``m * (weight * count)``
        wd = jnp.stack([jnp.float32(w) * c
                        for (_, _, w, c, _) in per_cohort])
        groups: dict = {}
        for li, p in enumerate(leaves_p):
            sig = (tuple(p.shape),
                   tuple(tuple(lg[li].shape) for lg in leaves_g),
                   tuple(getattr(lm[li], "ndim", 0) == 0
                         for lm in leaves_m))
            groups.setdefault(sig, []).append(li)
        out: list = [None] * len(leaves_p)
        for (shape, _locals, _mkinds), lis in groups.items():
            if len(lis) == 1:
                li = lis[0]
                out[li] = structured_scatter(
                    [lg[li] for lg in leaves_g],
                    [lm[li] for lm in leaves_m],
                    wn, wd, out_shape=shape)
                continue
            gs = [jnp.stack([lg[li] for li in lis]) for lg in leaves_g]
            ms = [jnp.stack([lm[li] for li in lis]) for lm in leaves_m]
            res = structured_scatter_batched(gs, ms, wn, wd,
                                             out_shape=shape)
            for j, li in enumerate(lis):
                out[li] = res[j]
        return jax.tree_util.tree_unflatten(treedef, out)

    def _aggregate_pallas(self, params, per_cohort):
        """Fused-kernel aggregation: structured fleets take the
        prefix-block kernel (bitwise); masked fleets stack the cohorts
        on a tier axis and run ``grad_aggregate`` once per ≥2-D leaf
        (numerator weights ``w``, denominator weights ``w·n_part`` — the
        cohort accumulator form). Scalar-denominator leaves (1-D params,
        excluded ≥2-D leaves have broadcast masks and still take the
        kernel) fall back to the sequential formula leaf-wise."""
        if self._any_sliced:
            return self._aggregate_pallas_structured(params, per_cohort)
        from repro.kernels.grad_aggregate import grad_aggregate
        leaves_p, treedef = jax.tree_util.tree_flatten(params)
        leaves_g = [jax.tree.leaves(g) for (g, _, _, _, _) in per_cohort]
        leaves_m = [jax.tree.leaves(m) for (_, m, _, _, _) in per_cohort]
        wn = jnp.asarray([w for (_, _, w, _, _) in per_cohort], jnp.float32)
        wd = jnp.stack([jnp.float32(w) * c
                        for (_, _, w, c, _) in per_cohort])
        out = []
        for li, p in enumerate(leaves_p):
            g_t = [lg[li] for lg in leaves_g]
            m_t = [lm[li] for lm in leaves_m]
            if p.ndim >= 2:
                out.append(grad_aggregate(jnp.stack(g_t), jnp.stack(m_t),
                                          wn, w_den=wd))
            else:
                # leaf-wise replay of the reference chain, so the
                # aggregation formula lives in aggregation.py, not here
                acc = (jnp.zeros(p.shape, jnp.float32),
                       jnp.zeros((), jnp.float32))
                for t, (_, _, w, count, _) in enumerate(per_cohort):
                    acc = accumulate_cohort(acc, g_t[t], m_t[t],
                                            jnp.float32(w), count)
                out.append(finalize(acc))
        return jax.tree_util.tree_unflatten(treedef, out)

    def _round_body(self, carry, x, datas):
        """One federated round, fused: the eager round's cohort loop with
        an optimization barrier standing in for each dispatch boundary."""
        srv = self.server
        params, opt_state, efs = carry
        per_cohort, new_efs = [], []
        loss_sum = jnp.float32(0.0)
        wall = jnp.float32(-np.inf)
        up_bytes = jnp.float32(0.0)
        n_part = jnp.float32(0.0)
        for ci, step in enumerate(self._steps):
            part = x["part"][ci]
            ef = efs[ci]
            if srv.upload_quant is not None and not srv.error_feedback:
                # the eager path re-zeros the residuals every dispatch
                # when feedback is off; recreate them in-program (at the
                # cohort's LOCAL shapes — sub-sized for structured plans)
                c = srv.cohorts[ci]
                ef = (_init_edge_ef(c.n_edges, c.cap,
                                    self._local_structs[ci])
                      if self._topology
                      else _init_cohort_ef(c.size, self._local_structs[ci]))
            cov = None
            if self._fault_uploads:
                g_sum, masks, cov, l_sum, new_ef = (
                    jax.lax.optimization_barrier(
                        step(params, datas[ci], part, ef,
                             x["corrupt"][ci], x["uid"][ci])))
            else:
                g_sum, masks, l_sum, new_ef = jax.lax.optimization_barrier(
                    step(params, datas[ci], part, ef))
            new_efs.append(new_ef if srv.error_feedback else efs[ci])
            if self._topology:
                # topology round: part is the (E, cap) grid, l_sum is the
                # (E,) per-edge stack. The loss chain replays the eager
                # grid branch's per-edge adds in edge order; empty edges
                # add exact zeros (bitwise identity). Wall/bytes/counts
                # are computed HOST-side from the flat masks (float64,
                # exactly the eager expressions) in _sync.
                per_cohort.append((g_sum, masks,
                                   srv.cohorts[ci].plan.weight,
                                   x["count"][ci], None))
                for e in range(srv.cohorts[ci].n_edges):
                    loss_sum = loss_sum + l_sum[e]
                continue
            per_cohort.append((g_sum, masks, srv.cohorts[ci].plan.weight,
                               jnp.sum(part), cov))
            loss_sum = loss_sum + l_sum
            # crashed clients burn wall-clock but upload nothing: the wall
            # maxes over the pre-dropout masks (``wpart``, present only
            # under a FaultPolicy), bytes/counts over the active ones
            wp = x["wpart"][ci] if "wpart" in x else part
            wall = jnp.maximum(wall, jnp.max(
                jnp.where(wp > 0, self._T_dev[ci], -np.inf)))
            up_bytes = up_bytes + jnp.dot(part, self._payload_dev[ci])
            n_part = n_part + jnp.sum(part)

        agg = (self._aggregate_pallas(params, per_cohort)
               if self.agg == "pallas"
               else self._aggregate_sequential(params, per_cohort))
        # barriers bracket the apply exactly like its eager jit boundary,
        # so the update subgraph compiles identically in both paths
        agg = jax.lax.optimization_barrier(agg)
        new_params, new_opt = jax.lax.optimization_barrier(
            self._apply(agg, opt_state, params, x["step"]))
        has = x["has"]
        params = jax.tree.map(lambda o, n: jnp.where(has, n, o),
                              params, new_params)
        opt_state = jax.tree.map(lambda o, n: jnp.where(has, n, o),
                                 opt_state, new_opt)
        metrics = ({"loss_sum": loss_sum} if self._topology
                   else {"loss_sum": loss_sum, "wall": wall,
                         "upload_bytes": up_bytes, "n_participants": n_part})
        return (params, opt_state, tuple(new_efs)), metrics

    def _chunk_fn(self, carry, xs, datas):
        # the per-client inputs arrive packed across cohorts, one host
        # transfer each (_stage_inputs); the rounds read them per cohort
        xs = dict(xs)
        for k in ("part", "wpart", "corrupt", "uid"):
            if k in xs:
                xs[k] = tuple(xs[k][..., col] for col in self._cols)
        if "count" in xs:
            xs["count"] = tuple(xs["count"][..., ci]
                                for ci in range(len(self._cols)))
        return jax.lax.scan(
            functools.partial(self._round_body, datas=datas), carry, xs)

    # -------------------------------------------------------------- host

    @functools.partial(jax.profiler.annotate_function,
                       name="fl.engine.masks")
    def _host_masks(self, R: int, participation=None):
        """The chunk's stacked participation: replay the eager path's
        per-round ``default_rng([seed, step])`` sampling, float64
        deadline comparison, and (under a FaultPolicy) the stateless
        availability/dropout/corruption draws, entirely on host — in the
        eager round's exact order: sample -> availability -> deadline
        drop -> mid-round crash. Returns per-round lists of ACTIVE masks
        (what uploads), pre-crash masks (what burns wall-clock),
        deadline-drop counts, crash counts, and corrupted-upload masks
        (active rows only — an inactive row must never carry injected
        non-finites into the participation sum)."""
        srv = self.server
        flt = srv.faults
        n_total = srv.n_clients
        parts, wparts, dropped, dropouts, corrs = [], [], [], [], []
        for r in range(R):
            step = srv.step + r
            rng = np.random.default_rng([srv.seed, step])
            sampled = (srv._sample_participation(rng)
                       if participation is None
                       else [np.asarray(p, bool) for p in participation[r]])
            if flt is not None:
                avail = availability_mask(flt, n_total, step)
                drops = dropout_mask(flt, n_total, step)
                corr = corrupt_mask(flt, n_total, step)
            n_dropped, n_do = 0, 0
            cur, curw, curc = [], [], []
            off = 0
            for ci in range(len(srv.cohorts)):
                off0, off = off, off + srv.cohorts[ci].size
                part = np.asarray(sampled[ci], bool).copy()
                if flt is not None:
                    part &= avail[off0:off]
                if srv.straggler == "drop":
                    late = self._times[ci]["T"] > srv.deadline
                    n_dropped += int(np.sum(part & late))
                    part &= ~late
                active = part
                if flt is not None and flt.dropout_rate > 0.0:
                    crashed = part & drops[off0:off]
                    n_do += int(crashed.sum())
                    active = part & ~crashed
                curw.append(part)
                cur.append(active)
                if self._fault_uploads:
                    curc.append(corr[off0:off] & active)
            parts.append(cur)
            wparts.append(curw)
            dropped.append(n_dropped)
            dropouts.append(n_do)
            corrs.append(curc)
        return parts, wparts, dropped, dropouts, corrs

    def _run_chunk(self, R: int, participation=None) -> list[dict]:
        step0 = self.server.step
        masks = self._host_masks(R, participation)
        with jax.profiler.TraceAnnotation("fl.engine.inputs"):
            carry, xs, datas = self._stage_inputs(step0, R, masks)
        with jax.profiler.TraceAnnotation("fl.engine.dispatch"):
            out, metrics = self._chunk(carry, xs, datas)
        with jax.profiler.TraceAnnotation("fl.engine.sync"):
            return self._sync(step0, R, out, metrics, masks)

    def _stage_inputs(self, step0: int, R: int, masks):
        """The chunk's arguments from its ``_host_masks``: the stacked
        per-round inputs ``xs`` on the device, the (donatable) carry and
        the cohorts' data. ``xs`` is built on the host in the dtypes the
        program takes (bool masks cast to float32 exactly), each
        per-client input packed across cohorts into one array whose last
        axis ``_chunk_fn`` slices by ``self._cols``, and moved in one
        ``device_put``: every leaf is a host-to-device round trip of its
        own, so the leaf count sets this phase's time (PERF.md §5)."""
        srv = self.server
        parts, wparts, _, _, corrs = masks

        def packed(rows):
            return np.stack([np.concatenate(rows[r], axis=-1)
                             for r in range(R)]).astype(np.float32)

        xs = {
            "step": np.arange(step0, step0 + R, dtype=np.int32),
            "has": np.array([any(p.any() for p in parts[r])
                             for r in range(R)], bool),
        }
        if srv.faults is not None and not self._topology:
            xs["wpart"] = packed(wparts)
        if self._fault_uploads:
            xs["corrupt"] = packed(corrs)
            # per-upload uid = step * n_clients + flat client index — the
            # eager fault dispatch's exact key, so the element-subset
            # corruption PRNG draws identically in both paths
            n_total = srv.n_clients
            xs["uid"] = ((step0 + np.arange(R))[:, None] * n_total
                         + np.arange(n_total)).astype(np.int32)
        placement = None
        if self._topology:
            # grid xs (DESIGN.md §16): the flat sampled masks scattered
            # into each cohort's (E, cap) float32 grid, the grids side by
            # side as (E, sum cap), plus per-edge participant counts
            # (exact small ints) as (E, cohorts). Under a mesh both are
            # placed shard-aligned with the cohort data: rounds
            # replicated, edges split on the "data" axis.
            xs["part"] = packed([[scatter_part(c, parts[r][ci])
                                  for ci, c in enumerate(srv.cohorts)]
                                 for r in range(R)])
            xs["count"] = packed([[np.bincount(c.edge_index[parts[r][ci]],
                                               minlength=c.n_edges)[:, None]
                                   for ci, c in enumerate(srv.cohorts)]
                                  for r in range(R)])
            if srv.mesh is not None:
                sh = jax.sharding.NamedSharding(
                    srv.mesh, jax.sharding.PartitionSpec(None, "data"))
                placement = {k: (sh if k in ("part", "count") else None)
                             for k in xs}
        else:
            xs["part"] = packed(parts)
        xs = jax.device_put(xs, placement)
        carry = (srv.params, srv.opt_state, self._ef_carry())
        if not self._owns(carry):
            # the carry is donated: never eat buffers the caller may still
            # hold (e.g. the params pytree a paired eager run shares) —
            # copy once, then chunks donate engine-produced state freely
            carry = jax.tree.map(jnp.array, carry)
        datas = tuple(c.data for c in srv.cohorts)
        return carry, xs, datas

    def _sync(self, step0: int, R: int, out, metrics, masks) -> list[dict]:
        """Hand the chunk's state back to the server, sync its metrics
        to host once, and append the eager-schema records."""
        srv = self.server
        parts, wparts, dropped, dropouts, corrs = masks
        params, opt_state, efs = out
        self._last_out = out
        srv.params, srv.opt_state = params, opt_state
        srv.step = step0 + R
        if srv.upload_quant is not None and srv.error_feedback:
            for c, ef in zip(srv.cohorts, efs):
                c.ef_buffer = ef
        # the chunk's single device->host sync
        m = jax.device_get(metrics)
        recs = []
        for r in range(R):
            if self._topology:
                # Eq. (1) record fields host-side, float64 — verbatim the
                # eager round's expressions over the same flat masks, so
                # topology records match the eager path EXACTLY (the flat
                # engine's in-program f32 wall/bytes are approximate).
                # Wall maxes over the pre-crash masks, bytes/counts over
                # the active ones, exactly like the eager fault round.
                n_p, wall, up = 0, 0.0, 0.0
                for ci, p in enumerate(parts[r]):
                    wp = wparts[r][ci]
                    if wp.any():
                        wall = max(wall,
                                   float(self._times[ci]["T"][wp].max()))
                    if p.any():
                        n_p += int(p.sum())
                        up += float(
                            self._times[ci]["payload_bytes"][p].sum())
            else:
                n_p = int(m["n_participants"][r])
                # the in-program wall is -inf when nothing ran (it can be
                # finite with n_p == 0: crashed clients burn wall-clock)
                wall = float(m["wall"][r])
                wall = wall if np.isfinite(wall) else 0.0
                up = float(m["upload_bytes"][r])
            rec = {
                "step": step0 + r + 1,
                # a zero-participant round is a graceful no-op: loss None
                # (never a NaN sentinel that poisons downstream means)
                "loss": (float(m["loss_sum"][r]) / n_p if n_p else None),
                "n_participants": n_p,
                "n_dropped": dropped[r],
                "round_wall_time": (
                    srv.deadline if srv.straggler == "drop" and dropped[r]
                    else wall),
                "total_upload_bytes": up,
            }
            if srv.faults is not None:
                rec["n_dropouts"] = dropouts[r]
                rec["n_corrupt"] = (int(np.sum([c.sum() for c in corrs[r]]))
                                    if self._fault_uploads else 0)
            srv.history.append(rec)
            recs.append(rec)
        self.chunks_run += 1
        self.rounds_run += R
        return recs

    def _owns(self, carry) -> bool:
        """True iff every array in ``carry`` came out of this engine's
        previous chunk (leaf-identity check), making it safe to donate."""
        if self._last_out is None:
            return False
        prev = jax.tree.leaves(self._last_out)
        cur = jax.tree.leaves(carry)
        return len(prev) == len(cur) and all(a is b
                                             for a, b in zip(prev, cur))

    def _ef_carry(self) -> tuple:
        """Per-cohort EF residuals for the scan carry. Real (stacked,
        lazily zero-initialized) buffers only when upload quantization
        with error feedback is on; otherwise leafless placeholders, so
        the donated carry stays minimal. Structured cohorts carry
        SUB-shaped buffers (their uploads live at the sliced shapes) —
        each cohort's donated sub-buffer rides the scan like the global
        params do."""
        srv = self.server
        if srv.upload_quant is None or not srv.error_feedback:
            return tuple(() for _ in srv.cohorts)
        if self._topology:
            from repro.core.topology import edge_sharding
            out = []
            for ci, c in enumerate(srv.cohorts):
                ef = c.ef_buffer
                if ef is None:
                    ef = _init_edge_ef(c.n_edges, c.cap,
                                       self._local_structs[ci])
                    if srv.mesh is not None:
                        ef = jax.device_put(ef, edge_sharding(srv.mesh))
                out.append(ef)
            return tuple(out)
        return tuple(c.ef_buffer if c.ef_buffer is not None
                     else _init_cohort_ef(c.size, self._local_structs[ci])
                     for ci, c in enumerate(srv.cohorts))

    def run(self, rounds: int, participation=None) -> list[dict]:
        """Advance the server ``rounds`` federated rounds through the
        compiled scan, in chunks of ``chunk_rounds`` (0 = one chunk).
        ``participation`` (optional, tests): one list of per-cohort bool
        masks PER ROUND, overriding the sampled participation exactly
        like ``CohortFLServer.round(participation=...)``. Returns the
        new history records (also appended to ``server.history``)."""
        if rounds < 1:
            raise ValueError(f"rounds must be >= 1, got {rounds}")
        if participation is not None and len(participation) != rounds:
            raise ValueError(f"participation pins {len(participation)} "
                             f"rounds for a {rounds}-round run")
        chunk = self.chunk_rounds or rounds
        recs, done = [], 0
        while done < rounds:
            r = min(chunk, rounds - done)
            sl = (None if participation is None
                  else participation[done:done + r])
            recs += self._run_chunk(r, sl)
            done += r
        return recs


# --------------------------------------------------------------------------
# Window-scan async engine (DESIGN.md §14)
# --------------------------------------------------------------------------

@dataclass
class WindowScanEngine:
    """Compiles chunks of ``AsyncFLServer`` aggregation windows into one
    scanned, donated-buffer program (DESIGN.md §14).

    The virtual-clock schedule is fully deterministic given
    ``(times, buffer_size, seed, jitter)``, so the whole window sequence
    is host-precomputed (``schedule.materialize_windows``) as stacked
    arrays: per-window (cohort, version-lag) group masks, staleness
    discounts ``(1+s)^-a``, ring indices and apply-step metadata. The
    device program is then a ``lax.scan`` over windows with the group
    slots unrolled — each slot replays one eager group dispatch
    (``cohort_step_fn`` verbatim, an ``optimization_barrier`` standing
    in for its jit boundary) — and the bounded version store rides the
    carry as a RING of ``max observed version lag + 1`` param copies:
    version ``v`` lives at slot ``v % capacity``, group slots gather
    their trained-against params from it, and each window writes the
    freshly-applied params over the slot whose version can no longer be
    referenced. Unused group slots carry all-zero participation masks
    and contribute exact zeros to the f32 accumulators (bitwise
    identity, the same property the sync engine rests on).

    The server object stays the source of truth: after a run the engine
    writes back ``params`` / ``opt_state`` / ``version`` / the
    refcounted version store / cohort EF buffers, advances the heap
    scheduler to match, and appends eager-schema records to
    ``history`` — so engine windows and eager ``step()`` calls can be
    freely interleaved, bit-identically (pinned in
    ``tests/test_engine.py``).

    Ring capacity and per-cohort slot counts grow monotonically across
    runs (a larger-than-needed ring or an extra padded slot is a
    no-op), so repeated same-length runs on a stationary schedule reuse
    the compiled chunk instead of re-tracing. Memory is
    ``capacity x |params|`` for the ring — bounded by the fleet's speed
    spread, as in the eager version store.
    """
    server: AsyncFLServer
    chunk_windows: int = 0
    chunks_run: int = field(default=0, init=False)
    windows_run: int = field(default=0, init=False)
    # engine-produced (opt_state, efs) from the last run: safe to donate
    _last_out: tuple | None = field(default=None, init=False, repr=False)
    # monotonic compiled-shape state: version-ring capacity and per-cohort
    # unrolled group-slot counts (see class docstring)
    _cap: int = field(default=1, init=False)
    _n_slots: list = field(default=None, init=False, repr=False)

    def __post_init__(self):
        if not isinstance(self.server, AsyncFLServer):
            raise TypeError(
                f"{type(self.server).__name__} is not the async buffered "
                "runtime; the window-scan engine compiles AsyncFLServer "
                "windows only (use ScanEngine for CohortFLServer rounds)")
        if self.chunk_windows < 0:
            raise ValueError(
                "chunk_windows must be >= 0 (0 = one chunk per run)")
        srv = self.server
        # upload faults (DESIGN.md §17) swap each cohort step for its
        # fault twin; the scheduler-side dropout/retry model needs no
        # engine support at all — materialize_windows replays the heap's
        # retry-delayed arrival times element-wise by construction
        self._fault_uploads = (srv.faults is not None
                               and srv.faults.touches_uploads)
        self._guard_cov = _guard_cov_active(srv.faults)
        if self._fault_uploads:
            self._steps = [fault_cohort_step_fn(
                srv.model.loss_fn, c.plan, srv.mode, srv.local_steps,
                srv.local_lr, srv.upload_quant, srv.faults)
                for c in srv.cohorts]
        else:
            self._steps = [cohort_step_fn(srv.model.loss_fn, c.plan,
                                          srv.mode, srv.local_steps,
                                          srv.local_lr, srv.upload_quant)
                           for c in srv.cohorts]
        # per-cohort width-slice specs / local shapes, same memo the eager
        # server's dispatch path uses (shapes are static per server)
        from repro.core.federated import _memo_submodel_spec
        self._specs = [_memo_submodel_spec(srv._spec_cache, ci, srv.params,
                                           c.plan)
                       for ci, c in enumerate(srv.cohorts)]
        self._local_structs = [_local_param_struct(srv.params, c.plan)
                               for c in srv.cohorts]
        self._any_structured = srv.any_structured
        self._acc_struct = jax.tree.map(
            lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype), srv.params)
        self._n_slots = [0] * len(srv.cohorts)
        # runtime ones shaped like each cohort step's masks output: fed
        # into the chunk as a jit ARGUMENT and multiplied onto the masks
        # (exact — masks are 0/1) so every mask leaf reaching the
        # accumulate is a runtime value. Plans without pruning return
        # literal-constant masks (jnp.ones_like / scalar 1.0), and XLA's
        # algebraic simplifier folds a constant-ones multiply out of the
        # fused body — re-exposing the inexact staleness product to FMA
        # contraction and breaking bit-identity with the eager op-by-op
        # chain (DESIGN.md §14).
        self._mask_ones = []
        for ci, c in enumerate(srv.cohorts):
            ef0 = _init_cohort_ef(c.size, self._local_structs[ci])
            args = (self._acc_struct, c.data,
                    jnp.zeros(c.size, jnp.float32), ef0)
            if self._fault_uploads:
                args += (jnp.zeros(c.size, jnp.float32),
                         jnp.zeros(c.size, jnp.int32))
            out = jax.eval_shape(self._steps[ci], *args)
            self._mask_ones.append(jax.tree.map(
                lambda s: jnp.ones(s.shape, s.dtype), out[1]))
        self._mask_ones = tuple(self._mask_ones)
        _, self._apply = _apply_fns(srv.optimizer, srv.mode, srv.server_lr)
        self._chunk = jax.jit(self._chunk_fn, donate_argnums=(0,))

    @property
    def agg_backend(self) -> str:
        """The window body has no stacked-tier aggregation axis (groups
        arrive one (cohort, version) slot at a time), so the async
        engine always aggregates through the sequential scatter chain —
        reported honestly so ``engine="scan_pallas"`` on an async
        scenario is an OBSERVABLE no-op, not a silent one."""
        return "sequential"

    # ------------------------------------------------------------ device

    def _window_body(self, carry, x, datas, mask_ones):
        """One buffered aggregation window, fused: the eager ``step()``'s
        sorted (cohort, version) group loop with ring gathers standing in
        for the version-store lookups and an optimization barrier at
        every eager dispatch boundary."""
        srv = self.server
        ring, opt_state, efs = carry
        acc = zeros_like_acc(self._acc_struct,
                             dense_den=(self._any_structured
                                        or self._guard_cov))
        loss_sum = jnp.float32(0.0)
        new_efs = []
        for ci, step in enumerate(self._steps):
            ef = efs[ci]
            n_slots = x["slot"][ci].shape[0]
            for sl in range(n_slots):
                if srv.upload_quant is not None and not srv.error_feedback:
                    # the eager path re-zeros residuals on every group
                    # dispatch when feedback is off; recreate in-program
                    ef = _init_cohort_ef(srv.cohorts[ci].size,
                                         self._local_structs[ci])
                # an absent group (padded slot, count 0) is gated out by
                # lax.cond rather than run fully masked: the whole
                # step + accumulate lives in the taken branch, and the
                # skip branch passes (acc, loss, ef) through untouched —
                # bitwise-equivalent, since an all-zero participation
                # mask contributes exact zeros to a finite f32
                # accumulator (a no-op), but skipping saves the cohort
                # step's FLOPs AND any zero-buffer materialization. At
                # bench scale each window populates one of the unrolled
                # slots, so this removes ~(total slots - 1)/total of the
                # per-window compute.
                def _run(ring, acc, loss_sum, ef,
                         _ci=ci, _sl=sl, _step=step):
                    pv = jax.tree.map(lambda r: r[x["slot"][_ci][_sl]],
                                      ring)
                    cov = None
                    if self._fault_uploads:
                        g_sum, masks, cov, l_sum, new_ef = _step(
                            pv, datas[_ci], x["part"][_ci][_sl], ef,
                            x["corrupt"][_ci][_sl], x["uid"][_ci][_sl])
                    else:
                        g_sum, masks, l_sum, new_ef = _step(
                            pv, datas[_ci], x["part"][_ci][_sl], ef)
                    # exact ×1 re-anchor: keeps constant-foldable masks
                    # runtime-valued so the accumulate's FMA contraction
                    # stays on the exact 0/1-mask product (association
                    # invariant, aggregation.py / DESIGN.md §14)
                    masks = jax.tree.map(lambda m, o: m * o,
                                         masks, mask_ones[_ci])
                    acc = scatter_accumulate(
                        acc, g_sum, masks, self._specs[_ci],
                        jnp.float32(srv.cohorts[_ci].plan.weight),
                        x["count"][_ci][_sl],
                        staleness_weight=x["disc"][_ci][_sl], cov=cov)
                    return acc, loss_sum + l_sum, (
                        new_ef if srv.error_feedback else ef)

                def _skip(ring, acc, loss_sum, ef):
                    return acc, loss_sum, ef

                acc, loss_sum, ef = jax.lax.optimization_barrier(
                    jax.lax.cond(x["count"][ci][sl] > 0, _run, _skip,
                                 ring, acc, loss_sum, ef))
            new_efs.append(ef if srv.error_feedback else efs[ci])

        agg = jax.lax.optimization_barrier(finalize(acc))
        cur = jax.tree.map(lambda r: r[x["cur"]], ring)
        new_params, new_opt = jax.lax.optimization_barrier(
            self._apply(agg, opt_state, cur, x["step"]))
        # publish the new version over the ring slot whose version has
        # fallen out of reach (capacity > max observed lag)
        ring = jax.tree.map(lambda r, n: r.at[x["write"]].set(n),
                            ring, new_params)
        return (ring, new_opt, tuple(new_efs)), {"loss_sum": loss_sum}

    def _chunk_fn(self, carry, xs, datas, mask_ones):
        return jax.lax.scan(
            functools.partial(self._window_body, datas=datas,
                              mask_ones=mask_ones), carry, xs)

    # -------------------------------------------------------------- host

    def _plan_slots(self, plan):
        """Host precompute of the chunk xs: per-cohort stacked group-slot
        arrays replaying ``window_groups`` exactly — participation masks,
        version-ring indices, participant counts, and the staleness
        discount computed with the eager path's float64 expression."""
        srv = self.server
        W, C = plan.n_windows, len(srv.cohorts)
        per_win = [window_groups(srv._slots, plan.client[w],
                                 plan.upload_version[w])
                   for w in range(W)]
        for gs in per_win:
            seen = [0] * C
            for (ci, _), _rows in gs:
                seen[ci] += 1
            self._n_slots = [max(a, b) for a, b in zip(self._n_slots, seen)]
        cap = self._cap
        part = [np.zeros((W, self._n_slots[ci], c.size), np.float32)
                for ci, c in enumerate(srv.cohorts)]
        slot = [np.empty((W, self._n_slots[ci]), np.int32)
                for ci in range(C)]
        count = [np.zeros((W, self._n_slots[ci]), np.float32)
                 for ci in range(C)]
        disc = [np.ones((W, self._n_slots[ci]), np.float32)
                for ci in range(C)]
        versions = plan.version0 + np.arange(W)
        for ci in range(C):
            slot[ci][:] = (versions % cap)[:, None]     # padded: live params
        if self._fault_uploads:
            # corruption is keyed by the upload's dispatch SEQUENCE number
            # (the eager step's exact per-upload uid), replayed from the
            # plan's seq array; padded slots stay all-zero — no injection
            corrupt = [np.zeros((W, self._n_slots[ci], c.size), np.float32)
                       for ci, c in enumerate(srv.cohorts)]
            uids = [np.zeros((W, self._n_slots[ci], c.size), np.int32)
                    for ci, c in enumerate(srv.cohorts)]
        for w, gs in enumerate(per_win):
            if self._fault_uploads:
                flags = corrupt_seq_mask(srv.faults, plan.upload_seq[w])
                info = {}
                for k in range(plan.buffer_size):
                    ci, row = srv._slots[int(plan.client[w][k])]
                    info[(ci, row)] = (int(plan.upload_seq[w][k]),
                                       float(flags[k]))
            li = [0] * C
            for (ci, v), rows in gs:
                sl = li[ci]
                li[ci] += 1
                part[ci][w, sl, rows] = 1.0
                slot[ci][w, sl] = v % cap
                count[ci][w, sl] = len(rows)
                disc[ci][w, sl] = np.float32(
                    (1.0 + (int(versions[w]) - v)) ** (-srv.staleness_exp))
                if self._fault_uploads:
                    for r in rows:
                        uids[ci][w, sl, r], corrupt[ci][w, sl, r] = \
                            info[(ci, r)]
        xs = {"part": tuple(jnp.asarray(p) for p in part),
              "slot": tuple(jnp.asarray(s) for s in slot),
              "count": tuple(jnp.asarray(c) for c in count),
              "disc": tuple(jnp.asarray(d) for d in disc),
              "cur": jnp.asarray(versions % cap, jnp.int32),
              "write": jnp.asarray((versions + 1) % cap, jnp.int32),
              "step": jnp.asarray(versions, jnp.int32)}
        if self._fault_uploads:
            xs["corrupt"] = tuple(jnp.asarray(c) for c in corrupt)
            xs["uid"] = tuple(jnp.asarray(u) for u in uids)
        return xs

    def _ring_init(self):
        """The version store as a ring: every live version's params at
        slot ``version % capacity``. Freshly allocated (``.at[].set`` on
        zeros), so the ring is always engine-owned and donation-safe."""
        srv = self.server
        ring = jax.tree.map(
            lambda p: jnp.zeros((self._cap,) + tuple(p.shape), p.dtype),
            srv.params)
        for v, pv in srv._versions.items():
            ring = jax.tree.map(lambda r, x: r.at[v % self._cap].set(x),
                                ring, pv)
        return ring

    def _ef_carry(self) -> tuple:
        """Per-cohort EF residuals for the scan carry — real stacked
        buffers only under quantization + error feedback, else leafless
        placeholders (the eager path's re-zeroed residuals are recreated
        in-program)."""
        srv = self.server
        if srv.upload_quant is None or not srv.error_feedback:
            return tuple(() for _ in srv.cohorts)
        return tuple(c.ef_buffer if c.ef_buffer is not None
                     else _init_cohort_ef(c.size, self._local_structs[ci])
                     for ci, c in enumerate(srv.cohorts))

    def _owns(self, state) -> bool:
        """True iff every array in ``state`` came out of this engine's
        previous run (leaf identity), making it safe to donate."""
        if self._last_out is None:
            return False
        prev = jax.tree.leaves(self._last_out)
        cur = jax.tree.leaves(state)
        return len(prev) == len(cur) and all(a is b
                                             for a, b in zip(prev, cur))

    def run(self, n_windows: int) -> list[dict]:
        """Advance the server ``n_windows`` buffered aggregation windows
        through the compiled scan, in chunks of ``chunk_windows`` (0 =
        one chunk). Drop-in for ``n_windows`` eager ``step()`` calls:
        returns the new history records (also appended to
        ``server.history``) and leaves the server resumable by either
        path."""
        if n_windows < 1:
            raise ValueError(f"n_windows must be >= 1, got {n_windows}")
        srv = self.server
        plan = materialize_windows(srv._sched, n_windows)
        # ring reach: the largest version lag the plan reads or still owes
        # at the end, plus any older version live at entry (a client
        # mid-flight from before this run). Any capacity above that is
        # semantically identical (slot = v % cap merely relabels), so the
        # sizing adds slack against retraces: a PROBE materialization two
        # fleet rotations past the run horizon catches the schedule's
        # steady-state lag before the first compile, and the result is
        # monotonic and rounded up to the next power of two so residual
        # lag creep between runs cannot retrace the chunk
        probe = materialize_windows(
            srv._sched,
            n_windows + 2 * -(-srv.n_clients // srv._sched.buffer_size))
        init_lag = srv.version - min(srv._versions)
        need = max(self._cap, probe.max_version_lag + 1, init_lag + 1)
        self._cap = 1 << (need - 1).bit_length()
        xs_all = self._plan_slots(plan)

        opt_state, efs = srv.opt_state, self._ef_carry()
        if not self._owns((opt_state, efs)):
            # donated carry: never eat buffers the caller may still hold
            opt_state, efs = jax.tree.map(jnp.array, (opt_state, efs))
        carry = (self._ring_init(), opt_state, efs)
        datas = tuple(c.data for c in srv.cohorts)

        K = plan.buffer_size
        chunk = self.chunk_windows or n_windows
        recs, done = [], 0
        while done < n_windows:
            Wc = min(chunk, n_windows - done)
            xs = jax.tree.map(lambda a: a[done:done + Wc], xs_all)
            carry, metrics = self._chunk(carry, xs, datas, self._mask_ones)
            # the chunk's single device->host sync
            m = jax.device_get(metrics)
            for r in range(Wc):
                w = done + r
                stale = plan.staleness[w]
                rec = {
                    "step": plan.version0 + w + 1,
                    "t": float(plan.t[w]),
                    "loss": float(m["loss_sum"][r]) / K,
                    "n_updates": K,
                    "staleness_mean": float(np.mean(stale)),
                    "staleness_max": int(stale.max()),
                    "n_versions_live": int(plan.n_versions_live[w]),
                    "total_upload_bytes": sum(
                        srv._payload_bytes[int(c)] for c in plan.client[w]),
                }
                if srv.faults is not None:
                    rec["n_corrupt"] = (
                        int(corrupt_seq_mask(srv.faults,
                                             plan.upload_seq[w]).sum())
                        if self._fault_uploads else 0)
                srv.history.append(rec)
                recs.append(rec)
            done += Wc
            self.chunks_run += 1
        self.windows_run += n_windows

        # write the advanced state back onto the server so eager step()
        # calls (or another engine run) continue bit-identically
        ring, opt_state, efs = carry
        v_end = plan.version0 + n_windows
        srv.params = jax.tree.map(lambda r: r[v_end % self._cap], ring)
        srv.opt_state = opt_state
        srv.version = v_end
        uniq, counts = np.unique(plan.end_version, return_counts=True)
        srv._versions = {int(v): (srv.params if int(v) == v_end else
                                  jax.tree.map(
                                      lambda r: r[int(v) % self._cap], ring))
                         for v in uniq}
        srv._refs = {int(v): int(c) for v, c in zip(uniq, counts)}
        srv._sched.trace(n_windows)         # advance the heap to match
        if srv.upload_quant is not None and srv.error_feedback:
            for cohort, ef in zip(srv.cohorts, efs):
                cohort.ef_buffer = ef
        self._last_out = (opt_state, efs)
        return recs


def simulate_rounds(server, rounds: int, *, chunk_rounds: int = 0,
                    agg: str = "sequential") -> list[dict]:
    """Convenience: run ``rounds`` on ``server`` through a fresh
    :class:`ScanEngine` / :class:`WindowScanEngine` (falls back to eager
    ``round()`` calls when the server is neither cohort-vectorized nor
    async). Returns the new history records."""
    if isinstance(server, AsyncFLServer):
        return WindowScanEngine(server,
                                chunk_windows=chunk_rounds).run(rounds)
    if _not_scannable(server):
        return [server.round() for _ in range(rounds)]
    return ScanEngine(server, chunk_rounds=chunk_rounds,
                      agg=agg).run(rounds)
