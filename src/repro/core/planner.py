"""MOAPI v2 query planner: ``Session.plan(queries) -> ExecutablePlan``.

Planning is a first-class, cached, QBS-informed step instead of a side
effect of execution (the paper's query-aware claim, §4.3 / Alg. 3,
applied to execution parameters; TAIJI-style declarative interface over
the lake). The pipeline per batch:

  query ASTs
    -> ``Q.normalize``      (flatten/dedupe, explicit V.K postfilter)
    -> ``Q.signature``      (stable archetype: shape+types+attrs+k)
    -> ``LogicalPlan``      (per-query fragment: engine vs scalar path,
                             V.K job layout, KNN group structure)
    -> ``ExecutablePlan``   (bound to this batch's constants; executes
                             through ``HybridEngine`` + scalar fallback)

Caching: ``Session`` keeps one ``LogicalPlan`` per (batch signature
tuple, loop kind, platform build id). A repeated query *shape* — the
common case in serving, where templates differ only in constants — skips
plannability analysis, walk/job-layout derivation, and KNN grouping, and
reuses the same compiled-shape universe (identical group sizes -> jit
cache hits instead of re-tracing). ``prepare()`` bumps the platform
build id, invalidating every cached plan.

Write semantics (async ingest): a cached plan stays VALID across
``MQRLD.append`` — the delta region is execution state, not plan
structure, and ``execute()`` unions whatever un-folded rows exist at its
write epoch (the engine re-syncs per call). ``fold()``/``prepare()``
bump ``build_id``, which invalidates every cached plan exactly like a
rebuild. ``explain()`` reports the delta epoch / row / tile counts the
next execution would see.

QBS-driven plan parameters: each KNN group carries a
``knn_archetype`` key; at execute time the plan seeds the group's beam
widths from ``QBSTable.convergence_width`` (p90 of per-query converged
widths from past runs of the archetype — the device loop seeds its
straggler round width / round budget, the host loop its initial
doubling beam, the sharded loop its per-shard straggler width; see
``HybridEngine._run_jobs``) and records the achieved widths back — the
query-aware beam seeding item from the ROADMAP. Seeding is
delta-aware: while un-folded delta rows exist, lookups and recordings
use the ``:delta`` variant of the archetype, so delta-widened scans
never inflate the base seed that post-fold batches read. Seeds shift
work between beam rounds only; exactness never depends on them.

Sharded topology (``Session(shards=N)``): the device loop executes
through the T-sharded multi-device path; plans cache per (batch
signature, loop kind, SHARD TOPOLOGY, build id) — each topology has
its own compiled-shape universe and QBS archetype keys (``:sN``) —
and ``explain()`` reports the topology. Results are identical at
every shard count.

Calibrated cost-model planning (``repro.core.cost``): when the
platform carries a calibrated ``cost_model`` (fitted by
``MQRLD.calibrate()`` / loaded from the snapshot's
``cost_model.json``), ``Session.plan`` chooses the LOOP KIND and SHARD
TOPOLOGY by predicted cost instead of the session defaults, and
``_seeds`` keeps a QBS beam seed only when the model predicts it
cheaper than the unseeded loop (the beam/round budget choice).
Predictions come from ``cost.knn_plan_features`` over the engine's
analytic layout quantities (tiles, cap, dim, beam, precision, shard
count) — the SAME builder the engine records observed stage times
against, so predicted and observed stay comparable. Contract:

  * the model is ADVISORY — it only ever moves a batch between exact
    paths; results never depend on it;
  * an explicit ``plan(device_loop=...)`` argument always wins (the
    oracle/bench paths stay pinned), and a session whose topology was
    pinned explicitly (``auto_topology=False``) only chooses between
    host and its configured device topology;
  * a candidate whose stage kind is uncalibrated is skipped, and when
    the session default's own kind is uncalibrated no choice is made
    at all — a platform without ``cost_model.json`` (or with a partial
    calibration) behaves byte-identically to the fixed-threshold code;
  * every executed plan feeds observed (kind, features, seconds) stage
    samples back through ``QBSTable.record_cost`` and
    ``CostModel.maybe_refit`` — online recalibration, the same
    feedback loop as beam seeding.

EXPLAIN: ``ExecutablePlan.explain()`` returns a structured description —
per query: chosen path, signature, cache hit/miss, per-V.K beam seed and
archetype, per-V.R pruned-tile estimates from the triangle bound. With
a cost model attached, each fragment's ``knn`` entries carry a
``cost`` block {kind, predicted_s, observed_s} (observed = mean of the
QBS cost ring for that kind), ``vr`` entries carry predicted dense/tile
seconds plus the route the engine would take, and the top level carries
``cost_model`` = {calibrated, kinds, choices} where ``choices`` records
how (and whether) the loop/topology was cost-chosen for THIS plan.

The v1 entry points (``MQRLD.execute_batch``, ``serve.RetrievalServer``)
are thin wrappers over a ``Session`` and return identical results.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core import cost as costm
from repro.core import query as Q
from repro.core.engine import (EnginePlan, EngineStats, KnnGroupSpec,
                               group_job_specs, plannable)
from repro.utils.spans import span


# ---------------------------------------------------------------------------
# Logical plan (cached skeleton, constants elided)
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class FragmentPlan:
    """Plan for one query of the batch."""
    signature: str
    path: str                       # "device-loop" | "host-loop" | "scalar"
    job_slots: Tuple[int, ...]      # this query's V.K job indices


@dataclass(frozen=True)
class LogicalPlan:
    """The cached, constants-free plan skeleton for one batch archetype:
    everything ``Session.plan`` derives that depends only on query
    *shapes* (signatures), not on the constants bound per batch.
    ``shards`` records the shard topology the KNN grouping was keyed
    for (0 = unsharded) — plans cache per topology, since the sharded
    loop's QBS archetypes and compiled-shape universe differ."""
    signatures: Tuple[str, ...]
    device_loop: bool
    fragments: Tuple[FragmentPlan, ...]
    engine_idx: Tuple[int, ...]     # positions routed to the engine
    scalar_idx: Tuple[int, ...]     # positions falling back to scalar
    job_specs: Tuple[Tuple[str, int, bool], ...]   # (attr, k, masked)/job
    groups: Tuple[KnnGroupSpec, ...]
    shards: int = 0


def _collect_job_specs(q: Q.Query, ambient: bool,
                       out: List[Tuple[str, int, bool]]):
    """Mirror of ``HybridEngine._walk``'s V.K registration order over an
    engine-plannable tree, shape-only: records (attr, k, masked) per job.
    ``ambient`` is True when an enclosing And contributed a predicate
    mask (the only way a job acquires a mask in the plannable fragment)."""
    if isinstance(q, Q.VK):
        out.append((q.attr, q.k, ambient))
        return
    if isinstance(q, (Q.NE, Q.NR, Q.VR)):
        return
    if isinstance(q, Q.And):
        vks = [p for p in q.parts if isinstance(p, Q.VK)]
        preds = [p for p in q.parts if not isinstance(p, Q.VK)]
        amb = ambient or bool(preds)
        for p in preds:   # VK-free in the plannable fragment: no jobs,
            _collect_job_specs(p, ambient, out)  # kept for symmetry
        for p in vks:
            out.append((p.attr, p.k, amb))
        return
    if isinstance(q, Q.Or):
        for p in q.parts:
            _collect_job_specs(p, ambient, out)
        return
    raise TypeError(q)


def build_logical_plan(norm: Sequence[Q.Query], device_loop: bool,
                       shards: int = 0) -> LogicalPlan:
    """Derive the plan skeleton for one batch of normalized queries."""
    sigs = tuple(Q.signature(q) for q in norm)
    engine_idx, scalar_idx = [], []
    fragments: List[FragmentPlan] = []
    job_specs: List[Tuple[str, int, bool]] = []
    loop_name = "device-loop" if device_loop else "host-loop"
    for i, q in enumerate(norm):
        if plannable(q):
            engine_idx.append(i)
            n0 = len(job_specs)
            _collect_job_specs(q, False, job_specs)
            fragments.append(FragmentPlan(
                signature=sigs[i], path=loop_name,
                job_slots=tuple(range(n0, len(job_specs)))))
        else:
            scalar_idx.append(i)
            fragments.append(FragmentPlan(
                signature=sigs[i], path="scalar", job_slots=()))
    eff_shards = shards if device_loop else 0
    return LogicalPlan(
        signatures=sigs, device_loop=device_loop,
        fragments=tuple(fragments), engine_idx=tuple(engine_idx),
        scalar_idx=tuple(scalar_idx), job_specs=tuple(job_specs),
        groups=group_job_specs(tuple(job_specs), device_loop,
                               eff_shards),
        shards=eff_shards)


def _knn_group_features(eng, grp: KnnGroupSpec, device_loop: bool,
                        shards: int, beam: int, precision: str,
                        seed: Optional[int] = None) -> Tuple[float, ...]:
    """Plan-time analytic cost features for one KNN group, read off the
    engine's existing layouts (a sharded engine keeps the unsharded
    layouts too, so ANY engine can price every candidate topology
    without building candidate-topology device state). Per-shard tile
    counts are ceil(T/shards) — the strided layout's padded t_local."""
    dim = eng.vec_np[grp.attr].shape[1]
    if device_loop:
        tiles = eng.bucket_rows_dev_np.shape[0]
        cap = eng.cap_dev
        if shards:
            tiles = -(-tiles // max(1, int(shards)))
    else:
        tiles = eng.n_tiles
        cap = eng.cap
    return costm.knn_plan_features(
        device_loop=device_loop, shards=shards, g=len(grp.jobs),
        k=grp.kmax, beam=beam, tiles=tiles, cap=cap, dim=dim,
        precision=precision, seed=seed)


# ---------------------------------------------------------------------------
# Executable plan (skeleton bound to one batch's constants)
# ---------------------------------------------------------------------------
class PendingExecution:
    """Deferred epilogue of ``ExecutablePlan.execute_async()``.

    Holds the dispatched batch's device-resident state (via the
    engine's ``PendingBatch``) plus the planner-level epilogue: scalar
    fallbacks and the QBS feedback writes, all funneled into
    ``materialize()``. Idempotent — repeated calls return the same
    (results, stats) and record feedback exactly once. The ONLY device
    fences the batch ever takes after dispatch happen inside
    ``materialize()``, which is what lets a serving pipeline overlap
    this batch's device compute with other chunks' host stages."""

    __slots__ = ("_fn", "_res")

    def __init__(self, fn):
        self._fn = fn
        self._res = None

    def materialize(self) -> Tuple[List[np.ndarray], "EngineStats"]:
        if self._res is None:
            self._res = self._fn()
        return self._res


class ExecutablePlan:
    """A ``LogicalPlan`` bound to one batch of queries, ready to run.

    ``execute()`` returns (results, EngineStats) with exactly the
    contract of the v1 ``MQRLD.execute_batch``: one row array per query
    in submission order, engine fragments through ``HybridEngine`` (with
    the cached grouping and QBS beam seeds), the rest through the scalar
    executor. Achieved KNN widths are recorded back into QBS after every
    run, so later plans of the same archetype seed tighter."""

    def __init__(self, session: "Session", logical: LogicalPlan,
                 queries: Sequence[Q.Query], norm: Sequence[Q.Query],
                 cache_hit: bool, choices: Optional[dict] = None):
        self.session = session
        self.logical = logical
        self.queries = list(queries)
        self.norm = list(norm)
        self.cache_hit = cache_hit
        # loop/topology provenance for explain(): how the (device_loop,
        # shards) pair was decided — "explicit" (caller pinned the
        # loop), "default" (session config), or "cost_model" with the
        # per-candidate predictions. Recomputed on every plan() call
        # (it is per-invocation state, never cached with the skeleton).
        self.choices = choices or {"by": "default"}

    # ------------------------------------------------------------- execute
    def _seeds(self) -> Dict[str, int]:
        """Current QBS convergence seeds for this plan's KNN groups —
        looked up at execute time (not baked at plan time) so a cached
        plan keeps learning from QBS between runs. Delta-aware: while
        un-folded delta rows exist the engine records (and we look up)
        the ``:delta`` variant of each archetype, so delta-widened
        convergence widths never leak into the base seed that post-fold
        batches read (see ``engine.knn_archetype``).

        Beam/round budget by predicted cost: with a calibrated cost
        model, each group's seed is kept only when the model predicts
        the seeded widths cheaper than the defaults (a stale wide seed
        inflates the first host round / straggler width long after the
        workload tightened). Seeds shift work between beam rounds only
        — dropping one never affects results."""
        p = self.session.platform
        suffix = ":delta" if p.n_delta else ""
        seeds: Dict[str, int] = {}
        for grp in self.logical.groups:
            key = grp.archetype + suffix
            w = p.qbs.convergence_width(key)
            if w is not None:
                seeds[key] = w
        cm = getattr(p, "cost_model", None)
        if cm is not None and seeds:
            lp = self.logical
            kind = costm.knn_kind(lp.device_loop, lp.shards)
            if cm.reliable(kind):
                eng = self.session.engine(lp.shards)
                sess = self.session
                for grp in lp.groups:
                    key = grp.archetype + suffix
                    if key not in seeds:
                        continue
                    ps = cm.predict(kind, _knn_group_features(
                        eng, grp, lp.device_loop, lp.shards, sess.beam,
                        sess.precision, seed=seeds[key]))
                    pn = cm.predict(kind, _knn_group_features(
                        eng, grp, lp.device_loop, lp.shards, sess.beam,
                        sess.precision, seed=None))
                    if ps is not None and pn is not None and pn < ps:
                        seeds.pop(key)
        return seeds

    def execute(self) -> Tuple[List[np.ndarray], EngineStats]:
        lp = self.logical
        p = self.session.platform
        with span("plan.execute") as sp:
            results: List[Optional[np.ndarray]] = [None] * len(self.norm)
            if lp.engine_idx:
                eng_plan = EnginePlan(
                    device_loop=lp.device_loop, job_specs=lp.job_specs,
                    groups=lp.groups, seeds=self._seeds(),
                    shards=lp.shards, precision=self.session.precision)
                eng = self.session.engine(lp.shards)
                rows, stats = eng.execute_batch(
                    [self.norm[i] for i in lp.engine_idx], plan=eng_plan)
                for i, r in zip(lp.engine_idx, rows):
                    results[i] = r
                self.session.mp_scanned += stats.mp_scanned
                self.session.mp_rescued += stats.mp_rescued
            else:
                stats = EngineStats()
            stats.queries = len(self.norm)  # incl. scalar fallbacks (their
            for i in lp.scalar_idx:         # work is not in engine counters)
                results[i] = p.execute(self.norm[i], record=False)[0]
        stats.time_s = sp.seconds
        self._feedback(stats, cost=True)
        return results, stats  # type: ignore[return-value]

    def _feedback(self, stats: EngineStats, *, cost: bool) -> None:
        """The batch's QBS writes, in one ``plan.feedback`` span: each
        KNN group's converged width (query-aware beam seeding); with
        ``cost``, the observed per-stage times into the QBS cost rings
        and the cost model's online-recalibration chance (the
        predicted-vs-observed loop, module doc); and one representative
        AST per signature (with the batch's count) into the workload
        ring that the online re-optimization controller replays against
        candidate transforms."""
        p = self.session.platform
        with span("plan.feedback"):
            for arch, width in stats.knn_group_widths:
                p.qbs.record_convergence(arch, width)
            if cost:
                for kind, feats, secs in stats.stage_samples:
                    p.qbs.record_cost(kind, feats, secs)
                cm = getattr(p, "cost_model", None)
                if cm is not None and stats.stage_samples:
                    cm.maybe_refit(p.qbs)
            reps: Dict[str, list] = {}
            for q, frag in zip(self.norm, self.logical.fragments):
                slot = reps.setdefault(frag.signature, [q, 0])
                slot[1] += 1
            for sig, (q, cnt) in reps.items():
                p.qbs.record_workload(sig, q, cnt)

    # ------------------------------------------------------- execute_async
    def execute_async(self, *, record: bool = True) -> "PendingExecution":
        """Dispatch half of ``execute()`` for the serving pipeline.

        Stage/fence contract: this call ENQUEUES the engine fragments'
        device work (predicate masks + each KNN group's fused first
        round) and returns immediately — no host sync is taken, and the
        per-round state stays device-resident. The returned
        ``PendingExecution.materialize()`` runs the deferred epilogue:
        one explicit fence per KNN group (the (G,) active-mask read,
        whose D2H copy was started at dispatch), straggler rounds, the
        finishing walk, scalar fallbacks, and ALL QBS feedback writes
        (convergence widths + workload ring) — funneled into the
        epilogue so ring mutation happens on the stage that retires the
        chunk, never mid-overlap. Results are identical to
        ``execute()``.

        What this path deliberately does NOT record: per-stage
        wall-time cost samples (``record_cost=False`` on the engine) —
        with other chunks enqueued between dispatch and materialize, a
        stage's observed seconds include unrelated waiting and would
        poison the calibrated cost model's online refit. The serial
        ``execute()`` remains the cost model's sample source.
        ``record=False`` additionally skips convergence/workload/mp
        recording entirely — used by pipeline shape prewarming so dummy
        executions never pollute the query-aware feedback loops."""
        lp = self.logical
        p = self.session.platform
        pending = None
        with span("plan.execute") as disp:
            if lp.engine_idx:
                eng_plan = EnginePlan(
                    device_loop=lp.device_loop, job_specs=lp.job_specs,
                    groups=lp.groups, seeds=self._seeds(),
                    shards=lp.shards, precision=self.session.precision)
                eng = self.session.engine(lp.shards)
                pending = eng.execute_batch_async(
                    [self.norm[i] for i in lp.engine_idx], plan=eng_plan)

        def _materialize() -> Tuple[List[np.ndarray], EngineStats]:
            with span("plan.execute") as epi:
                results: List[Optional[np.ndarray]] = \
                    [None] * len(self.norm)
                if pending is not None:
                    rows, stats = pending.materialize()
                    for i, r in zip(lp.engine_idx, rows):
                        results[i] = r
                    if record:
                        self.session.mp_scanned += stats.mp_scanned
                        self.session.mp_rescued += stats.mp_rescued
                else:
                    stats = EngineStats()
                stats.queries = len(self.norm)
                for i in lp.scalar_idx:
                    results[i] = p.execute(self.norm[i], record=False)[0]
            stats.time_s = disp.seconds + epi.seconds
            if record:
                self._feedback(stats, cost=False)
            return results, stats  # type: ignore[return-value]

        return PendingExecution(_materialize)

    # ------------------------------------------------------------- explain
    def explain(self) -> dict:
        """Structured plan description (no execution): chosen path per
        query, cache hit/miss, per-V.K group/archetype/beam seed,
        per-V.R pruned-tile estimates from the triangle bound, and the
        un-folded delta state the execution would union in (epoch, live
        rows, host-layout tile count) — read at explain time, like the
        seeds, so a cached plan reports fresh write state."""
        lp = self.logical
        seeds = self._seeds()
        sess = self.session
        p_qbs = sess.platform.qbs
        suffix = ":delta" if sess.platform.n_delta else ""
        eng = sess.engine(lp.shards) if lp.engine_idx else None
        cm = getattr(sess.platform, "cost_model", None)
        # predicted vs observed per KNN group (None entries when the
        # model is absent / the kind is uncalibrated): predicted from
        # the same analytic features the engine records against,
        # observed = mean seconds of the kind's QBS cost ring
        kind = costm.knn_kind(lp.device_loop, lp.shards)
        grp_cost = {}
        for gi, grp in enumerate(lp.groups):
            pred = None
            if cm is not None and eng is not None:
                pred = cm.predict(kind, _knn_group_features(
                    eng, grp, lp.device_loop, lp.shards, sess.beam,
                    sess.precision,
                    seed=seeds.get(grp.archetype + suffix)))
            grp_cost[gi] = {"kind": kind, "predicted_s": pred,
                            "observed_s": p_qbs.cost_observed(kind)}
        job_of_group = {}
        for gi, grp in enumerate(lp.groups):
            for j in grp.jobs:
                job_of_group[j] = gi
        frags = []
        for frag, q in zip(lp.fragments, self.norm):
            knn = []
            for slot in frag.job_slots:
                gi = job_of_group[slot]
                grp = lp.groups[gi]
                attr, k, masked = lp.job_specs[slot]
                knn.append({
                    "attr": attr, "k": k, "masked": masked,
                    "group": gi,
                    "archetype": grp.archetype + suffix,
                    "beam_seed": seeds.get(grp.archetype + suffix),
                    "cost": grp_cost[gi],
                })
            vr = []
            if eng is not None and frag.path != "scalar":
                for b in Q.basic_queries(q):
                    if isinstance(b, Q.VR):
                        survive, total = eng.vr_tile_estimate(b)
                        ent = {"attr": b.attr,
                               "tiles_surviving": survive,
                               "tiles_pruned": total - survive,
                               "tiles_total": total}
                        # per-query route preview, mirroring the
                        # _vr_masks decision (predicted cost when
                        # calibrated for both kinds, else the static
                        # row-fraction cutoff); the executed group
                        # unions survivors across its queries, so this
                        # is the single-query estimate
                        dim = eng.vec_np[b.attr].shape[1]
                        fd = costm.vr_features("vr:dense", 1, survive,
                                               eng.cap, dim, eng.n)
                        ft = costm.vr_features("vr:tile", 1, survive,
                                               eng.cap, dim, eng.n)
                        pd = pt = None
                        if cm is not None and lp.device_loop:
                            pd = cm.predict("vr:dense", fd)
                            pt = cm.predict("vr:tile", ft)
                        if not lp.device_loop:
                            route = "dense"
                        elif pd is not None and pt is not None \
                                and cm.reliable("vr:dense", "vr:tile"):
                            route = "dense" if pd <= pt else "tile"
                        else:
                            from repro.core.engine import \
                                _VR_DENSE_CUTOFF
                            route = "dense" if survive * eng.cap > \
                                _VR_DENSE_CUTOFF * max(1, eng.n) \
                                else "tile"
                        ent["cost"] = {
                            "predicted_dense_s": pd,
                            "predicted_tile_s": pt,
                            "route": route,
                            "observed_dense_s":
                            p_qbs.cost_observed("vr:dense"),
                            "observed_tile_s":
                            p_qbs.cost_observed("vr:tile")}
                        vr.append(ent)
            frags.append({"query": frag.signature, "path": frag.path,
                          "knn": knn, "vr": vr,
                          # serving-tier feedback: {p50, p99, n} of
                          # per-request service seconds recorded by
                          # ``RetrievalServer`` for this plan signature
                          # (None until the archetype has been served)
                          "latency":
                          p_qbs.latency_quantiles(frag.signature)})
        p = self.session.platform
        delta = {
            "epoch": p.delta_epoch,
            "rows": p.n_delta,
            "tiles": (eng.delta_tiles if eng is not None
                      else (0 if p.delta is None
                            else p.delta.n_tiles(self.session.tile))),
        }
        sess = self.session
        rescue = {
            "scanned": sess.mp_scanned,
            "rescued": sess.mp_rescued,
            "ratio": (sess.mp_rescued / sess.mp_scanned
                      if sess.mp_scanned else 0.0),
        }
        return {
            "cache": "hit" if self.cache_hit else "miss",
            "device_loop": lp.device_loop,
            "shards": lp.shards,
            # calibration state + this plan's loop/topology provenance
            # (choices["by"] == "cost_model" when the calibrated model
            # picked the configuration; see Session.plan)
            "cost_model": {
                "calibrated": cm is not None and cm.calibrated(),
                "kinds": sorted(cm.kinds) if cm is not None else [],
                "choices": self.choices,
            },
            "precision": sess.precision,
            # fp32-rescue pressure of the mixed-precision scan, summed
            # over every batch this session executed (all zero on fp32)
            "rescue": rescue,
            "build_id": self.session.platform.build_id,
            "delta": delta,
            "n_queries": len(self.norm),
            "n_engine": len(lp.engine_idx),
            "n_scalar": len(lp.scalar_idx),
            "knn_groups": [
                {"attr": g.attr, "kmax": g.kmax, "jobs": len(g.jobs),
                 "masked": g.n_masked, "archetype": g.archetype + suffix,
                 "beam_seed": seeds.get(g.archetype + suffix)}
                for g in lp.groups],
            "fragments": frags,
        }


# ---------------------------------------------------------------------------
# Session
# ---------------------------------------------------------------------------
class Session:
    """One planning/execution context over a prepared ``MQRLD`` platform.

    Holds the plan cache (keyed on batch signature tuple + loop kind +
    platform build id) and the engine configuration. Obtain via
    ``MQRLD.session()``; a session stays valid across ``prepare()`` calls
    (cached plans and device state are invalidated automatically through
    the build id / engine rebuild)."""

    def __init__(self, platform, *, interpret: bool = True,
                 device_loop: bool = True, beam: int = 16,
                 tile: int = 128, shards: Optional[int] = None,
                 precision: Optional[str] = None,
                 auto_topology: bool = False):
        self.platform = platform
        self.interpret = interpret
        self.device_loop = device_loop
        self.beam = beam
        self.tile = tile
        # True when the caller did NOT pin a shard topology (neither a
        # ``shards`` argument nor a platform ``default_shards``): the
        # calibrated cost model may then choose among every shard
        # count it has a fitted kind for; False restricts the cost
        # choice to host vs the configured topology (explicit pins
        # always win — see ``plan``).
        self.auto_topology = auto_topology
        # mixed-precision tile scan for the KNN loops (results stay
        # row-identical to fp32; see engine module doc). Resolved HERE
        # (explicit > MQRLD_PRECISION env > platform default) so plan
        # keys and the executing engine can never disagree. Part of the
        # plan-cache key — each precision has its own compiled scans.
        self.precision = platform._resolve_precision(precision) \
            if hasattr(platform, "_resolve_precision") \
            else (precision or "fp32")
        # session-lifetime mixed-precision counters (what explain()'s
        # rescue block reports): rescued/scanned over every batch this
        # session executed
        self.mp_scanned = 0
        self.mp_rescued = 0
        # shard topology for the device loop: None = the platform's
        # ``default_shards`` (itself None = single-device paths); 0 =
        # force the single-device paths; N >= 1 = the T-sharded
        # execution over an N-device ("shards",) mesh. Resolved HERE so
        # plan keys and the engine the plans execute on can never
        # disagree. Part of the plan-cache key — each topology has its
        # own compiled-shape universe and QBS archetypes.
        if shards is None:
            shards = getattr(platform, "default_shards", None)
        self.shards = shards or None
        self._cache: Dict[Tuple, LogicalPlan] = {}
        self._cache_build = platform.build_id
        self.cache_hits = 0
        self.cache_misses = 0

    def engine(self, shards: Optional[int] = None):
        """The engine for this session's topology — or for an explicit
        plan topology (``ExecutablePlan`` passes its own ``lp.shards``:
        host-loop plans carry 0, so the oracle path never builds — or
        requires — a device mesh, whatever the session default is)."""
        if shards is None:
            shards = self.shards or 0
        return self.platform.engine(interpret=self.interpret,
                                    beam=self.beam, tile=self.tile,
                                    shards=shards,
                                    precision=self.precision)

    # ----------------------------------------------------- cost choice
    def _cost_choice(self, norm: Sequence[Q.Query]
                     ) -> Optional[Tuple[bool, int, dict]]:
        """Cost-model loop/topology choice for one batch: (device_loop,
        shards, provenance) by minimum predicted KNN cost over the
        reliably calibrated candidate configurations, or None when no
        choice can be made (no model, no engine-plannable V.K work, or
        the session default's own stage kind is uncalibrated /
        unreliably fitted — the fixed-behavior fallback the module doc
        promises)."""
        cm = getattr(self.platform, "cost_model", None)
        if cm is None or not cm.calibrated():
            return None
        specs: List[Tuple[str, int, bool]] = []
        for q in norm:
            if plannable(q):
                _collect_job_specs(q, False, specs)
        if not specs:
            return None
        default = (self.device_loop,
                   (self.shards or 0) if self.device_loop else 0)
        if not cm.reliable(costm.knn_kind(*default)):
            return None
        cands = [(False, 0)]
        if self.auto_topology or not self.shards:
            cands.append((True, 0))
        if self.shards:
            cands.append((True, self.shards))
        if self.auto_topology:
            import jax
            ndev = jax.device_count()
            for kind in cm.kinds:
                s = costm.shards_of_kind(kind)
                if s and 1 <= s <= ndev and (True, s) not in cands:
                    cands.append((True, s))
        eng = self.engine(0)   # unsharded layouts price every candidate
        suffix = ":delta" if self.platform.n_delta else ""
        scored = []
        for dl, sh in cands:
            kind = costm.knn_kind(dl, sh)
            if not cm.reliable(kind):
                continue
            total = 0.0
            for grp in group_job_specs(tuple(specs), dl, sh):
                seed = self.platform.qbs.convergence_width(
                    grp.archetype + suffix)
                pred = cm.predict(kind, _knn_group_features(
                    eng, grp, dl, sh, self.beam, self.precision,
                    seed=seed))
                if pred is None:
                    total = None
                    break
                total += pred
            if total is not None:
                scored.append((total, dl, sh, kind))
        if len(scored) < 2:
            return None    # nothing to choose between
        scored.sort(key=lambda t: t[0])
        best = scored[0]
        prov = {"by": "cost_model",
                "candidates": [{"device_loop": dl, "shards": sh,
                                "kind": kind, "predicted_s": tot}
                               for tot, dl, sh, kind in scored],
                "chosen": {"device_loop": best[1], "shards": best[2]}}
        return best[1], best[2], prov

    # ---------------------------------------------------------------- plan
    def plan(self, queries: Sequence[Q.Query], *,
             device_loop: Optional[bool] = None) -> ExecutablePlan:
        """Normalize + sign the batch, then return an ``ExecutablePlan``
        — cached skeleton when this batch archetype was planned before
        (same signatures, same loop kind, same index build).

        Loop kind and shard topology come from the calibrated cost
        model when one is attached (``_cost_choice``; provenance in
        ``explain()["cost_model"]["choices"]``); an explicit
        ``device_loop`` argument always wins, and without a calibrated
        model the session defaults apply unchanged."""
        norm = [Q.normalize(q) for q in queries]
        choices: Optional[dict] = None
        if device_loop is None:
            sel = self._cost_choice(norm)
            if sel is not None:
                dl, shards, choices = sel
            else:
                dl = self.device_loop
                shards = (self.shards or 0) if dl else 0
        else:
            dl = device_loop
            shards = (self.shards or 0) if dl else 0
            choices = {"by": "explicit"}
        if self._cache_build != self.platform.build_id:
            # prepare()/fold()/swap() changed the index: dead-build
            # entries are stale and would grow without bound in a
            # long-lived serving process — but entries prewarmed FOR
            # this build (reopt warms the incoming generation's hot
            # signatures before the swap) must survive the flip, or the
            # first post-swap batch pays the cold-plan cost the warm-up
            # existed to avoid
            b = self.platform.build_id
            self._cache = {k: v for k, v in self._cache.items()
                           if k[-1] == b}
            self._cache_build = b
        key = (tuple(Q.signature(q) for q in norm), dl, shards,
               self.precision, self.platform.build_id)
        logical = self._cache.get(key)
        hit = logical is not None
        if hit:
            self.cache_hits += 1
        else:
            self.cache_misses += 1
            logical = build_logical_plan(norm, dl, shards)
            self._cache[key] = logical
        return ExecutablePlan(self, logical, queries, norm, hit,
                              choices=choices)

    def prewarm(self, queries: Sequence[Q.Query], *,
                build_id: Optional[int] = None,
                device_loop: Optional[bool] = None,
                sizes: Sequence[int] = (1,)) -> int:
        """Insert plan skeletons for the given query shapes, keyed under
        ``build_id`` (default: the current build) — the swap warm-up
        path. The reopt controller calls this with ``build_id =
        platform.build_id + 1`` (the id the incoming generation will
        serve under) and the pow2 batch ``sizes`` the server's
        coalescing emits, so the first post-swap micro-batch of every
        hot signature is a plan-cache HIT instead of paying
        plannability analysis + job-layout derivation at serving time.
        Returns the number of skeletons inserted (already-cached shapes
        are skipped)."""
        dl = self.device_loop if device_loop is None else device_loop
        shards = (self.shards or 0) if dl else 0
        b = self.platform.build_id if build_id is None else build_id
        n_new = 0
        for q in queries:
            norm = Q.normalize(q)
            sig = Q.signature(norm)
            for size in sizes:
                key = ((sig,) * int(size), dl, shards, self.precision, b)
                if key not in self._cache:
                    self._cache[key] = build_logical_plan(
                        [norm] * int(size), dl, shards)
                    n_new += 1
        return n_new

    def signature(self, query: Q.Query) -> str:
        """The archetype string ``plan()`` would key this query under
        (normalize + ``Q.signature``). The serving tier coalesces
        requests by this value: two queries with equal signatures share
        a ``LogicalPlan`` and a compiled-shape universe, so batching
        them together reuses warm state instead of forcing a re-trace.
        Vector constants are elided from signatures, so callers may pass
        placeholder vectors (e.g. an empty tuple) to sign a request
        before its embedding exists."""
        return Q.signature(Q.normalize(query))

    # --------------------------------------------------------- conveniences
    def execute(self, queries: Sequence[Q.Query], *,
                device_loop: Optional[bool] = None
                ) -> Tuple[List[np.ndarray], EngineStats]:
        return self.plan(queries, device_loop=device_loop).execute()

    def explain(self, queries: Sequence[Q.Query], *,
                device_loop: Optional[bool] = None) -> dict:
        return self.plan(queries, device_loop=device_loop).explain()
