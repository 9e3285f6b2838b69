"""MQRLD platform facade (paper Fig 2/3).

Pipeline: ingest -> (measure/choose embedding) -> hyperspace transformation
-> LPGF movement -> learned-index build -> physical re-layout -> MOAPI
queries with QBS recording -> query-aware optimization (transform refresh +
Algorithm 3 sibling reorder).

Query-space design (exactness; DESIGN.md §2): the *enhanced* space decides
the physical layout (which rows co-locate in a bucket) and the tree
geometry; every per-attribute query is answered EXACTLY in the original
attribute space using per-leaf (centroid, radius) metadata per vector
attribute and per-leaf [min, max] boxes per numeric attribute. The paper's
performance claim — better layout => fewer buckets touched => faster —
shows up as lower CBR, not as approximation error.

MOAPI v2 (the query-plan API): batched execution goes through a planner —
``MQRLD.session()`` returns a ``repro.core.planner.Session`` whose
``plan(queries)`` canonicalizes the ASTs (``Q.normalize``), derives stable
archetype signatures, chooses scalar / host-loop / device-loop per
fragment, seeds KNN beam widths from QBS convergence statistics, and
returns an ``ExecutablePlan`` with ``execute()`` and ``explain()``.
Plans are cached per (batch signature, loop kind, index build id):
repeated query *shapes* — serving templates differing only in constants —
skip plannability analysis, job-layout derivation, and KNN grouping, and
reuse the same compiled-shape universe. ``prepare()`` bumps ``build_id``,
invalidating every cached plan along with the device state.

Execution paths: ``execute`` is the paper-faithful scalar path —
host-side tree walk per query, the only path that records QBS rows,
per-query ``QueryStats`` and Algorithm-3 access counts. Engine fragments
run on the device-resident ``repro.core.engine.HybridEngine``
(vectorized leaf pruning, grouped predicate masks, masked KNN through
the Pallas fused_topk kernel) and return exactly the same rows; queries
outside the engine's plannable fragment transparently fall back to the
scalar path. The engine keeps two beam-loop implementations — the
on-device ``lax.while_loop`` path with V.R routed through the tile beam
(the serving default), and the host-driven doubling loop with dense V.R
kept as the exactness oracle. All paths are exact; use the scalar one
for QBS/stats parity and a ``Session`` for serving throughput.

Deprecated v1 surface: ``execute_batch`` (with its ``interpret`` /
``device_loop`` flags) is kept as a thin shim over ``session()`` with
identical results; new code should hold a ``Session`` and use
``plan()/execute()/explain()``.

Async ingest (freshness-exact writes): ``append(...)`` lands new rows in
a ``repro.core.lake.DeltaRegion`` — pow2-capacity buffers with their own
bucket tiles — WITHOUT rebuilding the index or invalidating cached
plans. Every query path unions the delta in from the next execution on:
the scalar executor scans it alongside the leaf walk, the batched engine
splices delta tiles into both beam loops and the V.R tile planner
(``HybridEngine.sync_delta``), so results always equal a brute-force
oracle over base+delta (``view()``). The delta lifecycle is append ->
union -> fold: ``fold()`` (or auto-fold past ``auto_fold_ratio``, or the
next full ``prepare()``) merges the delta into the learned index —
incremental nearest-leaf insertion through ``index.fold_into_tree``,
far cheaper than a cold rebuild — and bumps ``build_id`` so cached
``LogicalPlan``s invalidate cleanly. Un-folded appends only advance
``delta_epoch``, which engine state and plan execution check at execute
time; a warm plan stays warm across appends.

Index generations (online re-optimization + zero-downtime maintenance):
every heavyweight index change can be built BESIDE the serving state and
installed atomically, instead of mutating in place while queries wait.
``build_generation(theta=..., delta_scales=...)`` runs the full feature
representation + index build over the current data (base + the live
delta rows present at build start) with a perturbed hyperspace transform
— the output of the background MORBO tuner (``repro.core.reopt``) — and
``build_fold_generation()`` runs the incremental fold the same way, on
COPIES of the tree/enhanced state, so neither touches what queries are
executing against. ``swap(gen)`` then installs a built generation in one
bounded step: state pointers flip, ``build_id`` bumps (cached plans and
device state invalidate exactly like ``prepare()``), delta rows appended
AFTER the build started carry over into a fresh delta region (freshness
is never lost to a swap), and engines prewarmed against the incoming
generation (``repro.core.reopt`` warm-up) replace the stale ones, so the
first post-swap batch is not a cold trace. The previous serving state is
retained in memory — ``rollback()`` restores it (including every row
appended since the swap) in one call; ``repro.core.persist`` retains
generations on disk (``gen-XXXX/`` + ``CURRENT``) for durable rollback
across restarts. Every path stays oracle-exact before, during, and
after a swap: only WHICH transform/index serves changes, never the row
set a query answers over.

Background folds: ``fold_mode = "background"`` makes the auto-fold
trigger in ``append()`` non-blocking — instead of folding inline (the
caller pays the merge), the platform marks ``fold_due`` and the attached
``ReoptController``/serving loop builds the fold generation beside and
swaps it between micro-batches. ``fold_mode = "inline"`` (default)
keeps the original blocking behavior.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core import query as Q
from repro.core.index import (BuildReport, ClusterTree, QueryStats,
                              build_index)
from repro.core.lake import DeltaRegion, MMOTable
from repro.core.lpgf import lpgf
from repro.core.qbs import QBSTable, accuracy, recall_at_k
from repro.core.reorder import reorder_siblings
from repro.core.transform import HyperspaceTransform, init_transform, perturb
from repro.kernels import ops
from repro.utils.spans import span


@dataclass
class LeafMeta:
    """Per-leaf exact-space pruning metadata."""
    vec_centroid: Dict[str, np.ndarray]   # attr -> (L, d_attr)
    vec_radius: Dict[str, np.ndarray]     # attr -> (L,)
    num_lo: Dict[str, np.ndarray]         # attr -> (L,)
    num_hi: Dict[str, np.ndarray]


def build_leaf_meta(tree: ClusterTree, table: MMOTable) -> LeafMeta:
    """Exact original-space pruning metadata for every leaf of ``tree``
    over the PERMUTED ``table`` (bucket ranges index it directly)."""
    leaves = tree.leaf_ids
    vc, vr, nlo, nhi = {}, {}, {}, {}
    for attr, col in table.vector.items():
        cs, rs = [], []
        for lid in leaves:
            s, e = int(tree.bucket_start[lid]), int(tree.bucket_end[lid])
            pts = col[s:e]
            c = pts.mean(axis=0) if e > s else np.zeros(col.shape[1])
            cs.append(c)
            rs.append(float(np.sqrt(
                np.max(((pts - c) ** 2).sum(1), initial=0.0))))
        vc[attr] = np.stack(cs).astype(np.float32)
        vr[attr] = np.asarray(rs, np.float32)
    for attr, col in table.numeric.items():
        los, his = [], []
        for lid in leaves:
            s, e = int(tree.bucket_start[lid]), int(tree.bucket_end[lid])
            los.append(float(col[s:e].min(initial=np.inf)))
            his.append(float(col[s:e].max(initial=-np.inf)))
        nlo[attr] = np.asarray(los, np.float32)
        nhi[attr] = np.asarray(his, np.float32)
    return LeafMeta(vec_centroid=vc, vec_radius=vr, num_lo=nlo, num_hi=nhi)


def _build_state(raw_table: MMOTable, *, seed: int,
                 columns: Optional[List[str]] = None,
                 use_transform: bool = True, use_lpgf: bool = True,
                 lpgf_iters: int = 1, delta: float = 0.951,
                 min_leaf: int = 32, max_leaf: int = 4096,
                 max_depth: int = 12, dpc_max_clusters: int = 8,
                 dpc_sample: int = 4096,
                 theta: Optional[Sequence[float]] = None,
                 delta_scales: Optional[Sequence[float]] = None) -> Dict:
    """The full feature-representation + index-build pipeline as a PURE
    function of an input table: transform init (+ optional (θ, δ)
    perturbation), LPGF movement, learned-index build, physical
    re-layout, leaf metadata. ``prepare()`` installs the result into the
    live platform; ``build_generation()`` keeps it beside the serving
    state until ``swap()``. Mutates nothing it did not create."""
    with span("build.transform") as sp_transform:
        d, layout = raw_table.concat_features(columns)
        feats = d
        transform = None
        if use_transform:
            transform = init_transform(d)
            if theta is not None or delta_scales is not None:
                transform = perturb(
                    transform,
                    theta if theta is not None else [],
                    delta_scales if delta_scales is not None else [])
            feats = transform.apply(d)
    with span("build.lpgf") as sp_lpgf:
        if use_lpgf:
            feats = lpgf(feats, iters=lpgf_iters, seed=seed)
    with span("build.index") as sp_index:
        tree, perm, report = build_index(
            feats, delta=delta, min_leaf=min_leaf, max_leaf=max_leaf,
            max_depth=max_depth, dpc_max_clusters=dpc_max_clusters,
            dpc_sample=dpc_sample, seed=seed)
    with span("build.layout") as sp_layout:
        leaves = tree.leaf_ids
        bucket_id = np.zeros(len(perm), np.int32)
        for b, lid in enumerate(leaves):
            s, e = int(tree.bucket_start[lid]), int(tree.bucket_end[lid])
            bucket_id[s:e] = b
        bucket_starts = np.concatenate(
            [tree.bucket_start[leaves], [len(perm)]]).astype(np.int32)
        table = raw_table.apply_permutation(perm, bucket_id, bucket_starts)
        meta = build_leaf_meta(tree, table)
    report.phase_s = dict(transform=sp_transform.seconds,
                          lpgf=sp_lpgf.seconds, index=sp_index.seconds,
                          layout=sp_layout.seconds)
    return dict(table=table, tree=tree, report=report, transform=transform,
                enhanced=feats[perm], enhanced_unpermuted=feats,
                layout=layout, meta=meta)


def _copy_tree(tree: ClusterTree) -> ClusterTree:
    """Deep copy of a ``ClusterTree`` — fold-beside mutates bucket
    ranges, radii, and last-mile fits, which must never be visible to
    the serving generation before the swap."""
    return ClusterTree(
        centroid=tree.centroid.copy(), radius=tree.radius.copy(),
        parent=tree.parent.copy(),
        children=[list(c) for c in tree.children],
        is_leaf=tree.is_leaf.copy(),
        bucket_start=tree.bucket_start.copy(),
        bucket_end=tree.bucket_end.copy(),
        lm_a=tree.lm_a.copy(), lm_b=tree.lm_b.copy(),
        depth=tree.depth.copy(),
        access_count=tree.access_count.copy())


@dataclass
class Generation:
    """One complete, self-consistent index+layout state.

    Two roles: (a) the OUTPUT of a beside-build
    (``build_generation``/``build_fold_generation``) waiting to be
    swapped in — ``delta_consumed`` records how many live delta rows the
    build baked into its base, so ``swap()`` knows which delta tail must
    carry over; (b) the RETAINED previous serving state after a swap
    (``kind="serving"``), holding the old delta region and
    ``post_swap_tail`` so ``rollback()`` can restore it without losing
    rows appended after the swap."""
    gen_id: int
    kind: str                               # "reopt" | "fold" | "serving"
    raw_table: MMOTable
    table: MMOTable
    tree: ClusterTree
    meta: LeafMeta
    enhanced: np.ndarray
    transform: Optional[HyperspaceTransform]
    layout: Dict
    report: Optional[BuildReport]
    delta_consumed: int = 0                 # live delta rows in this base
    base_build_id: int = -1                 # serving build it was built from
    params: Optional[Tuple] = None          # (theta, delta_scales) | None
    engines: Dict = field(default_factory=dict)   # prewarmed HybridEngines
    # rollback bookkeeping (kind == "serving" only)
    delta: Optional[DeltaRegion] = None
    post_swap_tail: int = 0                 # delta rows carried into next gen


class MQRLD:
    """The platform. One instance per MMO table."""

    def __init__(self, table: MMOTable, *, qbs_sample: float = 1.0,
                 seed: int = 0):
        self.raw_table = table.validate()
        self.table: Optional[MMOTable] = None
        self.qbs = QBSTable(sample_rate=qbs_sample, seed=seed)
        self.tree: Optional[ClusterTree] = None
        self.report: Optional[BuildReport] = None
        self.transform: Optional[HyperspaceTransform] = None
        self.meta: Optional[LeafMeta] = None
        self.enhanced: Optional[np.ndarray] = None
        self.seed = seed
        self.build_id = 0  # bumped by prepare()/fold(); keys plan caches
        # async-ingest write path: un-folded appends live in the delta
        # region; delta_epoch is monotone across appends AND folds (it
        # never resets), so any state keyed on it can never alias
        self.delta: Optional[DeltaRegion] = None
        self.delta_epoch = 0
        self.auto_fold_ratio = 0.5   # fold when delta rows > ratio * base
        # sharded serving default: engine()/session() calls that do not
        # pass ``shards`` explicitly use this topology (None = the
        # single-device paths). Persisted by core.persist so a reloaded
        # platform rebuilds its sharded layout on first query.
        self.default_shards: Optional[int] = None
        # mixed-precision serving default: engine()/session() calls that
        # do not pass ``precision`` explicitly use this (after the
        # MQRLD_PRECISION env override). Persisted by core.persist along
        # with the quantized tile planes (``_quant_cache``) so a
        # reloaded int8 platform serves without re-quantizing.
        self.default_precision: str = "fp32"
        self._quant_cache: Optional[Dict] = None
        # calibrated execution cost model (repro.core.cost.CostModel,
        # or None = uncalibrated: every consumer falls back to the
        # fixed thresholds). Fitted by ``calibrate()``, persisted as
        # cost_model.json next to platform.json, refreshed online from
        # observed stage times. A HOST property, not an index
        # property: swap()/rollback() keep it (the model describes
        # this machine's compiled-stage throughput, which an index
        # generation change does not invalidate).
        self.cost_model = None
        self._view_cache: Optional[Tuple[Tuple[int, int], MMOTable]] = None
        self._oracle_cache: Dict = {}
        self._engines: Dict = {}
        self._sessions: Dict = {}
        # index generations (online re-optimization; see module doc):
        # ``generation`` counts installed index states monotonically
        # (prepare/fold/swap/rollback all advance it — it can never
        # alias, so it also numbers the on-disk gen-XXXX snapshots);
        # ``_prev_gen`` retains the pre-swap serving state for one-call
        # in-memory rollback; ``snapshot_dir`` (set by persist.save /
        # the owner) enables the disk-rollback fallback.
        self.generation = 0
        self._prev_gen: Optional[Generation] = None
        self.snapshot_dir: Optional[str] = None
        # background folds: "inline" folds inside append() (caller
        # pays); "background" marks ``fold_due`` for the attached
        # controller to build-beside + swap between micro-batches
        self.fold_mode: str = "inline"
        self._fold_requested = False
        # the build configuration of the LAST prepare(), so beside-
        # builds reproduce the serving index's parameters exactly
        self._prepare_cfg: Dict = dict(
            columns=None, use_transform=True, use_lpgf=True,
            lpgf_iters=1, delta=0.951, min_leaf=32, max_leaf=4096,
            max_depth=12, dpc_max_clusters=8, dpc_sample=4096)
        self._transform_params: Tuple = (None, None)   # (theta, delta_scales)

    # ------------------------------------------------------------ build
    def prepare(self, columns: Optional[List[str]] = None, *,
                use_transform: bool = True, use_lpgf: bool = True,
                lpgf_iters: int = 1, delta: float = 0.951,
                min_leaf: int = 32, max_leaf: int = 4096,
                max_depth: int = 12, dpc_max_clusters: int = 8,
                theta: Optional[Sequence[float]] = None,
                dpc_sample: int = 4096,
                delta_scales: Optional[Sequence[float]] = None) -> BuildReport:
        """Feature representation + index build + physical re-layout.

        A pending delta region is folded into the rebuild: its rows join
        ``raw_table`` before the transform/index build, so ``prepare()``
        is the full-rebuild end of the append -> union -> fold
        lifecycle (``fold()`` is the cheap incremental end).

        Lifecycle note: ``prepare()`` records its configuration so later
        beside-builds (``build_generation``) reproduce the serving
        index's parameters; it installs the built state in place and is
        therefore the BLOCKING end of the rebuild spectrum — the online
        path is ``build_generation()`` + ``swap()``."""
        if self.delta is not None and self.delta.m:
            self.raw_table = self._merged_raw()
            self.delta = None
            self.delta_epoch += 1
            self._view_cache = None
        self._prepare_cfg = dict(
            columns=columns, use_transform=use_transform,
            use_lpgf=use_lpgf, lpgf_iters=lpgf_iters, delta=delta,
            min_leaf=min_leaf, max_leaf=max_leaf, max_depth=max_depth,
            dpc_max_clusters=dpc_max_clusters, dpc_sample=dpc_sample)
        self._transform_params = (
            None if theta is None else np.asarray(theta, np.float64),
            None if delta_scales is None
            else np.asarray(delta_scales, np.float64))
        st = _build_state(self.raw_table, seed=self.seed, theta=theta,
                          delta_scales=delta_scales, **self._prepare_cfg)
        self._install_state(st)
        return st["report"]

    def _install_state(self, st: Dict):
        """Install a ``_build_state`` result as the serving state and
        invalidate everything derived from the old one (the tail of the
        original ``prepare()``, shared with ``swap``-less rebuilds)."""
        self.table = st["table"]
        self.tree = st["tree"]
        self.report = st["report"]
        self.transform = st["transform"]
        self.layout = st["layout"]
        self.enhanced = st["enhanced"]
        self.enhanced_unpermuted = st["enhanced_unpermuted"]
        self.meta = st["meta"]
        self._view_cache = None
        self._oracle_cache.clear()
        self._engines.clear()  # device state is stale after a rebuild
        # quantized planes were built from the PREVIOUS layout; a rebuild
        # at the same row count would otherwise pass the engine's
        # precision+shape validation and serve stale bounds
        self._quant_cache = None
        self.build_id += 1   # cached ExecutablePlans are keyed on this
        self.generation += 1

    def _build_meta(self):
        self.meta = build_leaf_meta(self.tree, self.table)

    # ----------------------------------------------------- async ingest
    @property
    def n_base(self) -> int:
        return self.table.n_rows

    @property
    def n_delta(self) -> int:
        return 0 if self.delta is None else self.delta.m

    def append(self, *, numeric: Optional[Dict] = None,
               vector: Optional[Dict] = None,
               raw_uri: Optional[Sequence[str]] = None,
               fold: Optional[bool] = None) -> int:
        """Ingest new rows into the delta region (freshness-exact).

        The rows are queryable from the very next execution — scalar,
        host-loop, and device-loop paths all union the delta — with ids
        ``n_base + j`` (j = delta position) until a fold re-lays them
        physically. Columns must cover the table schema exactly; the
        call validates everything before mutating any state, so a
        failed append changes nothing. Cached plans stay VALID (only
        ``delta_epoch`` advances; plans re-read delta state at execute
        time); ``fold`` controls merging into the learned index:
        None = auto (fold once delta rows exceed ``auto_fold_ratio`` x
        base rows), False = never, True = fold immediately (inline,
        regardless of ``fold_mode``). Under ``fold_mode =
        "background"`` the auto trigger marks ``fold_due`` instead of
        folding inline — the attached controller/serving loop builds
        the fold generation beside and swaps it in. Returns the number
        of live (un-folded) delta rows after the call."""
        assert self.tree is not None, "call prepare() first"
        if self.delta is None:
            self.delta = DeltaRegion.for_table(self.table)
        self.delta.append(dict(numeric or {}), dict(vector or {}), raw_uri)
        self.delta_epoch += 1
        self._view_cache = None
        if fold is True:
            self.fold()
        elif (fold is None and self.auto_fold_ratio
              and self.delta.m
              > self.auto_fold_ratio * self.table.n_rows):
            if self.fold_mode == "background":
                self._fold_requested = True
            else:
                self.fold()
        return self.n_delta

    @property
    def fold_due(self) -> bool:
        """True when a background fold is wanted: the auto-fold trigger
        fired under ``fold_mode = "background"`` (or the delta is past
        the ratio right now). Consumed by ``ReoptController.step()``;
        cleared by any fold/swap/prepare that drains the delta."""
        if self.delta is None or self.delta.m == 0:
            return False
        if self._fold_requested:
            return True
        return bool(self.fold_mode == "background" and self.auto_fold_ratio
                    and self.delta.m
                    > self.auto_fold_ratio * self.table.n_rows)

    def _concat_delta(self, t: MMOTable,
                      row_ids: Optional[np.ndarray] = None,
                      limit: Optional[int] = None) -> MMOTable:
        """``t`` with the live delta rows appended column-wise — the one
        concatenation recipe behind both ``view()`` (over the physical
        table) and ``_merged_raw`` (over ``raw_table``). ``limit``
        restricts to the FIRST ``limit`` live rows — beside-builds pin
        the delta prefix that existed when the build started, so rows
        appended during the build stay out of the new base."""
        d = self.delta
        m = d.m if limit is None else min(limit, d.m)
        uri = None
        if t.raw_uri is not None:
            extra = d.raw_uri if d.raw_uri is not None else [""] * m
            uri = np.concatenate([t.raw_uri,
                                  np.asarray(list(extra)[:m], dtype=object)])
        return MMOTable(
            name=t.name,
            numeric={k: np.concatenate([v, d.live_numeric(k)[:m]])
                     for k, v in t.numeric.items()},
            vector={k: np.concatenate([v, d.live_vector(k)[:m]])
                    for k, v in t.vector.items()},
            raw_uri=uri, embed_model=dict(t.embed_model), row_ids=row_ids)

    def _merged_raw(self, limit: Optional[int] = None) -> MMOTable:
        """raw_table + live delta rows appended (raw order)."""
        return self._concat_delta(self.raw_table, limit=limit)

    def _delta_feats(self, m0: Optional[int] = None) -> np.ndarray:
        """The first ``m0`` live delta rows pushed through the FROZEN
        feature representation (transform applied, no re-fit; LPGF — a
        global build-time movement — is skipped: it shapes layout
        quality, never exactness), in the column order ``prepare()``
        used (``self.layout`` preserves it). Shared by the inline fold
        and ``build_fold_generation`` so the two are bit-identical."""
        d = self.delta
        m0 = d.m if m0 is None else min(m0, d.m)
        parts = []
        for c in self.layout:
            a = (d.live_vector(c)[:m0] if c in d.vector_dims
                 else d.live_numeric(c)[:m0, None])
            parts.append(a.astype(np.float32))
        feats = np.concatenate(parts, axis=1)
        if self.transform is not None:
            feats = self.transform.apply(feats)
        return feats

    def fold(self) -> int:
        """Merge the delta region into the learned index incrementally.

        The cheap end of the append -> union -> fold lifecycle: delta
        rows are pushed through the FROZEN feature representation
        (transform applied, no re-fit; LPGF — a global build-time
        movement — is skipped: it shapes layout quality, never
        exactness), assigned to their nearest leaf in enhanced space
        (``index.fold_into_tree``: bucket splice + key re-sort +
        last-mile refit + radius widening), and the table is physically
        re-laid. Per-leaf meta and engine tiles are rebuilt exactly
        from the merged table, so every query path stays exact
        regardless of assignment quality. Bumps ``build_id`` — cached
        plans and device state invalidate cleanly — and advances
        ``delta_epoch``. Far cheaper than a cold ``prepare()`` of
        base+delta (no transform init, no DPC clustering). Returns the
        number of rows folded (0 = nothing to do)."""
        from repro.core.index import fold_into_tree
        if self.delta is None or self.delta.m == 0:
            self._fold_requested = False
            return 0
        m = self.delta.m
        comb = self.view()           # before raw merge: ids stay consistent
        self.raw_table = self._merged_raw()
        feats = self._delta_feats(m)
        perm, bucket_id, bucket_starts = fold_into_tree(
            self.tree, self.enhanced, feats)
        self.table = comb.apply_permutation(perm, bucket_id, bucket_starts)
        self.enhanced = np.concatenate([self.enhanced, feats])[perm]
        self._build_meta()
        self.delta = None
        self._fold_requested = False
        self.delta_epoch += 1
        self._view_cache = None
        self._oracle_cache.clear()
        self._engines.clear()        # device tiles are stale
        self._quant_cache = None     # planes quantized from the old layout
        self.build_id += 1           # cached plans invalidate
        self.generation += 1
        return m

    def view(self) -> MMOTable:
        """The queryable table: base physical rows plus live delta rows
        at ids ``n_base..n_base+m-1`` — what every query path (and the
        brute-force oracle) answers over. Returns the base table
        itself when the delta is empty; cached per write epoch."""
        if self.delta is None or self.delta.m == 0:
            return self.table
        key = (self.build_id, self.delta_epoch)
        if self._view_cache is not None and self._view_cache[0] == key:
            return self._view_cache[1]
        row_ids = None
        if self.table.row_ids is not None:
            # delta rows take the raw ids they will hold once folded
            row_ids = np.concatenate([
                self.table.row_ids,
                self.raw_table.n_rows + np.arange(self.delta.m)]
            ).astype(np.int64)
        v = self._concat_delta(self.table, row_ids=row_ids)
        self._view_cache = (key, v)
        return v

    # -------------------------------------------------- index generations
    @staticmethod
    def _engine_key(interpret: bool, beam: int, tile: int,
                    shards: Optional[int], precision: str) -> Tuple:
        """The cache key of ``engine()`` — exposed so the reopt warm-up
        can prewarm a ``Generation.engines`` entry under the exact key
        ``swap()`` will serve it from."""
        return (interpret, beam, tile, shards, precision)

    def snapshot_generation(self) -> Generation:
        """The current serving state as a ``Generation`` (no copies —
        after a swap nothing mutates these objects, so retaining the
        references is enough for in-memory rollback)."""
        return Generation(
            gen_id=self.generation, kind="serving",
            raw_table=self.raw_table, table=self.table, tree=self.tree,
            meta=self.meta, enhanced=self.enhanced,
            transform=self.transform, layout=self.layout,
            report=self.report, base_build_id=self.build_id,
            params=self._transform_params, delta=self.delta)

    def build_generation(self, *,
                         theta: Optional[Sequence[float]] = None,
                         delta_scales: Optional[Sequence[float]] = None
                         ) -> Generation:
        """Full rebuild BESIDE the serving state with a perturbed
        hyperspace transform — the materialization step of the online
        tuner. Uses the last ``prepare()`` configuration over the
        current data (base + the delta prefix live right now); the
        serving state is not touched. Install with ``swap()``."""
        assert self.tree is not None, "call prepare() first"
        m0 = self.n_delta
        raw = self._merged_raw(limit=m0) if m0 else self.raw_table
        st = _build_state(raw, seed=self.seed, theta=theta,
                          delta_scales=delta_scales, **self._prepare_cfg)
        return Generation(
            gen_id=self.generation + 1, kind="reopt", raw_table=raw,
            table=st["table"], tree=st["tree"], meta=st["meta"],
            enhanced=st["enhanced"], transform=st["transform"],
            layout=st["layout"], report=st["report"], delta_consumed=m0,
            base_build_id=self.build_id,
            params=(None if theta is None
                    else np.asarray(theta, np.float64),
                    None if delta_scales is None
                    else np.asarray(delta_scales, np.float64)))

    def build_fold_generation(self) -> Optional[Generation]:
        """The incremental fold as a beside-build: identical math to
        ``fold()`` (same ``fold_into_tree`` over the same frozen-
        representation delta features) but run on COPIES of the tree
        and enhanced matrix, so the serving state keeps answering
        queries untouched until ``swap()``. Returns None when the delta
        is empty. Rows appended while the build runs stay live in the
        delta; ``swap()`` carries them over."""
        from repro.core.index import fold_into_tree
        if self.delta is None or self.delta.m == 0:
            return None
        m0 = self.delta.m
        tree = _copy_tree(self.tree)
        enhanced = self.enhanced
        feats = self._delta_feats(m0)
        perm, bucket_id, bucket_starts = fold_into_tree(
            tree, enhanced, feats)
        row_ids = None
        if self.table.row_ids is not None:
            row_ids = np.concatenate([
                self.table.row_ids,
                self.raw_table.n_rows + np.arange(m0)]).astype(np.int64)
        comb = self._concat_delta(self.table, row_ids=row_ids, limit=m0)
        table = comb.apply_permutation(perm, bucket_id, bucket_starts)
        return Generation(
            gen_id=self.generation + 1, kind="fold",
            raw_table=self._merged_raw(limit=m0), table=table, tree=tree,
            meta=build_leaf_meta(tree, table),
            enhanced=np.concatenate([enhanced, feats])[perm],
            transform=self.transform, layout=self.layout,
            report=self.report, delta_consumed=m0,
            base_build_id=self.build_id, params=self._transform_params)

    def swap(self, gen: Generation) -> int:
        """Atomically install a beside-built generation as the serving
        state — the one bounded step of the zero-downtime path.

        Delta rows appended AFTER the build started (positions >=
        ``gen.delta_consumed``) carry over into a fresh delta region, so
        freshness survives the swap; the displaced serving state is
        retained as ``_prev_gen`` for one-call ``rollback()``. Cached
        plans/engines invalidate through the ``build_id`` bump exactly
        like ``prepare()``; engines prewarmed into ``gen.engines``
        (keyed by ``_engine_key``) become the serving engines so the
        first post-swap batch is not a cold trace. Raises if the
        serving index changed since the build started (a fold or
        another swap landed first) — rebuild and retry. Returns the new
        generation id."""
        if gen.base_build_id != self.build_id:
            raise RuntimeError(
                f"stale generation: built against build_id "
                f"{gen.base_build_id}, serving is {self.build_id} — "
                f"rebuild against the current state")
        prev = self.snapshot_generation()
        # carry over the delta tail appended during the build
        tail: Optional[DeltaRegion] = None
        carried = 0
        if self.delta is not None and self.delta.m > gen.delta_consumed:
            d = self.delta
            sl = slice(gen.delta_consumed, d.m)
            carried = d.m - gen.delta_consumed
            tail = DeltaRegion.for_table(gen.table)
            tail.append(
                {k: d.live_numeric(k)[sl] for k in d.numeric_keys},
                {k: d.live_vector(k)[sl] for k in d.vector_dims},
                None if d.raw_uri is None else d.raw_uri[sl])
        prev.post_swap_tail = carried
        self.raw_table = gen.raw_table
        self.table = gen.table
        self.tree = gen.tree
        self.meta = gen.meta
        self.enhanced = gen.enhanced
        self.transform = gen.transform
        self.layout = gen.layout
        self.report = gen.report
        if gen.params is not None:
            self._transform_params = gen.params
        self.delta = tail
        self._fold_requested = False
        self.delta_epoch += 1
        self._view_cache = None
        self._oracle_cache.clear()
        self._engines = dict(gen.engines)   # prewarmed, or empty
        self._quant_cache = None
        self.build_id += 1
        self.generation += 1
        gen.gen_id = self.generation
        self._prev_gen = prev
        return self.generation

    def rollback(self) -> int:
        """Restore the pre-swap serving state in one call.

        The in-memory ``_prev_gen`` is preferred; when this process has
        none (e.g. restarted since the swap) and ``snapshot_dir`` is
        set, the previous on-disk generation is loaded instead
        (``repro.core.persist.rollback_platform``). Rows appended AFTER
        the swap are re-appended to the restored delta region, so no
        write is lost to a rollback. Bumps ``build_id`` like any index
        change. Returns the new generation counter value."""
        prev = self._prev_gen
        if prev is None:
            if self.snapshot_dir is not None:
                from repro.core import persist
                persist.rollback_platform(self.snapshot_dir, into=self)
                return self.generation
            raise RuntimeError("no previous generation retained "
                               "(no swap since startup, or already "
                               "rolled back) and no snapshot_dir set")
        cur = self.delta                     # post-swap delta region
        self.raw_table = prev.raw_table
        self.table = prev.table
        self.tree = prev.tree
        self.meta = prev.meta
        self.enhanced = prev.enhanced
        self.transform = prev.transform
        self.layout = prev.layout
        self.report = prev.report
        if prev.params is not None:
            self._transform_params = prev.params
        self.delta = prev.delta
        # rows appended after the swap sit past the carried tail in the
        # current delta; re-append them so the rollback loses nothing
        if cur is not None and cur.m > prev.post_swap_tail:
            sl = slice(prev.post_swap_tail, cur.m)
            if self.delta is None:
                self.delta = DeltaRegion.for_table(self.table)
            self.delta.append(
                {k: cur.live_numeric(k)[sl] for k in cur.numeric_keys},
                {k: cur.live_vector(k)[sl] for k in cur.vector_dims},
                None if cur.raw_uri is None else cur.raw_uri[sl])
        self._fold_requested = False
        self.delta_epoch += 1
        self._view_cache = None
        self._oracle_cache.clear()
        self._engines.clear()
        self._quant_cache = None
        self.build_id += 1
        self.generation += 1
        self._prev_gen = None
        return self.generation

    # ------------------------------------------------------------ leaves
    def _leaf_rows(self, leaf_pos: int) -> np.ndarray:
        lid = self.tree.leaf_ids[leaf_pos]
        return np.arange(int(self.tree.bucket_start[lid]),
                         int(self.tree.bucket_end[lid]))

    def _count_leaf(self, lid: int):
        # Algorithm 3 statistics: node + ancestors were scanned to reach it
        node = int(self.tree.leaf_ids[lid])
        while node >= 0:
            self.tree.access_count[node] += 1
            node = int(self.tree.parent[node])

    # ------------------------------------------------------- basic queries
    def _predicate_leaves(self, q) -> np.ndarray:
        """Positions (into leaf_ids) of leaves that may contain matches."""
        m = self.meta
        if isinstance(q, Q.NE):
            return np.nonzero((m.num_lo[q.attr] <= q.value + q.tol)
                              & (m.num_hi[q.attr] >= q.value - q.tol))[0]
        if isinstance(q, Q.NR):
            return np.nonzero((m.num_lo[q.attr] <= q.hi)
                              & (m.num_hi[q.attr] >= q.lo))[0]
        if isinstance(q, Q.VR):
            qv = q.vec()
            d = np.sqrt(np.maximum(((m.vec_centroid[q.attr] - qv) ** 2)
                                   .sum(1), 0))
            return np.nonzero(d - m.vec_radius[q.attr] <= q.radius)[0]
        raise TypeError(q)

    def _mask_from_predicate(self, q, stats: QueryStats) -> np.ndarray:
        """Exact boolean mask over physical rows for NE/NR/VR (delta
        rows, when present, occupy the tail ``n_base..n_base+m-1`` and
        are scanned directly — the delta has no leaf metadata yet)."""
        nb = self.table.n_rows
        mask = np.zeros(nb + self.n_delta, bool)
        if self.n_delta:
            stats.rows_scanned += self.n_delta
            if isinstance(q, Q.NE):
                col = self.delta.live_numeric(q.attr)
                mask[nb:] = np.abs(col - q.value) <= q.tol
            elif isinstance(q, Q.NR):
                col = self.delta.live_numeric(q.attr)
                mask[nb:] = (col >= q.lo) & (col <= q.hi)
            else:  # VR
                col = self.delta.live_vector(q.attr)
                mask[nb:] = ((col - q.vec()) ** 2).sum(1) <= q.radius ** 2
        for lp in self._predicate_leaves(q):
            stats.touch(lp)
            self._count_leaf(lp)
            rows = self._leaf_rows(lp)
            stats.rows_scanned += len(rows)
            if isinstance(q, Q.NE):
                col = self.table.numeric[q.attr][rows]
                mask[rows] = np.abs(col - q.value) <= q.tol
            elif isinstance(q, Q.NR):
                col = self.table.numeric[q.attr][rows]
                mask[rows] = (col >= q.lo) & (col <= q.hi)
            else:  # VR
                col = self.table.vector[q.attr][rows]
                d2 = ((col - q.vec()) ** 2).sum(1)
                mask[rows] = d2 <= q.radius ** 2
        return mask

    def _knn(self, q: Q.VK, stats: QueryStats,
             row_mask: Optional[np.ndarray] = None) -> np.ndarray:
        """Exact per-attribute KNN via leaf lower-bound ranking, with
        live delta rows brute-force merged in after the leaf scan (the
        stable merge keeps base rows ahead of delta rows on exact
        distance ties, matching the combined-view oracle's row
        order)."""
        m = self.meta
        qv = q.vec()
        col = self.table.vector[q.attr]
        nb = self.table.n_rows
        dc = np.sqrt(np.maximum(((m.vec_centroid[q.attr] - qv) ** 2)
                                .sum(1), 0))
        lb = np.maximum(dc - m.vec_radius[q.attr], 0.0)
        order = np.argsort(lb, kind="stable")
        best_d = np.full(q.k, np.inf)
        best_i = np.full(q.k, -1, np.int64)
        for pos in order:
            if lb[pos] > best_d[-1]:
                break
            stats.touch(pos)
            self._count_leaf(pos)
            rows = self._leaf_rows(pos)
            stats.rows_scanned += len(rows)
            d2 = ((col[rows] - qv) ** 2).sum(1)
            if row_mask is not None:
                d2 = np.where(row_mask[rows], d2, np.inf)
            d = np.sqrt(np.maximum(d2, 0))
            alld = np.concatenate([best_d, d])
            alli = np.concatenate([best_i, rows])
            sel = np.argsort(alld, kind="stable")[:q.k]
            best_d, best_i = alld[sel], alli[sel]
        if self.n_delta:
            dcol = self.delta.live_vector(q.attr)
            d2 = ((dcol - qv) ** 2).sum(1)
            if row_mask is not None:
                d2 = np.where(row_mask[nb:], d2, np.inf)
            stats.rows_scanned += self.n_delta
            alld = np.concatenate([best_d, np.sqrt(np.maximum(d2, 0))])
            alli = np.concatenate([best_i, nb + np.arange(self.n_delta)])
            sel = np.argsort(alld, kind="stable")[:q.k]
            keep = np.isfinite(alld[sel])
            best_d, best_i = alld[sel], np.where(keep, alli[sel], -1)
        return best_i[best_i >= 0]

    # ------------------------------------------------------------- execute
    def execute(self, query: Q.Query, *, task: str = "",
                record: bool = True) -> Tuple[np.ndarray, QueryStats]:
        """Execute a rich hybrid query through the learned index."""
        assert self.tree is not None, "call prepare() first"
        t0 = time.time()
        stats = QueryStats()
        rows = self._exec(query, stats, row_mask=None)
        stats.time_s = time.time() - t0
        stats.cbr = stats.buckets_touched / max(1, len(self.tree.leaf_ids))
        if record:
            truth = self.oracle(query)
            self.qbs.maybe_record(
                statement=repr(query), object_set=self.table.name,
                attributes=Q.query_attrs(query), types=Q.query_types(query),
                recall_at_k=recall_at_k(rows, truth),
                cbr=stats.cbr, query_time_s=stats.time_s,
                accuracy=accuracy(rows, truth), task=task)
            self.qbs.record_workload(Q.signature(Q.normalize(query)),
                                     query)
        return rows, stats

    def _exec(self, q, stats: QueryStats,
              row_mask: Optional[np.ndarray]) -> np.ndarray:
        n = self.table.n_rows + self.n_delta
        if isinstance(q, (Q.NE, Q.NR, Q.VR)):
            mask = self._mask_from_predicate(q, stats)
            if row_mask is not None:
                mask &= row_mask
            return np.nonzero(mask)[0]
        if isinstance(q, Q.VK):
            return self._knn(q, stats, row_mask)
        if isinstance(q, Q.And):
            preds = [p for p in q.parts if not isinstance(p, Q.VK)]
            vks = [p for p in q.parts if isinstance(p, Q.VK)]
            mask = row_mask if row_mask is not None else None
            for p in preds:
                rows = self._exec(p, stats, mask)
                pm = np.zeros(n, bool)
                pm[rows] = True
                mask = pm if mask is None else (mask & pm)
            if not vks:
                return np.nonzero(mask)[0] if mask is not None else \
                    np.arange(n)
            result = None
            for vk in vks:
                rows = self._knn(vk, stats, mask)
                rm = np.zeros(n, bool)
                rm[rows] = True
                result = rm if result is None else (result & rm)
            return np.nonzero(result)[0]
        if isinstance(q, Q.Or):
            out = np.zeros(n, bool)
            for p in q.parts:
                out[self._exec(p, stats, row_mask)] = True
            return np.nonzero(out)[0]
        raise TypeError(q)

    def _resolve_precision(self, precision: Optional[str]) -> str:
        """Scan-precision resolution: explicit argument > MQRLD_PRECISION
        env > the platform's persisted ``default_precision``. Explicit
        wins over the env so a test that pins fp32 stays fp32 under a
        forced-int8 CI rerun."""
        import os
        from repro.utils.quant import PRECISIONS
        p = precision or os.environ.get("MQRLD_PRECISION") \
            or self.default_precision
        if p not in PRECISIONS:
            raise ValueError(
                f"precision must be one of {PRECISIONS}, got {p!r}")
        return p

    # ------------------------------------------------------- batched engine
    def engine(self, *, interpret: bool = True, beam: int = 16,
               tile: int = 128,
               device_loop: Optional[bool] = None,
               shards: Optional[int] = None,
               precision: Optional[str] = None):
        """The device-resident batched executor for this table (built
        lazily, invalidated by ``prepare``). ``device_loop`` sets the
        engine's default KNN beam-loop implementation (device
        ``lax.while_loop`` vs the host-driven exactness oracle) only
        when passed explicitly — None leaves a cached engine's
        configured default untouched — and is also a per-call override
        on ``execute_batch``; it never forces a rebuild of device
        state. ``shards`` (None = the platform's ``default_shards``;
        0 = force the single-device paths) lays the tile-major state
        out over an N-device ("shards",) mesh — the sharded execution
        path; each topology keeps its own cached engine. ``precision``
        (None = MQRLD_PRECISION env, then ``default_precision``) selects
        the mixed-precision tile scan — results stay row-identical to
        fp32; each precision keeps its own cached engine."""
        assert self.tree is not None, "call prepare() first"
        from repro.core.engine import HybridEngine
        if shards is None:
            shards = self.default_shards
        shards = shards or None
        prec = self._resolve_precision(precision)
        key = self._engine_key(interpret, beam, tile, shards, prec)
        eng = self._engines.get(key)
        if eng is None:
            # bounded LRU: each engine pins device-resident copies of
            # the whole table, so a long-lived process sweeping configs
            # (e.g. the bench's shard sweep) must not accumulate one
            # footprint per configuration ever touched. Eviction only
            # drops derived state — a re-request rebuilds it.
            while len(self._engines) >= 4:
                self._engines.pop(next(iter(self._engines)))
            eng = self._engines[key] = HybridEngine(
                self.tree, self.table, self.meta, interpret=interpret,
                beam=beam, tile=tile,
                device_loop=True if device_loop is None else device_loop,
                shards=shards, precision=prec,
                quant_cache=self._quant_cache)
        else:
            self._engines.pop(key)     # re-insert: keep LRU order
            self._engines[key] = eng
            if device_loop is not None:
                eng.device_loop = device_loop
        # refresh on EVERY call (cache hits included): cached engines
        # may predate a calibration — or a reloaded/refit model — and
        # the V.R dense-vs-tile decision reads it per batch
        eng.cost_model = self.cost_model
        # union any un-folded appends into the device state (no-op when
        # the write epoch is unchanged)
        eng.sync_delta(self.delta, self.delta_epoch)
        return eng

    def session(self, *, interpret: bool = True,
                device_loop: bool = True, beam: int = 16,
                tile: int = 128, shards: Optional[int] = None,
                precision: Optional[str] = None):
        """The MOAPI v2 entry point: a ``repro.core.planner.Session``
        over this platform (cached per configuration). Use
        ``session().plan(queries)`` for an ``ExecutablePlan`` with
        ``execute()`` / ``explain()``; the session's plan cache
        survives across batches and is invalidated by ``prepare()``
        through ``build_id``. ``shards`` (None = ``default_shards``)
        selects the sharded execution topology; plans cache per
        topology and ``explain()`` reports it. ``precision`` (None =
        MQRLD_PRECISION env, then ``default_precision``) selects the
        mixed-precision tile scan; plans cache per precision."""
        from repro.core.planner import Session
        # resolve to the EFFECTIVE topology here so the cache can never
        # alias a forced-off session (shards=0) with a defaulted one,
        # and Session cannot re-resolve 0 back to the default
        eff = self.default_shards if shards is None else shards
        eff = eff or None
        prec = self._resolve_precision(precision)
        # topology autonomy: only a session whose topology NOBODY
        # pinned (no ``shards`` argument, no platform default) lets
        # the calibrated cost model roam over shard counts; explicit
        # pins (including shards=0) restrict it to host-vs-configured.
        # Part of the cache key — shards=0 and shards=None resolve to
        # the same ``eff`` but mean different things here.
        auto = shards is None and self.default_shards is None
        key = (interpret, device_loop, beam, tile, eff, prec, auto)
        if key not in self._sessions:
            self._sessions[key] = Session(
                self, interpret=interpret, device_loop=device_loop,
                beam=beam, tile=tile,
                shards=0 if eff is None else eff, precision=prec,
                auto_topology=auto)
        return self._sessions[key]

    def calibrate(self, *, shard_counts=None, batch: int = 16,
                  repeats: int = 2, seed: int = 0):
        """Fit (or refresh) this host's execution cost model from a
        synthetic micro-run sweep (``repro.core.cost
        .calibrate_platform``) and install it as ``self.cost_model``
        — from then on ``Session.plan`` chooses loop kind / shard
        topology / beam budget and the engine chooses the V.R
        dense-vs-tile route by predicted cost, with observed stage
        times recalibrating the model online. Persisted by
        ``save_platform`` as ``cost_model.json``. Survives
        swap()/rollback() (a host property, not an index property)."""
        from repro.core.cost import calibrate_platform
        return calibrate_platform(self, shard_counts=shard_counts,
                                  batch=batch, repeats=repeats,
                                  seed=seed)

    def execute_batch(self, queries: Sequence[Q.Query], *,
                      interpret: bool = True,
                      device_loop: bool = True):
        """DEPRECATED v1 shim — ``session().plan(queries).execute()``
        with identical results and stats.

        Returns (results, EngineStats): one row array per query, exactly
        the rows scalar ``execute`` returns (top-level V.K results are
        distance-ordered, everything else ascending row ids). Queries
        outside the engine's plannable fragment (see
        ``repro.core.engine.plannable``) fall back to the scalar path.
        ``device_loop=False`` routes V.K beams through the host-driven
        loop (the exactness oracle) instead of the on-device
        ``lax.while_loop``. No QBS *row* recording happens here (replay
        on ``execute`` for that); KNN convergence widths are recorded
        for query-aware beam seeding, like every planned execution.
        """
        return self.session(interpret=interpret).plan(
            queries, device_loop=device_loop).execute()

    # ------------------------------------------------------------- oracle
    def oracle(self, query: Q.Query) -> np.ndarray:
        """Brute-force truth over the queryable view (base + live
        delta); cached per (query, build, write epoch) so appends and
        folds can never serve stale truths."""
        key = (repr(query), self.build_id, self.delta_epoch)
        if key not in self._oracle_cache:
            self._oracle_cache[key] = Q.execute_bruteforce(self.view(),
                                                           query)
        return self._oracle_cache[key]

    # -------------------------------------------------- query-aware tuning
    def optimize_index(self, workload: Sequence[Q.Query],
                       tie_break: bool = False) -> int:
        """Algorithm 3: run the workload to collect access counts, then
        reorder sibling lists."""
        self.tree.access_count[:] = 0
        for q in workload:
            self.execute(q, record=False)

        cost_fn = None
        if tie_break:
            def cost_fn():
                total = 0
                for q in workload:
                    _, st = self.execute(q, record=False)
                    total += st.nodes_scanned
                return total
        return reorder_siblings(self.tree, cost_fn)

    def objectives_for_morbo(self, workload: Sequence[Q.Query]):
        """(time, CBR, -accuracy) evaluator over (theta, delta_scales) for
        the MORBO transform optimization (paper Algorithm 1)."""
        def f(params: np.ndarray) -> np.ndarray:
            k = len(params) // 2
            theta, dscale = params[:k], params[k:]
            self.prepare(use_transform=True, use_lpgf=False,
                         theta=theta, delta_scales=dscale)
            times, cbrs, accs = [], [], []
            for q in workload:
                rows, st = self.execute(q, record=False)
                truth = self.oracle(q)
                times.append(st.time_s)
                cbrs.append(st.cbr)
                accs.append(accuracy(rows, truth))
            return np.array([np.mean(times), np.mean(cbrs),
                             -np.mean(accs)])
        return f
