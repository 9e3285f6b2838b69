"""Batched, device-resident hybrid-query engine — one compiled path from
MOAPI query trees to the Pallas kernels.

The scalar path (``MQRLD.execute``) walks the cluster tree per query in
host Python: faithful to the paper, and the source of QBS statistics. This
module is the serving path: it holds the cluster-tree leaves, padded bucket
tiles and per-attribute exact-space metadata (``LeafMeta``) as device
arrays, plans a *batch* of heterogeneous ``Q.Query`` trees into a fixed set
of vectorized stages, and executes them with a handful of compiled calls
regardless of batch size:

  1. **Leaf pruning** — for every distinct basic predicate, a (g, L)
     leaf-survival matrix from per-attribute centroid/radius balls (V.R)
     and [min, max] boxes (N.E/N.R), expanded to rows through the
     row->leaf map.
  2. **Predicate masks** — exact (g, n) boolean masks per (type, attr)
     group: one fused compare for numeric groups, one pairwise-L2 kernel
     call for vector groups.
  3. **Masked KNN** — every V.K node in the batch becomes a job; jobs are
     grouped per attribute and leaf-scanned through the Pallas
     ``fused_topk`` row-mask kernel (``ops.topk_l2_masked``): each beam
     round gathers every query's W best-lower-bound buckets into a
     (G, W*cap, d) candidate tile and keeps a fused running top-k.
     And(VK, predicate) stays fused by folding the predicate mask into
     the kernel's validity mask.

Execution-path flag (``device_loop``): the engine keeps two complete
query paths that return identical rows.

  * ``device_loop=False`` — the exactness oracle (the original serving
    path): the KNN beam loop is ``batched_knn``, beam *doubling* driven
    from host Python with one compiled round call plus one device->host
    merge per round (2-4 transfers per batch), and V.R predicates mask
    the full column. Keep this path as the reference when changing the
    device path.
  * ``device_loop=True`` (the default) — the device-resident path:
    ``batched_knn_device`` runs one fused first round over the whole
    batch, then finishes the stragglers inside a single
    ``jax.lax.while_loop`` (``_knn_device_loop``) that carries the
    per-query top-k heap and active mask as loop state, calls the same
    ``ops.topk_l2_masked`` kernel per round, and retires a query once
    its kth distance <= the next unscanned lower bound — the scalar
    executor's stopping rule, with a fixed round budget of
    ceil(T / W) as the worst-case backstop, so the loop is exact even
    when the rule never fires. V.R predicates route through the same
    tile beam (below) instead of the full column.

Sharded execution (``shards``): the tile-major layout shards along T
over a ("shards",) device mesh (``repro.sharding.partitioning`` is the
placement layer: strided tile assignment, pad tiles with -inf radii).
``batched_knn_sharded`` mirrors the device loop — fused per-shard
start, one active-mask transfer, compacted straggler ``while_loop`` —
with each round's per-shard top-k heaps merged by an all-gather k-way
merge and the stopping rule evaluated against the pmin of the shards'
next local bounds; ``_sharded_vr_fns`` runs the V.R triangle bound and
union GEMM per shard with a host count/concat epilogue. Delta tiles are
replicated (live on shard 0 only), preserving freshness-exactness
verbatim. Every shard count returns an exact top-k — row-identical to
the single-device loop whenever kth-boundary distances are unique (an
exact tie at the boundary may resolve to a different equally-distant
row); the single-device paths remain the exactness oracle. See the
"Sharded multi-device execution" section below for layout/merge
contracts.

V.R routing (device path): the tile-level planner ``_vr_leaf_plan``
keeps only tiles satisfying the triangle bound |q - C| - R <= r (C, R
the tile ball; r the query radius), distances are evaluated on the
gathered surviving tiles alone, and rows within fp noise of the
boundary are re-checked on the host with the exact formula. When the
bound is unselective (surviving tiles cover more than
``_VR_DENSE_CUTOFF`` of the table) the planner falls back to the dense
full-column mask (also the oracle path's behavior), which is cheaper
than a near-total gather. With a calibrated cost model attached
(``cost_model``, see ``repro.core.cost``) the dense-vs-tile decision is
made by predicted cost instead of the fixed cutoff — the static
threshold remains as the uncalibrated fallback — and every executed
KNN/V.R stage reports (kind, features, seconds) through
``EngineStats.stage_samples`` so the model recalibrates online.

Mixed-precision tile scan (``precision``: "fp32" | "bf16" | "int8"):
both KNN beam loops can run their tile distances in reduced precision
WITHOUT changing results. At prepare time each tile layout is quantized
once into per-tile symmetric planes (``repro.utils.quant.plan_tiles``;
delta tiles get their own scales at ``sync_delta``); each round then
scans the narrow codes, widens the result by the analytic quantization
error bound into a valid *lower* bound on the true distance
(conservative-bound contract — the bound may be loose, never violated;
see ``ops.topk_l2_masked_mp``), refutes candidates whose bound strictly
exceeds the running kth distance exactly like the ball-bound early-out,
and rescores the surviving frontier in exact fp32. Returned rows are
identical to the fp32 path on every loop (host, device, sharded) and
over base+delta; only the rescue *work* varies (``EngineStats``
``mp_rescued``/``mp_scanned`` is the observability knob). The V.R
predicate path intentionally stays fp32 — its triangle bound already
prunes on ball metadata before the union GEMM.

Planner integration (MOAPI v2): ``execute_batch`` accepts a pre-built
``EnginePlan`` from ``repro.core.planner`` — the cached-per-archetype job
layout, KNN grouping (``KnnGroupSpec``), and QBS-seeded first-round beam
widths — instead of re-deriving them per batch; every executed KNN group
reports its converged width back through ``EngineStats.knn_group_widths``
(keyed by ``knn_archetype``), closing the paper's query-aware feedback
loop over execution parameters.

Execution contract (scalar vs batched): ``execute_batch`` returns exactly
the rows of scalar ``execute`` for every query archetype whose V.K
candidate masks are derivable from predicate-only subtrees — V.K at top
level, under Or, or as a direct child of And whose other parts are VK-free
(this covers all MOAPI archetypes in tests/ and the paper's rich hybrid
queries). For the one order-dependent corner the scalar path permits (a VK
nested inside a combiner that is itself a *sibling* of other And parts,
where ``_exec`` threads partially-accumulated masks), ``plannable`` returns
False and ``MQRLD.execute_batch`` falls back to the scalar path for that
query. Row order: top-level V.K results are distance-ordered (ties by
bucket-beam order, matching the scalar executor's visit order); every other
result is ascending row ids.
"""
from __future__ import annotations

import functools
import time
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cost as costm
from repro.core import query as Q
from repro.core.lake import _next_pow2
from repro.kernels import ops
from repro.sharding.partitioning import (manual_shard_map, shard_put,
                                         strided_tile_layout, tile_mesh)


# ---------------------------------------------------------------------------
# Device leaf state
# ---------------------------------------------------------------------------
@dataclass
class LeafGeometry:
    """Struct-of-arrays for one vector space over the shared bucket layout:
    per-leaf ball metadata plus padded bucket row tiles."""
    centroid: jax.Array      # (L, d)
    radius: jax.Array        # (L,)
    bucket_rows: jax.Array   # (L, cap) int32; -1 = padding
    cap: int

    @property
    def n_leaves(self) -> int:
        return int(self.centroid.shape[0])


def bucket_tiles(starts: np.ndarray, ends: np.ndarray, tile: int = 0
                 ) -> Tuple[np.ndarray, int, np.ndarray]:
    """Padded physical-row tiles from leaf [start, end) ranges.

    tile=0: one tile per leaf, cap = max bucket size. tile>0: each leaf is
    split into fixed ``tile``-row chunks — buckets vary 10-30x in size, so
    fixed chunks keep the padding waste of the (T, cap) gather bounded at
    <2x instead of max/mean. Returns (rows (T, cap), cap, leaf_of_tile
    (T,)); chunks of one leaf are consecutive, so a stable lower-bound sort
    preserves the scalar executor's bucket visit order.
    """
    starts = np.asarray(starts)
    ends = np.asarray(ends)
    if tile <= 0:
        sizes = ends - starts
        cap = int(sizes.max(initial=1))
        rows = np.full((len(starts), cap), -1, np.int32)
        for i, (s, e) in enumerate(zip(starts, ends)):
            rows[i, :e - s] = np.arange(s, e, dtype=np.int32)
        return rows, cap, np.arange(len(starts), dtype=np.int32)
    chunks: List[np.ndarray] = []
    leaf_of_tile: List[int] = []
    for i, (s, e) in enumerate(zip(starts, ends)):
        for c0 in range(int(s), int(e), tile):
            chunks.append(np.arange(c0, min(c0 + tile, int(e)),
                                    dtype=np.int32))
            leaf_of_tile.append(i)
    if not chunks:  # degenerate: no rows at all
        chunks.append(np.empty(0, np.int32))
        leaf_of_tile.append(0)
    rows = np.full((len(chunks), tile), -1, np.int32)
    for i, c in enumerate(chunks):
        rows[i, :len(c)] = c
    return rows, tile, np.asarray(leaf_of_tile, np.int32)


def _tile_geometry(col: np.ndarray, rows_np: np.ndarray, bucket_rows,
                   cap: int) -> "LeafGeometry":
    """Per-tile ball (centroid, radius) over the tile's own rows."""
    valid = rows_np >= 0
    cnt = np.maximum(valid.sum(1), 1)
    pts = np.asarray(col, np.float32)[np.maximum(rows_np, 0)]
    pts = np.where(valid[:, :, None], pts, 0.0)
    cen = pts.sum(1) / cnt[:, None]
    d2 = ((pts - cen[:, None, :]) ** 2).sum(2)
    rad = np.sqrt(np.max(np.where(valid, d2, 0.0), axis=1))
    return LeafGeometry(centroid=jnp.asarray(cen, jnp.float32),
                        radius=jnp.asarray(rad, jnp.float32),
                        bucket_rows=bucket_rows, cap=cap)


def tile_data(col: np.ndarray, bucket_rows: np.ndarray) -> np.ndarray:
    """(n, d) column -> (T, cap, d) tile-major copy (padding rows are row 0;
    a tile's validity mask excludes them). Tiles are contiguous row runs, so
    beam rounds gather whole tiles instead of individual rows."""
    col = np.asarray(col, np.float32)
    safe = np.maximum(np.asarray(bucket_rows), 0)
    return col[safe]


@dataclass
class EngineStats:
    """Aggregate stats for one batch (the scalar path's per-query
    ``QueryStats``/QBS recording is intentionally not replicated here)."""
    queries: int = 0
    predicate_buckets: int = 0   # leaves surviving box/ball pruning
    knn_buckets: int = 0         # bucket tiles scanned across beam rounds
    rows_scanned: int = 0        # valid rows fed to the top-k kernel
    knn_rounds: int = 0
    vr_tiles_scanned: int = 0    # tiles gathered by the V.R tile planner
    vr_tiles_pruned: int = 0     # tiles dropped by the V.R triangle bound
    vr_dense_fallbacks: int = 0  # V.R groups that took the dense column path
    shards: int = 0              # 0 = unsharded; else the mesh size used
    # mixed-precision scan counters (precision != "fp32"): candidates
    # scanned in reduced precision vs candidates rescored in fp32 —
    # rescued/scanned is the rescue ratio explain() reports
    mp_scanned: int = 0
    mp_rescued: int = 0
    time_s: float = 0.0
    # (archetype, converged width in tiles) per executed KNN group — the
    # feedback signal Session records into QBS for query-aware seeding
    knn_group_widths: List[Tuple[str, int]] = field(default_factory=list)
    # (stage kind, feature vector, observed seconds) per executed KNN
    # group and V.R group (see ``repro.core.cost``) — Session feeds
    # these into the QBS cost rings, closing the calibrated cost
    # model's online-recalibration loop
    stage_samples: List[Tuple[str, Tuple[float, ...], float]] = \
        field(default_factory=list)


# ---------------------------------------------------------------------------
# Batched exact KNN over bucket tiles (one vector space)
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("w0", "w1", "k", "precision",
                                             "interpret"))
def _knn_round(act, qs, order, masks_tiles, data_tiles, bucket_rows,
               planes=None, lb_all=None, kth0_all=None, *,
               w0: int, w1: int, k: int, precision: str = "fp32",
               interpret: bool):
    """One beam round for the ``act`` query subset: scan each query's
    [w0, w1) best-lower-bound buckets with the fused distance+top-k kernel.
    Returns (sq_dists, physical rows, number of valid candidate rows,
    fp32-rescued candidate count — 0 on the fp32 path).
    Rounds are incremental — the host merges each round's top-k with the
    carry from earlier buckets. ``data_tiles`` is the (T, cap, d)
    tile-major copy of the table column: candidate gathers move whole
    contiguous tiles, not individual rows.

    Mixed precision (``precision`` != "fp32"): ``planes`` carries the
    layout's quantized tile arrays (data, scale, ppq, eps — see
    ``repro.utils.quant.plan_tiles``), ``lb_all`` the per-query sorted
    ball bounds and ``kth0_all`` (optional, (G_full,)) the host carry's
    kth SQUARED distance; the round scans the narrow codes and rescores
    only the surviving frontier in fp32 (``ops.topk_l2_masked_mp``) —
    row-identical to the fp32 scan."""
    qa = jnp.take(qs, act, axis=0)
    sel = jnp.take(order, act, axis=0)[:, w0:w1]         # (G, w1-w0)
    g, w = sel.shape
    cand = bucket_rows[sel].reshape(g, -1)               # (G, w*cap)
    valid = cand >= 0
    if masks_tiles is not None:
        ma = jnp.take(masks_tiles, act, axis=0)          # (G, T, cap)
        ma = jnp.take_along_axis(ma, sel[:, :, None], axis=1)
        valid = valid & ma.reshape(g, -1)
    if precision != "fp32":
        cap = bucket_rows.shape[1]
        lb_col = jnp.take(lb_all, act, axis=0)[:, w0:w1]
        lb2 = jnp.repeat(lb_col * lb_col, cap, axis=1)
        kth0 = None if kth0_all is None else jnp.take(kth0_all, act,
                                                      axis=0)
        d2, idx, resc = ops.topk_l2_masked_mp(
            qa, sel, valid, data_tiles, *planes, k, lb2=lb2, kth0=kth0,
            precision=precision, interpret=interpret)
    else:
        pts = jnp.take(data_tiles, sel, axis=0)          # (G, w, cap, d)
        pts = pts.reshape(g, -1, pts.shape[-1])          # (G, w*cap, d)
        d2, idx = ops.topk_l2_masked(qa, pts, valid, k,
                                     interpret=interpret)
        resc = jnp.zeros(g, jnp.int32)
    rows = jnp.take_along_axis(cand, jnp.maximum(idx, 0), axis=1)
    rows = jnp.where(idx >= 0, rows, -1)
    return d2, rows, jnp.sum(valid, axis=1), resc


@jax.jit
def _tile_masks(masks, bucket_rows):
    """Re-layout per-row masks (G, n) into tile-major (G, T, cap) once per
    KNN group, so beam rounds gather masks by tile index."""
    t, cap = bucket_rows.shape
    flat = jnp.maximum(bucket_rows.reshape(-1), 0)
    return jnp.take(masks, flat, axis=1).reshape(masks.shape[0], t, cap)


@jax.jit
def _knn_prologue(qs, centroid, radius, masks_tiles=None):
    """Per-query leaf lower bounds, visit order, and sorted bounds.

    With a row mask, tiles holding NO masked rows get lb = +inf: they sort
    last and the stopping bound treats them as exhausted, so a selective
    filter (the And(VK, predicate) case) scans only the filter's own tiles
    instead of expanding the beam across the whole table."""
    d2c = ops.pairwise_sq_l2(qs, centroid)
    dc = jnp.sqrt(jnp.maximum(d2c, 0.0))
    lb = jnp.maximum(dc - radius[None, :], 0.0)          # (G, L)
    if masks_tiles is not None:
        lb = jnp.where(jnp.any(masks_tiles, axis=2), lb, jnp.inf)
    order = jnp.argsort(lb, axis=1)
    return order, jnp.take_along_axis(lb, order, axis=1)


def batched_knn(geom: LeafGeometry, data_tiles, qs, k: int, *,
                masks: Optional[jax.Array] = None, beam: int = 8,
                interpret: bool = True, planes=None,
                precision: str = "fp32",
                stats: Optional[EngineStats] = None,
                conv_out: Optional[list] = None
                ) -> Tuple[np.ndarray, np.ndarray]:
    """Exact batched (optionally row-masked) KNN.

    qs: (G, d); data_tiles: (T, cap, d) tile-major device copy of the
    column (see ``tile_data``); masks: optional (G, n) bool device.
    Returns (dists (G, k) fp32 L2, rows (G, k) int; -1/inf pad slots).

    Exactness: leaves are ranked per query by the lower bound
    max(0, |q - C| - R); after scanning the top-w, the result is final once
    the kth masked distance <= the (w+1)-th lower bound — identical to the
    scalar executor's stopping rule, with the beam doubling host-driven so
    every round is one fixed-shape compiled call. Rounds are incremental
    (each scans only the newly admitted buckets and merges with the carry),
    queries whose bound is met leave the batch, and straggler subsets are
    padded to powers of two so compiled round shapes stay bounded.

    ``conv_out``: when a list is passed, one (g,) int64 array is appended
    with each query's converged beam width — the number of sorted-bound
    tiles admitted when its stopping rule fired (granularity: the round
    widths actually scanned). The QBS convergence signal.
    """
    t0 = time.time()
    qs = jnp.asarray(qs, jnp.float32)
    masks_tiles = None
    if masks is not None:
        masks_tiles = _tile_masks(jnp.asarray(masks), geom.bucket_rows)
    g = int(qs.shape[0])
    l = geom.n_leaves
    # same packed int32 single-key bound sort as the device path (several
    # times faster than XLA's variadic argsort on CPU); the truncated
    # bound only ever LOWERS lb, so the stopping rule stays conservative
    # and the loop exact. Reference argsort kept for > 4096 tiles.
    prologue = _knn_prologue_fast if l <= 4096 else _knn_prologue
    order, lb_sorted = prologue(qs, geom.centroid, geom.radius,
                                masks_tiles)
    lb_dev = lb_sorted                     # device copy for the mp rounds
    lb_sorted = np.asarray(lb_sorted)
    best_d2 = np.full((g, k), np.inf, np.float32)
    best_r = np.full((g, k), -1, np.int64)
    conv = np.zeros(g, np.int64)
    active = np.arange(g)
    w0, w = 0, max(1, min(beam, l))
    first = True
    while len(active):
        na = len(active)
        gp = _next_pow2(na)
        padded = np.zeros(gp, np.int32)
        padded[:na] = active
        kth0_all = None
        if precision != "fp32" and not first:
            # host carry's kth SQUARED distance tightens the mp round's
            # refutation from its first rescue iteration
            kth0_all = jnp.asarray(best_d2[:, -1])
        d2, rows, nvalid, resc = _knn_round(
            jnp.asarray(padded), qs, order, masks_tiles,
            data_tiles, geom.bucket_rows, planes, lb_dev, kth0_all,
            w0=w0, w1=w, k=k, precision=precision, interpret=interpret)
        first = False
        d2 = np.asarray(d2[:na])
        rows = np.asarray(rows[:na])
        if stats is not None:
            stats.knn_rounds += 1
            stats.knn_buckets += na * (w - w0)
            nv = int(np.asarray(nvalid)[:na].sum())
            stats.rows_scanned += nv
            if precision != "fp32":
                stats.mp_scanned += nv
                stats.mp_rescued += int(np.asarray(resc)[:na].sum())
        # host merge with the carry: carried entries come from
        # earlier (lower-lb) buckets, so a stable sort keeps the scalar
        # executor's visit-order tie-break
        alld = np.concatenate([best_d2[active], d2], axis=1)
        allr = np.concatenate([best_r[active], rows], axis=1)
        pick = np.argsort(alld, axis=1, kind="stable")[:, :k]
        merged_d = np.take_along_axis(alld, pick, axis=1)
        merged_r = np.take_along_axis(allr, pick, axis=1)
        best_d2[active] = merged_d
        best_r[active] = merged_r
        kth = np.sqrt(merged_d[:, -1])
        nxt = lb_sorted[active, w] if w < l else np.full(na, np.inf)
        done = (kth <= nxt) | (w >= l)
        conv[active[done]] = w
        active = active[~done]
        w0, w = w, min(2 * w, l)
    if stats is not None:
        stats.time_s += time.time() - t0
    if conv_out is not None:
        conv_out.append(conv)
    return np.sqrt(best_d2), best_r


# ---------------------------------------------------------------------------
# Device-resident beam loop (lax.while_loop variant of batched_knn)
# ---------------------------------------------------------------------------
@functools.partial(jax.jit,
                   static_argnames=("w1", "w", "budget", "k", "precision",
                                    "interpret"))
def _knn_device_loop(idx, active0, qs_full, d2_full, rows_full, order,
                     lb_sorted, masks_tiles, data_tiles, bucket_rows,
                     planes=None, *, w1: int, w: int, budget: int, k: int,
                     precision: str = "fp32", interpret: bool):
    """The straggler beam loop as one compiled call (see module
    docstring): compaction gathers, the ``lax.while_loop``, and the
    stats reduction all land in a single dispatch.

    ``idx`` selects the straggler subset (padded to a power of two so
    compiled shapes stay bounded; ``active0`` marks the real rows) out
    of the full-batch arrays; the first fused round's (d2, rows) seed
    the per-query top-k carry, and each straggler keeps its own
    remaining visit order (columns past ``w1``), padded to the loop's
    static budget*w width with 0-columns whose +inf lower bound kills
    them. Returns (best_d2, best_rows, [rounds, buckets_scanned,
    rows_scanned], per-query retirement round)."""
    l = order.shape[1]
    qs = jnp.take(qs_full, idx, axis=0)
    bd0 = jnp.take(d2_full, idx, axis=0)
    br0 = jnp.take(rows_full, idx, axis=0)
    order_pad = jnp.pad(jnp.take(order, idx, axis=0)[:, w1:],
                        ((0, 0), (0, budget * w - (l - w1))))
    lb_pad = jnp.pad(jnp.take(lb_sorted, idx, axis=0)[:, w1:],
                     ((0, 0), (0, budget * w + 1 - (l - w1))),
                     constant_values=jnp.inf)
    if masks_tiles is not None:
        masks_tiles = jnp.take(masks_tiles, idx, axis=0)
    g = qs.shape[0]

    def cond(st):
        r, active = st[0], st[1]
        return (r < budget) & jnp.any(active)

    def body(st):
        r, active, bd, br, nbuck, nrows, nresc, rr = st
        start = r * w
        sel = jax.lax.dynamic_slice_in_dim(order_pad, start, w, axis=1)
        lb_col = jax.lax.dynamic_slice_in_dim(lb_pad, start, w, axis=1)
        # columns whose lower bound is +inf are padding, or real tiles
        # with no mask-surviving rows — neither can contribute a row
        colv = ~jnp.isinf(lb_col)                        # (G, w)
        cand = bucket_rows[sel].reshape(g, -1)           # (G, w*cap)
        valid = ((cand >= 0) & jnp.repeat(colv, bucket_rows.shape[1],
                                          axis=1) & active[:, None])
        if masks_tiles is not None:
            ma = jnp.take_along_axis(masks_tiles, sel[:, :, None], axis=1)
            valid = valid & ma.reshape(g, -1)
        # per-candidate squared tile bounds: the kernel's tile early-out
        # skips a block's distance+merge once every valid candidate in
        # it is bound-refuted by the running kth (converged queries stop
        # paying for straggler tiles)
        lb2 = jnp.repeat(lb_col * lb_col, bucket_rows.shape[1], axis=1)
        if precision != "fp32":
            # the carry's kth squared distance refutes quantized
            # candidates before any fp32 rescore (exact: the widened
            # bound is a true lower bound, strict-exceed only)
            d2, idx, resc = ops.topk_l2_masked_mp(
                qs, sel, valid, data_tiles, *planes, k, lb2=lb2,
                kth0=bd[:, -1], precision=precision, interpret=interpret)
        else:
            pts = jnp.take(data_tiles, sel, axis=0)      # (G, w, cap, d)
            pts = pts.reshape(g, -1, pts.shape[-1])
            d2, idx = ops.topk_l2_masked(qs, pts, valid, k,
                                         interpret=interpret, lb2=lb2)
            resc = jnp.zeros(g, jnp.int32)
        rows = jnp.take_along_axis(cand, jnp.maximum(idx, 0), axis=1)
        rows = jnp.where(idx >= 0, rows, -1)
        # merge with the carry: carry first, lax.top_k is stable, so
        # earlier (lower-lb) buckets keep the scalar executor's
        # visit-order tie-break; inactive queries contribute only +inf
        # candidates (valid was zeroed), so their carry is a fixed point
        alld = jnp.concatenate([bd, d2], axis=1)
        allr = jnp.concatenate([br, rows], axis=1)
        negd, pick = jax.lax.top_k(-alld, k)
        md = -negd
        mr = jnp.take_along_axis(allr, pick, axis=1)
        kth = jnp.sqrt(md[:, -1])
        nxt = jax.lax.dynamic_slice_in_dim(lb_pad, start + w, 1,
                                           axis=1)[:, 0]
        active2 = active & ~(kth <= nxt)
        # per-query retirement round (for QBS convergence widths)
        rr = jnp.where(active & ~active2, r + 1, rr)
        nbuck = nbuck + jnp.sum(jnp.where(active[:, None], colv, False))
        nrows = nrows + jnp.sum(valid)
        nresc = nresc + jnp.sum(resc)
        return r + 1, active2, md, mr, nbuck, nrows, nresc, rr

    st0 = (jnp.int32(0), active0, bd0, br0,
           jnp.int32(0), jnp.int32(0), jnp.int32(0),
           jnp.zeros(g, jnp.int32))
    r, act_f, bd, br, nbuck, nrows, nresc, rr = \
        jax.lax.while_loop(cond, body, st0)
    rr = jnp.where(act_f, r, rr)  # budget-exhausted: scanned everything
    return bd, br, jnp.stack([r, nbuck, nrows, nresc]), rr


_ROUND_BYTES = 256 << 20   # cap on one straggler round's gathered fp32
#                            (G, W, cap, d) candidate tile


def _round_tiles(w: int, g: int, cap: int, d: int) -> int:
    """Straggler round width ``w`` clamped so the round's candidate
    gather stays within ``_ROUND_BYTES``, floored to a power of two (the
    compiled-shape universe stays bounded). QBS seeds can ask for the
    whole table in one round when queries converge late — held-out
    queries in high dimension do — and a width-T gather of a 1M-row
    table does not fit in device memory."""
    fit = max(1, _ROUND_BYTES // (4 * max(1, g * cap * d)))
    return max(1, min(w, 1 << (fit.bit_length() - 1)))


@jax.jit
def _knn_prologue_fast(qs, centroid, radius, masks_tiles=None):
    """``_knn_prologue`` with a packed single-key sort (both loops use
    it below 4096 tiles; the reference prologue above is kept for
    larger tile counts).

    The fp32 lower bound's bit pattern is order-preserving for
    non-negative floats (+inf included), so bound and tile index can
    share one int32 key: the low 12 mantissa bits are TRUNCATED and
    replaced by the tile index (< 4096 tiles; ``batched_knn_device``
    falls back to the reference prologue above that). XLA then sorts
    one integer tensor instead of a variadic (float, index) pair —
    several times faster on CPU. Truncation only LOWERS the reported
    bound, so the stopping rule stays conservative and the loop exact;
    near-equal bounds order by tile index, which is also the reference
    tie-break."""
    d2c = ops.pairwise_sq_l2(qs, centroid)
    dc = jnp.sqrt(jnp.maximum(d2c, 0.0))
    lb = jnp.maximum(dc - radius[None, :], 0.0)          # (G, L)
    if masks_tiles is not None:
        lb = jnp.where(jnp.any(masks_tiles, axis=2), lb, jnp.inf)
    bits = jax.lax.bitcast_convert_type(lb, jnp.int32)
    l = lb.shape[1]
    key = jnp.sort((bits & ~jnp.int32(4095))
                   | jnp.arange(l, dtype=jnp.int32)[None, :], axis=1)
    order = key & 4095
    lb_sorted = jax.lax.bitcast_convert_type(key & ~jnp.int32(4095),
                                             jnp.float32)
    return order, lb_sorted


@functools.partial(jax.jit, static_argnames=("w1", "k", "precision",
                                             "interpret"))
def _knn_start(qs, masks_tiles, centroid, radius, data_tiles,
               bucket_rows, planes=None, *, w1: int, k: int,
               precision: str = "fp32", interpret: bool):
    """Fused prologue + first beam round over the full batch + the
    stopping rule: a query stays active iff its kth distance exceeds
    the next unscanned lower bound (the scalar executor's rule). One
    dispatch; only the (G,) active mask and the stats scalars leave the
    device before the straggler loop."""
    g = qs.shape[0]
    prologue = _knn_prologue_fast if centroid.shape[0] <= 4096 \
        else _knn_prologue
    order, lb_sorted = prologue(qs, centroid, radius, masks_tiles)
    l = lb_sorted.shape[1]
    d2, rows, nvalid, resc = _knn_round(
        jnp.arange(g, dtype=jnp.int32), qs, order, masks_tiles,
        data_tiles, bucket_rows, planes, lb_sorted, None,
        w0=0, w1=w1, k=k, precision=precision, interpret=interpret)
    kth = jnp.sqrt(d2[:, -1])
    nxt = lb_sorted[:, w1] if w1 < l else \
        jnp.full(g, jnp.inf, jnp.float32)
    return (order, lb_sorted, d2, rows, kth > nxt, jnp.sum(nvalid),
            jnp.sum(resc))


def _start_d2h(a) -> None:
    """Kick off a non-blocking device->host copy for ``a`` so a later
    ``np.asarray(a)`` is a completed-transfer fence rather than a
    blocking round-trip. Best-effort: silently a no-op for backends or
    array types without the API (numpy inputs, older jax)."""
    try:
        a.copy_to_host_async()
    except (AttributeError, RuntimeError, TypeError):
        pass


class _PendingDeviceKnn:
    """Deferred half of ``batched_knn_device_async``: the fused first
    round is already ENQUEUED on the device (with its result transfers
    started async); ``finish()`` takes the single stage-boundary fence —
    the (G,) active-mask read — runs the compacted straggler loop for
    queries the fused round left active, and materializes rows + stats.
    ``finish()`` is idempotent."""

    __slots__ = ("_fn", "_out")

    def __init__(self, fn):
        self._fn = fn
        self._out = None

    def finish(self) -> Tuple[np.ndarray, np.ndarray]:
        if self._out is None:
            self._out = self._fn()
        return self._out


def batched_knn_device_async(geom: LeafGeometry, data_tiles, qs, k: int,
                             *, masks: Optional[jax.Array] = None,
                             beam: int = 8, interpret: bool = True,
                             planes=None, precision: str = "fp32",
                             w1: Optional[int] = None,
                             ws: Optional[int] = None,
                             stats: Optional[EngineStats] = None,
                             conv_out: Optional[list] = None
                             ) -> _PendingDeviceKnn:
    """Dispatch half of ``batched_knn_device``: enqueues the fused
    first round (one device program) and returns WITHOUT any host sync
    — per-round state (heaps, bounds, active mask) stays device-
    resident until ``finish()``. The transfers ``finish()`` will read
    are started asynchronously here, so when another chunk's host work
    runs in between (the serving pipeline's overlap window), the
    eventual fence usually costs nothing. Results and stats are
    identical to the synchronous wrapper."""
    t0 = time.time()
    qs = jnp.asarray(qs, jnp.float32)
    masks_tiles = None
    if masks is not None:
        masks_tiles = _tile_masks(jnp.asarray(masks), geom.bucket_rows)
    g = int(qs.shape[0])
    l = geom.n_leaves
    w1 = max(1, min(w1 if w1 else max(1, beam // 2), l))
    order, lb_sorted, d2, rows, active, nvalid, resc = _knn_start(
        qs, masks_tiles, geom.centroid, geom.radius, data_tiles,
        geom.bucket_rows, planes, w1=w1, k=k, precision=precision,
        interpret=interpret)
    for a in (active, d2, rows, nvalid, resc):
        _start_d2h(a)
    t_disp = time.time() - t0

    def _finish() -> Tuple[np.ndarray, np.ndarray]:
        t1 = time.time()
        d2f, rowsf = d2, rows
        if stats is not None:
            stats.knn_rounds += 1
            stats.knn_buckets += g * w1
            stats.rows_scanned += int(nvalid)
            if precision != "fp32":
                stats.mp_scanned += int(nvalid)
                stats.mp_rescued += int(resc)
        conv = np.full(g, w1, np.int64)
        act = np.nonzero(np.asarray(active))[0]
        if len(act) and w1 < l:
            na = len(act)
            gp = _next_pow2(na)
            padded = np.zeros(gp, np.int64)
            padded[:na] = act
            idx = jnp.asarray(padded, jnp.int32)
            active0 = jnp.asarray(np.arange(gp) < na)
            w = _round_tiles(max(1, ws if ws else beam), gp,
                             geom.cap, qs.shape[1])
            budget = -(-(l - w1) // w)
            bd, br, loop_stats, retire_round = _knn_device_loop(
                idx, active0, qs, d2, rows, order, lb_sorted,
                masks_tiles, data_tiles, geom.bucket_rows, planes,
                w1=w1, w=w, budget=budget, k=k, precision=precision,
                interpret=interpret)
            d2f = np.asarray(d2, dtype=np.float32).copy()
            rowsf = np.asarray(rows).copy()
            d2f[act] = np.asarray(bd)[:na]
            rowsf[act] = np.asarray(br)[:na]
            conv[act] = np.minimum(
                w1 + np.asarray(retire_round)[:na].astype(np.int64) * w,
                l)
            if stats is not None:
                rounds, nbuck, nrows, nresc = np.asarray(loop_stats)
                stats.knn_rounds += int(rounds)
                stats.knn_buckets += int(nbuck)
                stats.rows_scanned += int(nrows)
                if precision != "fp32":
                    stats.mp_scanned += int(nrows)
                    stats.mp_rescued += int(nresc)
        if stats is not None:
            stats.time_s += t_disp + (time.time() - t1)
        if conv_out is not None:
            conv_out.append(conv)
        return np.sqrt(np.asarray(d2f)), np.asarray(rowsf).astype(np.int64)

    return _PendingDeviceKnn(_finish)


def batched_knn_device(geom: LeafGeometry, data_tiles, qs, k: int, *,
                       masks: Optional[jax.Array] = None, beam: int = 8,
                       interpret: bool = True, planes=None,
                       precision: str = "fp32",
                       w1: Optional[int] = None, ws: Optional[int] = None,
                       stats: Optional[EngineStats] = None,
                       conv_out: Optional[list] = None
                       ) -> Tuple[np.ndarray, np.ndarray]:
    """Exact batched (optionally row-masked) KNN with the beam loop on
    device: same contract (and identical rows) as ``batched_knn``, which
    stays as the host exactness oracle.

    Structure: ONE fused first round scans every query's top beam/2
    lower-bound tiles — on clustered data this finishes the large
    majority of the batch. A single (G,) active-mask transfer then
    compacts the stragglers (padded to a power of two, so compiled
    shapes stay bounded), and their remaining rounds run entirely
    inside ``_knn_device_loop`` (a ``lax.while_loop`` carrying the
    per-query top-k heap and active mask as loop state). Round widths
    (overridable via ``w1``/``ws``, in tiles of the layout passed in)
    default to beam/2 for the first round and beam for straggler
    rounds — and the engine hands this path its FINER device tile
    layout, so a device round scans roughly half the rows of a host
    round: a device round costs no host round-trip, so the host loop's
    over-scanning (wide tiles, wide doubling beams, both needed to
    amortize its per-round sync) buys nothing here. The fixed round
    budget ceil(remaining / W) makes the loop exact even when the
    stopping rule never fires (k > matching rows), while the per-round
    bound check retires queries exactly like the scalar executor.
    ``conv_out`` (see ``batched_knn``) receives per-query converged
    widths: w1 for queries the fused round finished, w1 + r*W for a
    straggler retired in loop round r (capped at the tile count).
    Versus the host loop's 2-4 full transfers + host merges per batch,
    this path transfers one bool per query mid-batch and never computes
    a straggler round at full batch width.

    Implementation: the synchronous wrapper over the dispatch half
    (``batched_knn_device_async``) and its deferred ``finish()`` —
    dispatch and fence back-to-back is exactly the pre-split loop."""
    return batched_knn_device_async(
        geom, data_tiles, qs, k, masks=masks, beam=beam,
        interpret=interpret, planes=planes, precision=precision,
        w1=w1, ws=ws, stats=stats, conv_out=conv_out).finish()


class _ReadyKnn:
    """Already-materialized stand-in for ``_PendingDeviceKnn`` — used by
    job paths with no async implementation (host loop, sharded), which
    execute eagerly at dispatch time and defer nothing."""

    __slots__ = ("_rows",)

    def __init__(self, rows):
        self._rows = rows

    def finish(self):
        return None, self._rows


class _PendingJobs:
    """Deferred half of ``HybridEngine._dispatch_jobs``: per-group
    finishers that fence, materialize rows, and record width/cost stats
    in dispatch order. ``finish()`` is idempotent and returns the
    per-job row arrays ``_run_jobs`` would have returned."""

    __slots__ = ("_finishers", "_out", "_done")

    def __init__(self, n_jobs: int):
        self._finishers: list = []
        self._out: List[Optional[np.ndarray]] = [None] * n_jobs
        self._done = False

    def add(self, fn) -> None:
        self._finishers.append(fn)

    def run_now(self, fn) -> None:
        """Eager mode: run one group's finisher inline at dispatch."""
        fn(self._out)

    def finish(self) -> List[np.ndarray]:
        if not self._done:
            for fn in self._finishers:
                fn(self._out)
            self._done = True
        return self._out  # type: ignore[return-value]


class PendingBatch:
    """Deferred epilogue of ``HybridEngine.execute_batch_async`` /
    ``ExecutablePlan``'s engine fragment: device work is enqueued,
    ``materialize()`` fences at the stage boundary and yields exactly
    the (rows, stats) the synchronous call would have returned.
    Idempotent — the serving pipeline may retire a chunk through any
    code path without double-running its epilogue."""

    __slots__ = ("_fn", "_res")

    def __init__(self, fn):
        self._fn = fn
        self._res = None

    def materialize(self):
        if self._res is None:
            self._res = self._fn()
        return self._res


# ---------------------------------------------------------------------------
# Sharded multi-device execution (tile-major layout sharded along T)
# ---------------------------------------------------------------------------
# The tile axis is the natural shard axis: tiles are self-contained (ball
# metadata + row ids + data rows), so splitting T across a ("shards",)
# device mesh gives shared-nothing partitions whose only cross-talk is a
# per-round k-way merge of (G, k) heaps. Layout contract (see
# repro.sharding.partitioning): the padded tile axis is permuted STRIDED
# (tile t -> shard t mod S, each shard an even 1/S sample of the
# tree-ordered tile sequence), pad tiles carry -1 rows and -inf radii
# (lower bound +inf — invisible to every pruning rule). Delta tiles (async
# ingest) are NOT sharded: they are replicated to every device and gated
# by axis_index so only shard 0's copies are live (radius -inf elsewhere)
# — the delta is small, re-uploading it per write epoch is cheap, and
# keeping it whole preserves PR 4's freshness-exactness verbatim with no
# cross-shard row duplication.
#
# Merge semantics (exactness): each shard keeps a LOCAL top-k heap over
# only its own (disjoint) tiles; every round ends with an
# all-reduce-style merge — all_gather the S local heaps, one stable
# top_k over (G, S*k) — giving the replicated GLOBAL heap. A query
# retires when its global kth distance <= pmin over shards of the next
# unscanned LOCAL lower bound, which equals the next unscanned GLOBAL
# bound — the scalar executor's stopping rule. Results match the
# single-device loop row-for-row whenever the kth-boundary distance is
# unique (carry-first + shard-order keeps the merge deterministic);
# rows tied EXACTLY at the kth distance may resolve to a different
# equally-distant row than the single-device visit-order tie-break —
# the returned distance multiset is identical either way, so every
# shard count returns AN exact top-k. Keeping local heaps local is what
# makes the merge exact: merging the global heap back into shard carries
# would duplicate rows across shards and let copies crowd out true
# neighbors.
@dataclass
class ShardedTiles:
    """One attribute's tile-major state laid out over a ("shards",) mesh:
    base tiles sharded along T (strided placement), delta tiles
    replicated (live on shard 0 only). ``rows_np`` keeps the permuted
    host copy for mask staging and row decoding."""
    mesh: object
    shards: int
    t_local: int            # padded base tiles per shard
    cap: int
    centroid: jax.Array     # (S*t_local, d)   P("shards", None)
    radius: jax.Array       # (S*t_local,)     P("shards")
    bucket_rows: jax.Array  # (S*t_local, cap) P("shards", None)
    data_tiles: jax.Array   # (S*t_local, cap, d) P("shards", None, None)
    rows_np: np.ndarray     # host copy of the permuted padded rows
    perm: np.ndarray        # padded position -> original tile index
    tile_pp: Optional[jax.Array] = None   # (S*t_local, cap) row sq-norms
    # quantized tile planes (mixed-precision scan; None on fp32 engines).
    # Per-tile quantization commutes with the strided permutation, so the
    # planes are quantized once on the unpermuted tiles and permuted like
    # every other tile array.
    q_data: Optional[jax.Array] = None    # (S*t_local, cap, d) i8/bf16
    q_scale: Optional[jax.Array] = None   # (S*t_local,)
    q_ppq: Optional[jax.Array] = None     # (S*t_local, cap)
    q_eps: Optional[jax.Array] = None     # (S*t_local,)
    # replicated delta extension (zero-width when no delta)
    td: int = 0
    d_centroid: Optional[jax.Array] = None
    d_radius: Optional[jax.Array] = None
    d_bucket_rows: Optional[jax.Array] = None
    d_data_tiles: Optional[jax.Array] = None
    d_rows_np: Optional[np.ndarray] = None
    d_tile_pp: Optional[jax.Array] = None
    d_q_data: Optional[jax.Array] = None
    d_q_scale: Optional[jax.Array] = None
    d_q_ppq: Optional[jax.Array] = None
    d_q_eps: Optional[jax.Array] = None

    @property
    def t_total(self) -> int:
        """Per-shard tile count the compiled bodies see (base + delta)."""
        return self.t_local + self.td


def make_sharded_tiles(mesh, shards: int, centroid: np.ndarray,
                       radius: np.ndarray, rows_np: np.ndarray,
                       tiles_np: np.ndarray, *, with_pp: bool = False,
                       planes=None) -> ShardedTiles:
    """Pad + permute one layout's tile arrays (strided placement) and
    upload them pre-sharded — each device receives only its slice.
    ``planes`` (optional ``repro.utils.quant.TilePlanes``, host numpy):
    the layout's quantized scan operands, permuted alongside. Pad-tile
    plane values (codes 0, scale 1, ppq 0, eps 0) are benign — pad rows
    are already invalid via rows -1 / radius -inf."""
    from jax.sharding import PartitionSpec as P
    t, cap = rows_np.shape
    d = centroid.shape[1]
    perm, t_local, t_pad = strided_tile_layout(t, shards)
    src = np.minimum(perm, t - 1)
    pad = perm >= t
    cen = np.where(pad[:, None], 0.0, centroid[src]).astype(np.float32)
    rad = np.where(pad, -np.inf, radius[src]).astype(np.float32)
    rws = np.where(pad[:, None], -1, rows_np[src]).astype(np.int32)
    dts = np.where(pad[:, None, None], 0.0, tiles_np[src]
                   ).astype(np.float32)
    st = ShardedTiles(
        mesh=mesh, shards=shards, t_local=t_local, cap=cap,
        centroid=shard_put(cen, mesh, P("shards", None)),
        radius=shard_put(rad, mesh, P("shards")),
        bucket_rows=shard_put(rws, mesh, P("shards", None)),
        data_tiles=shard_put(dts, mesh, P("shards", None, None)),
        rows_np=rws, perm=perm)
    if with_pp:
        st.tile_pp = shard_put((dts ** 2).sum(-1), mesh, P("shards", None))
    if planes is not None:
        qd = np.array(planes.data[src])
        qd[pad] = 0
        qs_ = np.where(pad, 1.0, planes.scale[src]).astype(np.float32)
        qp = np.where(pad[:, None], 0.0, planes.ppq[src]
                      ).astype(np.float32)
        qe = np.where(pad, 0.0, planes.eps[src]).astype(np.float32)
        st.q_data = shard_put(qd, mesh, P("shards", None, None))
        st.q_scale = shard_put(qs_, mesh, P("shards"))
        st.q_ppq = shard_put(qp, mesh, P("shards", None))
        st.q_eps = shard_put(qe, mesh, P("shards"))
    st_clear_delta(st)
    return st


def st_clear_delta(st: ShardedTiles):
    """Zero-width replicated delta arrays (the no-delta state)."""
    from jax.sharding import PartitionSpec as P
    cap, d = st.cap, st.centroid.shape[1]
    rep = lambda x, spec: shard_put(x, st.mesh, spec)
    st.td = 0
    st.d_centroid = rep(np.zeros((0, d), np.float32), P(None, None))
    st.d_radius = rep(np.zeros((0,), np.float32), P(None))
    st.d_bucket_rows = rep(np.zeros((0, cap), np.int32), P(None, None))
    st.d_data_tiles = rep(np.zeros((0, cap, d), np.float32),
                          P(None, None, None))
    st.d_rows_np = np.zeros((0, cap), np.int32)
    if st.tile_pp is not None:
        st.d_tile_pp = rep(np.zeros((0, cap), np.float32), P(None, None))
    if st.q_data is not None:
        qdt = np.asarray(st.q_data).dtype
        st.d_q_data = rep(np.zeros((0, cap, d), qdt), P(None, None, None))
        st.d_q_scale = rep(np.zeros((0,), np.float32), P(None))
        st.d_q_ppq = rep(np.zeros((0, cap), np.float32), P(None, None))
        st.d_q_eps = rep(np.zeros((0,), np.float32), P(None))


def st_set_delta(st: ShardedTiles, rows_np: np.ndarray, tiles_np: np.ndarray,
                 centroid: np.ndarray, radius: np.ndarray, planes=None):
    """Refresh the replicated delta extension (one small upload per
    write epoch; shapes change only on pow2 capacity doublings, so the
    compiled bodies re-trace rarely). ``planes``: the delta tiles'
    quantized scan operands (own scales, quantized at sync time) when
    the owning engine runs a reduced-precision scan."""
    from jax.sharding import PartitionSpec as P
    rep = lambda x, spec: shard_put(np.asarray(x), st.mesh, spec)
    st.td = len(rows_np)
    st.d_centroid = rep(centroid.astype(np.float32), P(None, None))
    st.d_radius = rep(radius.astype(np.float32), P(None))
    st.d_bucket_rows = rep(rows_np.astype(np.int32), P(None, None))
    st.d_data_tiles = rep(tiles_np.astype(np.float32), P(None, None, None))
    st.d_rows_np = rows_np.astype(np.int32)
    if st.tile_pp is not None:
        st.d_tile_pp = rep((tiles_np.astype(np.float32) ** 2).sum(-1),
                           P(None, None))
    if planes is not None:
        st.d_q_data = rep(planes.data, P(None, None, None))
        st.d_q_scale = rep(planes.scale, P(None))
        st.d_q_ppq = rep(planes.ppq, P(None, None))
        st.d_q_eps = rep(planes.eps, P(None))


def _shard_heap_merge(lbd, lbr, k: int):
    """The all-reduce-style k-way merge: gather every shard's local
    heap (shard order = deterministic tie-break) and keep the global
    best k with one stable top_k. Local heaps cover disjoint rows, so
    the merged heap is the exact global top-k of everything scanned."""
    ad = jax.lax.all_gather(lbd, "shards", axis=1, tiled=True)
    ar = jax.lax.all_gather(lbr, "shards", axis=1, tiled=True)
    negd, pick = jax.lax.top_k(-ad, k)
    return -negd, jnp.take_along_axis(ar, pick, axis=1)


def _sharded_local_scan(qs, sel, colv, act, lbd, lbr, br_all, dt_all,
                        mt_all, k: int, interpret: bool, lb_col=None,
                        planes=None, precision: str = "fp32", kth0=None):
    """One shard's beam scan of its selected local tiles, merged into
    its LOCAL heap (stable: carry first, so earlier lower-bound tiles
    keep the visit-order tie-break). With ``precision`` != "fp32",
    ``planes`` holds the shard's assembled (base + delta) quantized
    arrays and ``kth0`` the previous round's GLOBAL kth squared
    distance; returns an extra scalar — this shard's fp32-rescued
    candidate count."""
    g = qs.shape[0]
    cap = br_all.shape[1]
    cand = br_all[sel].reshape(g, -1)
    valid = (cand >= 0) & jnp.repeat(colv, cap, axis=1)
    if act is not None:
        valid = valid & act[:, None]
    ma = jnp.take_along_axis(mt_all, sel[:, :, None], axis=1)
    valid = valid & ma.reshape(g, -1)
    lb2 = None
    if lb_col is not None:
        lb2 = jnp.repeat(lb_col * lb_col, cap, axis=1)
    if precision != "fp32":
        d2, idx, resc = ops.topk_l2_masked_mp(
            qs, sel, valid, dt_all, *planes, k, lb2=lb2, kth0=kth0,
            precision=precision, interpret=interpret)
        nresc = jnp.sum(resc)
    else:
        pts = jnp.take(dt_all, sel, axis=0).reshape(g, -1,
                                                    dt_all.shape[-1])
        d2, idx = ops.topk_l2_masked(qs, pts, valid, k,
                                     interpret=interpret, lb2=lb2)
        nresc = jnp.int32(0)
    rows = jnp.take_along_axis(cand, jnp.maximum(idx, 0), axis=1)
    rows = jnp.where(idx >= 0, rows, -1)
    alld = jnp.concatenate([lbd, d2], axis=1)
    allr = jnp.concatenate([lbr, rows], axis=1)
    negd, pick = jax.lax.top_k(-alld, k)
    return -negd, jnp.take_along_axis(allr, pick, axis=1), \
        jnp.sum(valid), nresc


@functools.lru_cache(maxsize=None)
def _sharded_knn_fns(mesh, t_local: int, td: int, cap: int, w1: int,
                     w: int, budget: int, k: int, interpret: bool,
                     precision: str = "fp32"):
    """Build (start_fn, loop_fn) — the two compiled shard_map dispatches
    of the sharded beam loop, memoized per (mesh, layout, widths).

    start_fn: per-shard mask relayout tail + prologue (local packed
    bound sort) + first round of ``w1`` LOCAL tiles (global coverage
    S*w1) + the cross-shard heap merge + the stopping rule. loop_fn:
    the compacted straggler loop — per round each shard scans its next
    ``w`` local tiles into its local heap, then the round's all-gather
    merge recomputes the global heap and retires queries whose kth
    distance <= pmin over shards of the next local bound."""
    from jax.sharding import PartitionSpec as P
    t_tot = t_local + td
    prologue = _knn_prologue_fast if t_tot <= 4096 else _knn_prologue
    mp = precision != "fp32"
    # extra operands when the scan is mixed-precision: the base planes
    # (sharded along T like every other tile array) then the replicated
    # delta planes, in plan_tiles component order (data, scale, ppq, eps)
    qp_in_specs = (
        (P("shards", None, None), P("shards"), P("shards", None),
         P("shards"), P(None, None, None), P(None), P(None, None),
         P(None)) if mp else ())

    def _assemble(n_masked, mtm, dmtm, g, cen_l, rad_l, br_l, dt_l,
                  dcen, drad, dbr, ddt, qp):
        """Per-shard (local base + gated replicated delta) tile arrays
        and the full (g, t_tot, cap) mask stack."""
        sidx = jax.lax.axis_index("shards")
        drad_g = jnp.where(sidx == 0, drad,
                           jnp.full_like(drad, -jnp.inf))
        cen = jnp.concatenate([cen_l, dcen])
        rad = jnp.concatenate([rad_l, drad_g])
        br = jnp.concatenate([br_l, dbr])
        dt = jnp.concatenate([dt_l, ddt])
        mt_m = jnp.concatenate([mtm, dmtm], axis=1)
        tail = jnp.broadcast_to((br >= 0)[None],
                                (g - n_masked, br.shape[0], cap))
        mt = jnp.concatenate([mt_m, tail], axis=0)
        # quantized planes: non-shard-0 delta copies need no gating here
        # — their tiles' radius gate already makes every bound +inf, so
        # no candidate of theirs is ever valid, rescued, or merged
        planes = (tuple(jnp.concatenate([a, b])
                        for a, b in zip(qp[:4], qp[4:]))
                  if qp else None)
        return cen, rad, br, dt, mt, planes

    def start(qs, mtm, dmtm, cen_l, rad_l, br_l, dt_l,
              dcen, drad, dbr, ddt, *qp):
        g = qs.shape[0]
        n_masked = mtm.shape[0]
        cen, rad, br, dt, mt, planes = _assemble(
            n_masked, mtm, dmtm, g, cen_l, rad_l, br_l, dt_l,
            dcen, drad, dbr, ddt, qp)
        order_l, lb_l = prologue(qs, cen, rad, mt)
        l = lb_l.shape[1]
        bd0 = jnp.full((g, k), jnp.inf, jnp.float32)
        br0 = jnp.full((g, k), -1, jnp.int32)
        colv = ~jnp.isinf(lb_l[:, :w1])
        lbd, lbr, nvalid, nresc = _sharded_local_scan(
            qs, order_l[:, :w1], colv, None, bd0, br0, br, dt, mt, k,
            interpret, lb_col=lb_l[:, :w1] if mp else None,
            planes=planes, precision=precision)
        gbd, gbr = _shard_heap_merge(lbd, lbr, k)
        kth = jnp.sqrt(gbd[:, -1])
        nxt = lb_l[:, w1] if w1 < l else jnp.full(g, jnp.inf, jnp.float32)
        nxt = jax.lax.pmin(nxt, "shards")
        return (order_l, lb_l, mt, lbd, lbr, gbd, gbr, kth > nxt,
                jax.lax.psum(nvalid, "shards"),
                jax.lax.psum(nresc, "shards"))

    start_fn = jax.jit(manual_shard_map(
        start, mesh,
        in_specs=(P(None, None), P(None, "shards", None), P(None, None,
                                                            None),
                  P("shards", None), P("shards"), P("shards", None),
                  P("shards", None, None), P(None, None), P(None),
                  P(None, None), P(None, None, None)) + qp_in_specs,
        out_specs=(P(None, "shards"), P(None, "shards"),
                   P(None, "shards", None), P(None, "shards"),
                   P(None, "shards"), P(None, None), P(None, None),
                   P(None), P(), P()),
        manual_axes=("shards",)))

    def loop(idx, active0, qs_f, lbd_f, lbr_f, order_f, lb_f, mt_f,
             br_l, dt_l, dbr, ddt, *qp):
        qs = jnp.take(qs_f, idx, axis=0)
        lbd = jnp.take(lbd_f, idx, axis=0)
        lbr = jnp.take(lbr_f, idx, axis=0)
        mt = jnp.take(mt_f, idx, axis=0)
        g = qs.shape[0]
        br = jnp.concatenate([br_l, dbr])
        dt = jnp.concatenate([dt_l, ddt])
        planes = (tuple(jnp.concatenate([a, b])
                        for a, b in zip(qp[:4], qp[4:]))
                  if qp else None)
        l = order_f.shape[1]
        order_pad = jnp.pad(jnp.take(order_f, idx, axis=0)[:, w1:],
                            ((0, 0), (0, budget * w - (l - w1))))
        lb_pad = jnp.pad(jnp.take(lb_f, idx, axis=0)[:, w1:],
                         ((0, 0), (0, budget * w + 1 - (l - w1))),
                         constant_values=jnp.inf)
        gbd0, gbr0 = _shard_heap_merge(lbd, lbr, k)

        def cond(st):
            return (st[0] < budget) & jnp.any(st[1])

        def body(st):
            r, act, gbd, _, lbd, lbr, nbuck, nrows, nresc_a, rr = st
            start_ = r * w
            sel = jax.lax.dynamic_slice_in_dim(order_pad, start_, w,
                                               axis=1)
            lb_col = jax.lax.dynamic_slice_in_dim(lb_pad, start_, w,
                                                  axis=1)
            colv = ~jnp.isinf(lb_col)
            lbd2, lbr2, nv, nresc = _sharded_local_scan(
                qs, sel, colv, act, lbd, lbr, br, dt, mt, k, interpret,
                lb_col=lb_col, planes=planes, precision=precision,
                kth0=gbd[:, -1] if mp else None)
            gbd2, gbr2 = _shard_heap_merge(lbd2, lbr2, k)
            kth = jnp.sqrt(gbd2[:, -1])
            nxt = jax.lax.pmin(jax.lax.dynamic_slice_in_dim(
                lb_pad, start_ + w, 1, axis=1)[:, 0], "shards")
            act2 = act & ~(kth <= nxt)
            rr = jnp.where(act & ~act2, r + 1, rr)
            nbuck = nbuck + jax.lax.psum(
                jnp.sum(jnp.where(act[:, None], colv, False)), "shards")
            nrows = nrows + jax.lax.psum(nv, "shards")
            nresc_a = nresc_a + jax.lax.psum(nresc, "shards")
            return (r + 1, act2, gbd2, gbr2, lbd2, lbr2, nbuck, nrows,
                    nresc_a, rr)

        st0 = (jnp.int32(0), active0, gbd0, gbr0, lbd, lbr,
               jnp.int32(0), jnp.int32(0), jnp.int32(0),
               jnp.zeros(g, jnp.int32))
        r, act_f, gbd, gbr, _, _, nbuck, nrows, nresc, rr = \
            jax.lax.while_loop(cond, body, st0)
        rr = jnp.where(act_f, r, rr)
        return gbd, gbr, jnp.stack([r, nbuck, nrows, nresc]), rr

    loop_fn = jax.jit(manual_shard_map(
        loop, mesh,
        in_specs=(P(None), P(None), P(None, None), P(None, "shards"),
                  P(None, "shards"), P(None, "shards"), P(None, "shards"),
                  P(None, "shards", None), P("shards", None),
                  P("shards", None, None), P(None, None),
                  P(None, None, None)) + qp_in_specs,
        out_specs=(P(None, None), P(None, None), P(None), P(None)),
        manual_axes=("shards",)))
    return start_fn, loop_fn


def batched_knn_sharded(st: ShardedTiles, qs, k: int, *,
                        masks_np: Optional[np.ndarray] = None,
                        beam: int = 8, interpret: bool = True,
                        precision: str = "fp32",
                        w1: Optional[int] = None, ws: Optional[int] = None,
                        stats: Optional[EngineStats] = None,
                        conv_out: Optional[list] = None
                        ) -> Tuple[np.ndarray, np.ndarray]:
    """Exact batched (optionally row-masked) KNN over the T-sharded
    layout: same contract (and identical rows) as ``batched_knn_device``.

    Structure mirrors the single-device loop — one fused start dispatch
    (per-shard prologue + first round + merge), one (G,) active-mask
    transfer, one compacted straggler-loop dispatch — but every stage
    runs per shard over 1/S of the tiles, and each round's per-shard
    top-k heaps are merged with the all-gather k-way merge (module
    section docstring). Round widths ``w1``/``ws`` are PER-SHARD tile
    counts: defaults beam/2 and beam scaled down by the shard count, so
    global first-round coverage (S * w1) matches the single-device
    default and total per-round work stays flat while the latency is
    split S ways. ``masks_np`` holds masks for the masked PREFIX of the
    batch only (the unmasked tail's all-true tiles are built on device);
    staging relayouts masks host-side into tile-major slabs uploaded
    pre-sharded, so no (G, n) mask is ever broadcast to every device.
    ``conv_out`` (see ``batched_knn``): per-query converged widths in
    per-shard tiles of this layout."""
    t0 = time.time()
    s = st.shards
    qs_np = np.asarray(qs, np.float32)
    g = len(qs_np)
    qs_j = jnp.asarray(qs_np)
    l = st.t_total
    w1 = max(1, min(w1 if w1 else max(1, -(-max(1, beam // 2) // s)), l))
    w = _round_tiles(max(1, ws if ws else max(1, -(-beam // s))), g,
                     st.cap, qs_np.shape[1])
    budget = max(1, -(-(l - w1) // w)) if l > w1 else 1
    start_fn, loop_fn = _sharded_knn_fns(
        st.mesh, st.t_local, st.td, st.cap, w1, w, budget, k, interpret,
        precision)
    qp = () if precision == "fp32" else (
        st.q_data, st.q_scale, st.q_ppq, st.q_eps,
        st.d_q_data, st.d_q_scale, st.d_q_ppq, st.d_q_eps)
    # host-side tile-major mask staging, uploaded pre-sharded
    from jax.sharding import PartitionSpec as P
    n_masked = 0 if masks_np is None else len(masks_np)
    if n_masked:
        mtm_np = (masks_np[:, np.maximum(st.rows_np, 0)]
                  & (st.rows_np >= 0)[None])
        dmtm_np = (masks_np[:, np.maximum(st.d_rows_np, 0)]
                   & (st.d_rows_np >= 0)[None])
    else:
        mtm_np = np.zeros((0,) + st.rows_np.shape, bool)
        dmtm_np = np.zeros((0,) + st.d_rows_np.shape, bool)
    mtm = shard_put(mtm_np, st.mesh, P(None, "shards", None))
    dmtm = shard_put(dmtm_np, st.mesh, P(None, None, None))
    (order_f, lb_f, mt_f, lbd, lbr, gbd, gbr, active, nvalid,
     nresc) = start_fn(
        qs_j, mtm, dmtm, st.centroid, st.radius, st.bucket_rows,
        st.data_tiles, st.d_centroid, st.d_radius, st.d_bucket_rows,
        st.d_data_tiles, *qp)
    if stats is not None:
        stats.knn_rounds += 1
        stats.knn_buckets += g * w1 * s
        stats.rows_scanned += int(nvalid)
        if precision != "fp32":
            stats.mp_scanned += int(nvalid)
            stats.mp_rescued += int(nresc)
    conv = np.full(g, w1, np.int64)
    act = np.nonzero(np.asarray(active))[0]
    d2_out, rows_out = gbd, gbr
    if len(act) and w1 < l:
        na = len(act)
        gp = _next_pow2(na)
        padded = np.zeros(gp, np.int64)
        padded[:na] = act
        idx = jnp.asarray(padded, jnp.int32)
        active0 = jnp.asarray(np.arange(gp) < na)
        bd, br, loop_stats, retire_round = loop_fn(
            idx, active0, qs_j, lbd, lbr, order_f, lb_f, mt_f,
            st.bucket_rows, st.data_tiles, st.d_bucket_rows,
            st.d_data_tiles, *qp)
        d2_np = np.asarray(d2_out, dtype=np.float32).copy()
        rows_np_out = np.asarray(rows_out).copy()
        d2_np[act] = np.asarray(bd)[:na]
        rows_np_out[act] = np.asarray(br)[:na]
        d2_out, rows_out = d2_np, rows_np_out
        conv[act] = np.minimum(
            w1 + np.asarray(retire_round)[:na].astype(np.int64) * w, l)
        if stats is not None:
            rounds, nbuck, nrows, nresc_l = np.asarray(loop_stats)
            stats.knn_rounds += int(rounds)
            stats.knn_buckets += int(nbuck)
            stats.rows_scanned += int(nrows)
            if precision != "fp32":
                stats.mp_scanned += int(nrows)
                stats.mp_rescued += int(nresc_l)
    if stats is not None:
        stats.time_s += time.time() - t0
    if conv_out is not None:
        conv_out.append(conv)
    return np.sqrt(np.asarray(d2_out)), \
        np.asarray(rows_out).astype(np.int64)


# ---------------------------------------------------------------------------
# Sharded V.R (tile planner + union evaluation per shard)
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _sharded_vr_fns(mesh, t_local: int, td: int, cap: int):
    """(plan_fn, eval_fn) for the sharded V.R route, memoized like the
    KNN dispatches. plan_fn evaluates the triangle bound per shard over
    local (+ shard-0-gated delta) tile balls; the (g, S*(t_local+td))
    survival matrix is assembled by the output spec — the cross-shard
    "count" epilogue is just host numpy over it. eval_fn runs the
    union GEMM per shard over each shard's own surviving tiles (padded
    to one uniform width); the packed int8 verdicts concatenate across
    shards for the host decode — the "concat" epilogue."""
    from jax.sharding import PartitionSpec as P

    def plan(qs, r, cen_l, rad_l, dcen, drad):
        sidx = jax.lax.axis_index("shards")
        drad_g = jnp.where(sidx == 0, drad,
                           jnp.full_like(drad, -jnp.inf))
        cen = jnp.concatenate([cen_l, dcen])
        rad = jnp.concatenate([rad_l, drad_g])
        return _vr_leaf_plan(qs, r, cen, rad)

    plan_fn = jax.jit(manual_shard_map(
        plan, mesh,
        in_specs=(P(None, None), P(None), P("shards", None), P("shards"),
                  P(None, None), P(None)),
        out_specs=P(None, "shards"), manual_axes=("shards",)))

    def ueval(qs, r2, sel_u, member, br_l, dt_l, pp_l, dbr, ddt, dpp):
        br = jnp.concatenate([br_l, dbr])
        dt = jnp.concatenate([dt_l, ddt])
        pp = jnp.concatenate([pp_l, dpp])
        return _vr_union_eval(qs, r2, sel_u[0], member[0], dt, pp,
                              br)[None]

    eval_fn = jax.jit(manual_shard_map(
        ueval, mesh,
        in_specs=(P(None, None), P(None), P("shards", None),
                  P("shards", None, None), P("shards", None),
                  P("shards", None, None), P("shards", None),
                  P(None, None), P(None, None, None), P(None, None)),
        out_specs=P("shards", None, None), manual_axes=("shards",)))
    return plan_fn, eval_fn


# ---------------------------------------------------------------------------
# Grouped predicate masks (one compiled call per (type, attr) group)
# ---------------------------------------------------------------------------
@jax.jit
def _ne_group_masks(col, num_lo, num_hi, row_leaf, v, tol):
    leaf_ok = ((num_lo[None, :] <= (v + tol)[:, None])
               & (num_hi[None, :] >= (v - tol)[:, None]))
    m = jnp.abs(col[None, :] - v[:, None]) <= tol[:, None]
    return m & leaf_ok[:, row_leaf], jnp.sum(leaf_ok)


@jax.jit
def _nr_group_masks(col, num_lo, num_hi, row_leaf, lo, hi):
    leaf_ok = ((num_lo[None, :] <= hi[:, None])
               & (num_hi[None, :] >= lo[:, None]))
    m = (col[None, :] >= lo[:, None]) & (col[None, :] <= hi[:, None])
    return m & leaf_ok[:, row_leaf], jnp.sum(leaf_ok)


_VR_DENSE_CUTOFF = 0.5  # surviving-tile row fraction above which the
#                         gather costs more than one dense column pass


@jax.jit
def _vr_leaf_plan(qs, r, centroid, radius):
    """Tile-level V.R planner: (g, T) survival matrix from the triangle
    bound |q - C| - R <= r. Conservative slack: distances come from the
    quadratic-expansion kernel and can overestimate by fp epsilon —
    pruning must never drop a tile whose boundary row is exactly at
    distance r + R. The slack has a RELATIVE ``1e-4 * dc`` term on top
    of the absolute one: the tile route evaluates (and fp-rechecks)
    only rows of surviving tiles, so unlike the dense path a wrongly
    pruned tile cannot be rescued later — the expansion's error grows
    with coordinate magnitude (~eps * (|q|^2 + |C|^2) / dc) and the
    relative term dominates it whenever the bound is anywhere near
    tight."""
    d2c = ops.pairwise_sq_l2(qs, centroid)
    dc = jnp.sqrt(jnp.maximum(d2c, 0.0))
    slack = 1e-4 * (1.0 + r[:, None] + radius[None, :]) + 1e-4 * dc
    return dc - radius[None, :] <= r[:, None] + slack


@jax.jit
def _vr_union_eval(qs, r2, sel_u, member, data_tiles, tile_pp,
                   bucket_rows):
    """Exact radius test over the UNION of the group's surviving tiles.

    sel_u: (U,) union tile ids (padded to a power of two; pad columns
    carry no members); member: (g, U) per-query tile survival. The
    union layout turns the evaluation into ONE (g, d) x (d, U*cap) GEMM
    — compute-bound — instead of per-query gathers + batched matvecs,
    which are memory-bound. Returns one packed int8 (g, U*cap) — bit 0:
    within radius, bit 1: within fp noise of the boundary (host
    re-checks those exactly) — a single transfer; the candidate ->
    physical-row map is rebuilt host-side from ``sel_u``. ``tile_pp``
    holds precomputed per-row squared norms, so the gathered points are
    read once (the GEMM)."""
    pts = jnp.take(data_tiles, sel_u, axis=0)        # (U, cap, d)
    rows = jnp.take(bucket_rows, sel_u, axis=0)      # (U, cap)
    u, cap, dim = pts.shape
    pts = pts.reshape(u * cap, dim)
    rows = rows.reshape(u * cap)
    valid = (rows >= 0)[None, :] & jnp.repeat(member, cap, axis=1)
    qq = jnp.sum(qs * qs, axis=1)
    pp = jnp.take(tile_pp, sel_u, axis=0).reshape(u * cap)
    cross = jax.lax.dot_general(
        qs, pts, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)          # (g, U*cap)
    d2 = jnp.maximum(qq[:, None] + pp[None, :] - 2.0 * cross, 0.0)
    within = valid & (d2 <= r2[:, None])
    near = valid & (jnp.abs(d2 - r2[:, None]) <= 1e-3 * (r2[:, None] + 1.0))
    return within.astype(jnp.int8) | (near.astype(jnp.int8) << 1)


@jax.jit
def _vr_dense_masks(qs, r, leaf_ok, col, row_leaf):
    """Dense fallback (the pre-planner path): full-column distances,
    masked by the tile survival matrix through the row->tile map."""
    d2 = ops.pairwise_sq_l2(qs, col)
    r2 = (r * r)[:, None]
    m = d2 <= r2
    # rows whose kernel distance sits within fp noise of the boundary get
    # re-checked on the host with the exact sum((x-q)^2) formula
    near = jnp.abs(d2 - r2) <= 1e-3 * (r2 + 1.0)
    return m & leaf_ok[:, row_leaf], near


# ---------------------------------------------------------------------------
# Query planning
# ---------------------------------------------------------------------------
def _contains_vk(q: Q.Query) -> bool:
    return any(isinstance(b, Q.VK) for b in Q.basic_queries(q))


def plannable(q: Q.Query) -> bool:
    """True when every V.K candidate mask derives from predicate-only
    subtrees (see module docstring for the excluded corner)."""
    if isinstance(q, (Q.NE, Q.NR, Q.VR, Q.VK)):
        return True
    if isinstance(q, Q.And):
        return all(isinstance(p, Q.VK) or
                   (not _contains_vk(p) and plannable(p))
                   for p in q.parts)
    if isinstance(q, Q.Or):
        return all(plannable(p) for p in q.parts)
    return False


def knn_archetype(attr: str, kmax: int, masked: bool,
                  device_loop: bool, shards: int = 0) -> str:
    """QBS convergence key for one KNN job group. Widths are in tiles of
    the layout the loop actually scans, which differs between the device
    (finer ``device_tile``) and host layouts — hence the loop tag; the
    sharded loop's widths are PER-SHARD tile counts, so each shard
    topology keys separately (``:sN``). Execution appends a ``:delta``
    suffix while un-folded delta tiles are unioned in (see
    ``HybridEngine._run_jobs``) — delta scans converge wider, and
    folding them into one key would permanently inflate the archetype's
    p90 after ``fold()``."""
    tag = "dl" if device_loop else "hl"
    if shards:
        tag += f":s{shards}"
    return (f"VK:{attr}:k{kmax}:{'masked' if masked else 'plain'}"
            f":{tag}")


@dataclass(frozen=True)
class KnnGroupSpec:
    """One KNN job group: which jobs run together through the beam loop.
    Derived by the engine per batch, or handed in pre-built (and cached)
    by the planner via ``EnginePlan``."""
    attr: str
    jobs: Tuple[int, ...]   # job indices, masked jobs first
    kmax: int
    n_masked: int
    archetype: str          # ``knn_archetype`` key for QBS feedback


def group_job_specs(job_specs: Sequence[Tuple[str, int, bool]],
                    device_loop: bool, shards: int = 0
                    ) -> Tuple[KnnGroupSpec, ...]:
    """THE grouping policy, shared by the engine (per batch, from live
    jobs) and the planner (cached, from shape specs) so the two can
    never drift apart.

    Device path: ONE group per attribute — masked and unmasked jobs
    share a single compiled program (unmasked jobs get an all-true
    mask); straggler compaction retires finished queries, so mixing no
    longer drags unmasked queries through extra full-width rounds, and
    the per-call fixed cost is paid once. Oracle path: masked jobs are
    kept apart — filtered candidates push the kth bound up, so masked
    groups need deeper beams and mixing would drag unmasked queries
    through extra rounds. Within a group, masked jobs order first (the
    all-true rows of the unmasked tail are built on device instead of
    being staged and uploaded)."""
    by_grp: Dict[Tuple, List[int]] = defaultdict(list)
    for i, (attr, k, masked) in enumerate(job_specs):
        key = attr if device_loop else (attr, masked)
        by_grp[key].append(i)
    specs: List[KnnGroupSpec] = []
    for key, idxs in by_grp.items():
        attr = key if device_loop else key[0]
        idxs = sorted(idxs, key=lambda i: not job_specs[i][2])
        kmax = max(job_specs[i][1] for i in idxs)
        n_masked = sum(1 for i in idxs if job_specs[i][2])
        specs.append(KnnGroupSpec(
            attr=attr, jobs=tuple(idxs), kmax=kmax, n_masked=n_masked,
            archetype=knn_archetype(attr, kmax, n_masked > 0,
                                    device_loop, shards)))
    return tuple(specs)


@dataclass
class EnginePlan:
    """Pre-derived execution structure for one batch archetype, built by
    ``repro.core.planner`` and cached across batches with the same
    signature: the V.K job layout (walk registration order), the KNN
    grouping, and QBS-seeded first-round beam widths. ``execute_batch``
    validates the job layout against its own walk (shape mismatches fail
    loudly instead of mis-assigning rows) and skips re-deriving the rest."""
    device_loop: bool
    job_specs: Tuple[Tuple[str, int, bool], ...]  # (attr, k, masked)/job
    groups: Tuple[KnnGroupSpec, ...]
    seeds: Optional[Dict[str, int]] = None        # archetype -> width
    shards: int = 0   # the shard topology the grouping was keyed for;
    #                   must match the executing engine (0 = unsharded)
    precision: str = "fp32"   # scan precision the plan was keyed for;
    #                           must match the executing engine


class HybridEngine:
    """Batched executor over one prepared MQRLD table (see module doc).

    Delta union (async ingest): ``sync_delta`` splices a platform
    ``DeltaRegion`` into the device state — delta rows get their own
    tiles (both layouts) with exact per-tile balls/boxes, appended after
    the base tiles, so both beam loops, the V.R tile planner, and the
    grouped predicate masks see ONE tile universe and stay exact over
    base+delta with no per-path special casing. Empty delta slots carry
    ``-1`` row ids and ``-inf`` ball radii (lower bound +inf: never
    scanned, never survive the triangle bound). Union state is cached
    per write epoch; delta capacities are pow2 so shapes re-trace only
    on capacity doublings.
    """

    def __init__(self, tree, table, meta, *, interpret: bool = True,
                 beam: int = 16, tile: int = 128,
                 device_loop: bool = True,
                 device_tile: Optional[int] = None,
                 shards: Optional[int] = None, mesh=None,
                 precision: str = "fp32", quant_cache=None,
                 cost_model=None):
        from repro.utils import quant
        # calibrated cost model (repro.core.cost.CostModel, or None):
        # ADVISORY — when calibrated for both V.R kinds, ``_vr_masks``
        # picks dense-vs-tile by predicted cost instead of the static
        # ``_VR_DENSE_CUTOFF`` threshold; uncalibrated engines keep the
        # fixed-threshold behavior bit-for-bit. Either path is exact.
        # The owning platform refreshes this on every ``engine()``
        # call, so cached engines see later calibrations.
        self.cost_model = cost_model
        if precision not in quant.PRECISIONS:
            raise ValueError(f"precision must be one of {quant.PRECISIONS},"
                             f" got {precision!r}")
        # mixed-precision tile scan (see module doc): both KNN beam-loop
        # layouts get reduced-precision planes built at prepare time;
        # the V.R predicate path stays fp32 (its triangle bound already
        # prunes on ball metadata — quantizing its union GEMM would buy
        # little and double the plane memory). ``quant_cache`` optionally
        # supplies persisted planes (repro.core.persist) so load skips
        # re-quantization.
        self.precision = precision
        self.vec_planes: Dict[str, Any] = {}
        self.vec_planes_dev: Dict[str, Any] = {}
        self._planes_np: Dict[Tuple[str, str], Any] = {}
        self._quant_cache = quant_cache
        self.device_loop = device_loop
        self.device_tile = device_tile or max(32, tile // 2)
        # sharded execution: shards=None keeps the single-device paths
        # (the exactness oracle); shards >= 1 lays the tile-major state
        # out over a ("shards",) mesh (shards=1 exercises the sharded
        # program on a one-device mesh). The mesh needs that many
        # backend devices — tile_mesh raises with the XLA_FLAGS hint.
        self.shards = shards
        self.mesh = None
        if shards is not None:
            self.mesh = mesh if mesh is not None else tile_mesh(shards)
        leaves = tree.leaf_ids
        starts = np.asarray(tree.bucket_start[leaves])
        ends = np.asarray(tree.bucket_end[leaves])
        rows_np, cap, leaf_of_tile = bucket_tiles(starts, ends, tile)
        self.bucket_rows = jnp.asarray(rows_np)
        self.bucket_rows_np = rows_np
        self.cap = cap
        self.tile = tile
        self.n = table.n_rows
        self.n_leaves = len(leaves)
        self.n_tiles = len(leaf_of_tile)
        self.interpret = interpret
        self.beam = beam
        # all metadata lives at TILE granularity (a tile inherits its
        # leaf's ball/box bounds); row_tile maps rows back for pruning
        row_tile = np.zeros(max(1, self.n), np.int32)
        for t in range(len(rows_np)):
            valid = rows_np[t][rows_np[t] >= 0]
            row_tile[valid] = t
        self.row_leaf = jnp.asarray(row_tile[:self.n])
        self.vec = {a: jnp.asarray(c, jnp.float32)
                    for a, c in table.vector.items()}
        self.vec_np = {a: np.asarray(c, np.float32)
                       for a, c in table.vector.items()}
        self.vec_tiles, self.vec_tile_pp = {}, {}
        for a, c in table.vector.items():
            tiles = tile_data(c, rows_np)
            self.vec_tiles[a] = jnp.asarray(tiles)
            self.vec_tile_pp[a] = jnp.asarray((tiles ** 2).sum(-1))
            if precision != "fp32":
                self.vec_planes[a] = self._make_planes(
                    "host", a, tiles, rows_np >= 0)
        self.num = {a: jnp.asarray(c, jnp.float32)
                    for a, c in table.numeric.items()}
        # per-TILE balls/boxes, not the leaf's: chunks of one big leaf
        # would otherwise share the leaf ball, giving duplicate loose
        # lower bounds that keep the KNN stopping rule from firing and
        # the V.R triangle bound from pruning. Computed once (numpy) at
        # build; LeafMeta stays the scalar path's leaf-level truth.
        valid = rows_np >= 0
        self.geom = {a: _tile_geometry(c, rows_np, self.bucket_rows, cap)
                     for a, c in table.vector.items()}
        # finer KNN-only layout for the device beam loop: narrow device
        # rounds want narrow tiles (tighter balls, finer stopping
        # granularity); the host loop's wide synced rounds keep the
        # coarse layout. Both are exact — tiling never affects results.
        rows_dev, cap_dev, _ = bucket_tiles(starts, ends,
                                            self.device_tile)
        self.cap_dev = cap_dev
        self.bucket_rows_dev_np = rows_dev
        br_dev = jnp.asarray(rows_dev)
        self.bucket_rows_dev = br_dev
        self.vec_tiles_dev = {}
        for a, c in table.vector.items():
            tiles_d = tile_data(c, rows_dev)
            self.vec_tiles_dev[a] = jnp.asarray(tiles_d)
            if precision != "fp32":
                self.vec_planes_dev[a] = self._make_planes(
                    "dev", a, tiles_d, rows_dev >= 0)
        self.geom_dev = {a: _tile_geometry(c, rows_dev, br_dev, cap_dev)
                         for a, c in table.vector.items()}
        # T-sharded copies of both layouts: the finer device layout
        # drives the sharded KNN beam loop, the coarse layout (with
        # per-row squared norms) the sharded V.R union GEMM. Base tiles
        # are uploaded pre-sharded once; delta tiles ride replicated.
        # KNOWN COST: a sharded engine also keeps the unsharded layouts
        # above — the per-call device_loop=False oracle and the scalar
        # parity paths still read them, and sync_delta derives the
        # union from them; on a real accelerator deployment that is an
        # extra table copy on device 0 (drop the oracle paths in a
        # memory-tight deployment to reclaim it).
        self.sharded_dev: Dict[str, ShardedTiles] = {}
        self.sharded_vr: Dict[str, ShardedTiles] = {}
        if self.mesh is not None:
            for a, c in table.vector.items():
                gd = self.geom_dev[a]
                self.sharded_dev[a] = make_sharded_tiles(
                    self.mesh, self.shards, np.asarray(gd.centroid),
                    np.asarray(gd.radius), rows_dev,
                    np.asarray(self.vec_tiles_dev[a]),
                    planes=self._planes_np.get(("dev", a)))
                gc = self.geom[a]
                self.sharded_vr[a] = make_sharded_tiles(
                    self.mesh, self.shards, np.asarray(gc.centroid),
                    np.asarray(gc.radius), rows_np,
                    np.asarray(self.vec_tiles[a]), with_pp=True)
        self.num_lo, self.num_hi = {}, {}
        for a, c in table.numeric.items():
            cv = np.asarray(c, np.float32)[np.maximum(rows_np, 0)]
            self.num_lo[a] = jnp.asarray(
                np.where(valid, cv, np.inf).min(axis=1), jnp.float32)
            self.num_hi[a] = jnp.asarray(
                np.where(valid, cv, -np.inf).max(axis=1), jnp.float32)
        # base-state snapshot: sync_delta swaps the attributes above
        # between "base only" and "base (+) delta-union" views
        self._base = {k: getattr(self, k) for k in (
            "n", "n_tiles", "bucket_rows", "bucket_rows_np", "row_leaf",
            "vec", "vec_np", "vec_tiles", "vec_tile_pp", "num",
            "num_lo", "num_hi", "geom", "geom_dev", "vec_tiles_dev",
            "vec_planes", "vec_planes_dev")}
        self.n_base = self.n
        self.delta_epoch = 0
        self.delta_rows = 0
        self.delta_tiles = 0

    # ------------------------------------------------- mixed precision
    def _make_planes(self, layout: str, attr: str, tiles_np: np.ndarray,
                     valid: np.ndarray):
        """Quantize one tile layout (or consume a persisted snapshot with
        matching precision and shape) and upload. Keeps the host-numpy
        planes around for the sharded upload and ``snapshot_planes``."""
        from repro.utils import quant
        cache = self._quant_cache
        planes = None
        if cache and cache.get("precision") == self.precision:
            keys = [f"{layout}__{attr}__{c}" for c in quant.TilePlanes._fields]
            if all(k in cache for k in keys):
                cand = quant.TilePlanes(*(cache[k] for k in keys))
                if np.asarray(cand.data).shape == tiles_np.shape:
                    planes = cand
        if planes is None:
            planes = quant.plan_tiles(tiles_np, valid, self.precision)
        planes = quant.TilePlanes(*(np.asarray(x) for x in planes))
        self._planes_np[(layout, attr)] = planes
        return quant.TilePlanes(*(jnp.asarray(x) for x in planes))

    def snapshot_planes(self) -> Dict[str, np.ndarray]:
        """BASE-layout quantized planes as flat numpy arrays for
        ``repro.core.persist`` (keys ``{layout}__{attr}__{component}``);
        feeding the dict back as ``quant_cache`` (plus a ``precision``
        entry) lets a loaded platform skip re-quantization."""
        out: Dict[str, np.ndarray] = {}
        for (layout, attr), planes in self._planes_np.items():
            for comp, arr in zip(planes._fields, planes):
                out[f"{layout}__{attr}__{comp}"] = np.asarray(arr)
        return out

    # --------------------------------------------------------- delta union
    def _delta_group_count(self, delta) -> int:
        """One grouping center per device-tile-worth of capacity —
        deterministic in the capacity, so tile budgets (and compiled
        shapes) never depend on the data distribution."""
        return max(1, delta.capacity // self.cap_dev)

    def _delta_groups(self, delta) -> List[np.ndarray]:
        """Layout heuristic: cluster live delta rows (k-means-lite over
        the primary vector attribute, k = ``_delta_group_count``) and
        sort each group by distance to its center. Delta tiles are then
        cut WITHIN groups ("annulus" chunks, the base layout's recipe),
        so their balls are as tight as base tiles' and prune honestly —
        arrival-order tiles are grab-bags whose lb ~ 0 everywhere,
        which displaces true nearest tiles from the first beam round
        and multiplies straggler rounds. Ids stay stable (a tile slot
        holds any global id); only tile membership changes, so
        exactness never depends on this grouping."""
        m = delta.m
        k = self._delta_group_count(delta)
        a = next(iter(delta.vector_dims), None)
        if a is None or m <= 1 or k <= 1:
            return [np.arange(m, dtype=np.int64)]
        pts_np = delta.vector[a][:m]
        pts = jnp.asarray(pts_np, jnp.float32)
        cen = pts_np[np.linspace(0, m - 1, k).astype(int)].copy()
        for _ in range(4):
            d2 = np.asarray(ops.pairwise_sq_l2(pts, jnp.asarray(cen)))
            asg = d2.argmin(axis=1)
            sums = np.zeros_like(cen)
            np.add.at(sums, asg, pts_np)
            cnt = np.bincount(asg, minlength=k)
            nz = cnt > 0
            cen[nz] = sums[nz] / cnt[nz][:, None]
        dist = d2[np.arange(m), asg]
        groups = []
        for j in range(k):
            sel = np.nonzero(asg == j)[0]
            if len(sel):
                groups.append(sel[np.argsort(dist[sel], kind="stable")]
                              .astype(np.int64))
        return groups

    def _delta_layout(self, delta, cap: int, groups: List[np.ndarray]):
        """Delta tiling at ``cap`` rows/tile: (global row ids (Td, cap),
        clipped local index, validity, per-row tile map). Chunks are
        aligned to group boundaries; the tile budget carries one slack
        tile per group (sum ceil(|g|/cap) <= ceil(capacity/cap) +
        n_groups), so Td is fixed by the capacity alone and compiled
        shapes never depend on the data."""
        td = delta.n_tiles(cap) + self._delta_group_count(delta)
        slots = np.full((td, cap), -1, np.int64)
        row_tile = np.zeros(delta.capacity, np.int64)
        t = 0
        for g in groups:
            for c0 in range(0, len(g), cap):
                chunk = g[c0:c0 + cap]
                slots[t, :len(chunk)] = chunk
                row_tile[chunk] = t
                t += 1
        assert t <= td, (t, td)
        valid = slots >= 0
        rows = np.where(valid, self.n_base + slots, -1).astype(np.int32)
        # pad rows keep tile 0: their NaN columns fail every predicate,
        # so the gate value is irrelevant
        return rows, np.maximum(slots, 0), valid, row_tile

    @staticmethod
    def _delta_geom(pts: np.ndarray, valid: np.ndarray):
        """Exact per-tile balls over the live slots; empty tiles get
        radius -inf (lower bound +inf: sorted last, pruned by V.R)."""
        cnt = valid.sum(1)
        cen = pts.sum(1) / np.maximum(cnt, 1)[:, None]
        d2 = ((pts - cen[:, None, :]) ** 2).sum(2)
        rad = np.where(cnt > 0,
                       np.sqrt(np.max(np.where(valid, d2, 0.0), axis=1)),
                       -np.inf)
        return (np.where(cnt[:, None] > 0, cen, 0.0).astype(np.float32),
                rad.astype(np.float32))

    def sync_delta(self, delta, epoch: int):
        """Bring the device state up to the platform's write epoch:
        no-op when unchanged, base-only when the delta is empty, else
        rebuild the base(+)delta union arrays (one host->device upload
        of the delta tiles plus concatenations; queries between appends
        reuse the cached union)."""
        if epoch == self.delta_epoch:
            return
        self.delta_epoch = epoch
        live = 0 if delta is None else delta.m
        if live == 0:
            for k, v in self._base.items():
                setattr(self, k, v)
            self.delta_rows = 0
            self.delta_tiles = 0
            for st in self.sharded_dev.values():
                st_clear_delta(st)
            for st in self.sharded_vr.values():
                st_clear_delta(st)
            return
        base = self._base
        nb = self.n_base
        self.n = nb + delta.capacity      # pad rows included: NaN columns
        #                                   fail every predicate, -1 tile
        #                                   slots never reach a kernel
        self.delta_rows = live
        groups = self._delta_groups(delta)
        rows_h, local_h, valid_h, row_tile_h = self._delta_layout(
            delta, self.cap, groups)
        self.delta_tiles = len(rows_h)
        self.n_tiles = base["n_tiles"] + len(rows_h)
        self.bucket_rows_np = np.concatenate(
            [np.asarray(base["bucket_rows_np"]), rows_h])
        self.bucket_rows = jnp.asarray(self.bucket_rows_np)
        self.row_leaf = jnp.concatenate(
            [base["row_leaf"],
             jnp.asarray(base["n_tiles"] + row_tile_h, jnp.int32)])
        rows_d, local_d, valid_d, _ = self._delta_layout(
            delta, self.cap_dev, groups)
        br_dev_u = jnp.concatenate(
            [self.bucket_rows_dev, jnp.asarray(rows_d)])
        vec, vec_np, vt, vpp, geom = {}, {}, {}, {}, {}
        vt_dev, geom_dev = {}, {}
        vpl, vpl_dev = {}, {}
        from repro.utils import quant
        for a in delta.vector_dims:
            dcol = delta.vector[a]                       # (capn, d), NaN pads
            full = np.concatenate([base["vec_np"][a], dcol])
            vec_np[a] = full
            vec[a] = jnp.asarray(full)
            # tile gathers clip to live data then zero pad slots: tiles
            # stay NaN-free (pads are excluded by -1 row ids anyway)
            pts_h = np.where(valid_h[:, :, None], dcol[local_h], 0.0
                             ).astype(np.float32)
            vt[a] = jnp.concatenate([base["vec_tiles"][a],
                                     jnp.asarray(pts_h)])
            vpp[a] = jnp.concatenate([base["vec_tile_pp"][a],
                                      jnp.asarray((pts_h ** 2).sum(-1))])
            cen, rad = self._delta_geom(pts_h, valid_h)
            g0 = base["geom"][a]
            geom[a] = LeafGeometry(
                centroid=jnp.concatenate([g0.centroid, jnp.asarray(cen)]),
                radius=jnp.concatenate([g0.radius, jnp.asarray(rad)]),
                bucket_rows=self.bucket_rows, cap=self.cap)
            pts_d = np.where(valid_d[:, :, None], dcol[local_d], 0.0
                             ).astype(np.float32)
            vt_dev[a] = jnp.concatenate([base["vec_tiles_dev"][a],
                                         jnp.asarray(pts_d)])
            cen_d, rad_d = self._delta_geom(pts_d, valid_d)
            # delta tiles get their OWN quantization scales (quantized
            # at sync, like base tiles at prepare) and the plane arrays
            # are concatenated tile-major exactly like the fp32 tiles —
            # the mixed-precision scan sees one uniform tile universe
            dpl_h = dpl_d = None
            if self.precision != "fp32":
                dpl_h = quant.plan_tiles(pts_h, valid_h, self.precision)
                vpl[a] = quant.TilePlanes(*(
                    jnp.concatenate([b, jnp.asarray(np.asarray(x))])
                    for b, x in zip(base["vec_planes"][a], dpl_h)))
                dpl_d = quant.plan_tiles(pts_d, valid_d, self.precision)
                vpl_dev[a] = quant.TilePlanes(*(
                    jnp.concatenate([b, jnp.asarray(np.asarray(x))])
                    for b, x in zip(base["vec_planes_dev"][a], dpl_d)))
            gd0 = base["geom_dev"][a]
            geom_dev[a] = LeafGeometry(
                centroid=jnp.concatenate([gd0.centroid,
                                          jnp.asarray(cen_d)]),
                radius=jnp.concatenate([gd0.radius, jnp.asarray(rad_d)]),
                bucket_rows=br_dev_u, cap=self.cap_dev)
            # sharded states: delta tiles ride REPLICATED (live on
            # shard 0 only) — one small upload per write epoch, base
            # shards untouched, freshness-exactness preserved verbatim
            if a in self.sharded_dev:
                st_set_delta(self.sharded_dev[a], rows_d, pts_d,
                             cen_d, rad_d, planes=dpl_d)
            if a in self.sharded_vr:
                st_set_delta(self.sharded_vr[a], rows_h, pts_h,
                             cen, rad)
        self.vec, self.vec_np = vec, vec_np
        self.vec_tiles, self.vec_tile_pp, self.geom = vt, vpp, geom
        self.vec_tiles_dev, self.geom_dev = vt_dev, geom_dev
        if self.precision != "fp32":
            self.vec_planes, self.vec_planes_dev = vpl, vpl_dev
        num, num_lo, num_hi = {}, {}, {}
        for a in delta.numeric_keys:
            dcol = delta.numeric[a]
            num[a] = jnp.concatenate([base["num"][a], jnp.asarray(dcol)])
            dval = dcol[local_h]
            num_lo[a] = jnp.concatenate([base["num_lo"][a], jnp.asarray(
                np.where(valid_h, dval, np.inf).min(axis=1), jnp.float32)])
            num_hi[a] = jnp.concatenate([base["num_hi"][a], jnp.asarray(
                np.where(valid_h, dval, -np.inf).max(axis=1),
                jnp.float32)])
        self.num, self.num_lo, self.num_hi = num, num_lo, num_hi

    # ------------------------------------------------------------ stage 1+2
    def _predicate_masks(self, queries: Sequence[Q.Query],
                         stats: EngineStats, tile_route: bool = True
                         ) -> Dict[Q.Query, np.ndarray]:
        """Exact (n,) row masks for every distinct basic predicate in the
        batch, computed group-wise: one leaf-pruning + one compare/kernel
        call per (type, attr) group. Masks come back to the host as one
        (g, n) transfer per group — the boolean combining in ``_walk`` is
        numpy (sub-microsecond per op vs ~100us device dispatch), and only
        the final V.K candidate masks return to the device.

        Dispatch order: every NE/NR group's compare kernel is ENQUEUED
        first (pure device work, transfer started async), then the V.R
        groups run (their plan/union epilogues take host syncs anyway,
        which now overlap the queued numeric compares), and the NE/NR
        masks materialize last — one explicit fence per group at the
        stage boundary instead of an eager sync per dispatch."""
        nodes: List[Q.Query] = []
        seen = set()
        for q in queries:
            for b in Q.basic_queries(q):
                if isinstance(b, Q.VK) or b in seen:
                    continue
                seen.add(b)
                nodes.append(b)
        groups: Dict[Tuple[str, str], List[Q.Query]] = defaultdict(list)
        for b in nodes:
            groups[(type(b).__name__, b.attr)].append(b)

        masks: Dict[Q.Query, np.ndarray] = {}
        deferred = []   # numeric groups: (grp, device mask, device count)
        for (tname, attr), grp in groups.items():
            if tname == "NE":
                m, touched = _ne_group_masks(
                    self.num[attr], self.num_lo[attr], self.num_hi[attr],
                    self.row_leaf,
                    jnp.asarray([b.value for b in grp], jnp.float32),
                    jnp.asarray([b.tol for b in grp], jnp.float32))
                _start_d2h(m)
                deferred.append((grp, m, touched))
            elif tname == "NR":
                m, touched = _nr_group_masks(
                    self.num[attr], self.num_lo[attr], self.num_hi[attr],
                    self.row_leaf,
                    jnp.asarray([b.lo for b in grp], jnp.float32),
                    jnp.asarray([b.hi for b in grp], jnp.float32))
                _start_d2h(m)
                deferred.append((grp, m, touched))
            else:  # VR
                m, touched = self._vr_masks(attr, grp, stats, tile_route)
                stats.predicate_buckets += int(touched)
                for i, b in enumerate(grp):
                    masks[b] = m[i]
        for grp, m, touched in deferred:   # stage-boundary fence
            m = np.asarray(m)
            stats.predicate_buckets += int(touched)
            for i, b in enumerate(grp):
                masks[b] = m[i]
        return masks

    def _vr_plan_sharded(self, attr: str, qs, r):
        """Sharded triangle-bound survival: per-shard plan over local
        (+ shard-0 delta) tile balls, host-mapped back to the GLOBAL
        (g, n_tiles) matrix (the count epilogue runs on it). Returns
        (global survival, per-shard local survival (g, S, tl+td))."""
        st = self.sharded_vr[attr]
        plan_fn, _ = _sharded_vr_fns(st.mesh, st.t_local, st.td, st.cap)
        surv = np.asarray(plan_fn(qs, jnp.asarray(r), st.centroid,
                                  st.radius, st.d_centroid, st.d_radius))
        g = surv.shape[0]
        tl, td = st.t_local, st.td
        cols = surv.reshape(g, st.shards, tl + td)
        t_base = self._base["n_tiles"]
        leaf_ok = np.zeros((g, self.n_tiles), bool)
        base_cols = cols[:, :, :tl].reshape(g, st.shards * tl)
        live = st.perm < t_base
        leaf_ok[:, st.perm[live]] = base_cols[:, live]
        if td:
            leaf_ok[:, t_base:] = cols[:, 0, tl:]
        return leaf_ok, cols, st

    def _vr_union_sharded(self, attr: str, st: ShardedTiles,
                          cols: np.ndarray, qs, r2: np.ndarray,
                          vecs: np.ndarray) -> np.ndarray:
        """Sharded union evaluation: each shard GEMMs the union of ITS
        OWN surviving tiles (padded to one uniform width so the SPMD
        shapes agree); the packed verdicts concat across shards and
        decode on the host exactly like the single-device route."""
        g = cols.shape[0]
        tl, td = st.t_local, st.td
        sel_lists = [np.nonzero(cols[:, s].any(axis=0))[0]
                     for s in range(st.shards)]
        u = max(1, _next_pow2(max(len(x) for x in sel_lists)))
        sel_u = np.zeros((st.shards, u), np.int32)
        member = np.zeros((st.shards, g, u), bool)
        for s, loc in enumerate(sel_lists):
            sel_u[s, :len(loc)] = loc
            member[s, :, :len(loc)] = cols[:, s, loc]
        _, eval_fn = _sharded_vr_fns(st.mesh, tl, td, st.cap)
        packed = np.asarray(eval_fn(
            qs, jnp.asarray(r2), jnp.asarray(sel_u), jnp.asarray(member),
            st.bucket_rows, st.data_tiles, st.tile_pp,
            st.d_bucket_rows, st.d_data_tiles, st.d_tile_pp))
        m = np.zeros((g, self.n), bool)
        col = self.vec_np[attr]
        for s in range(st.shards):
            local_rows = np.concatenate(
                [st.rows_np[s * tl:(s + 1) * tl], st.d_rows_np])
            rows = local_rows[sel_u[s]].reshape(-1)
            within = (packed[s] & 1).astype(bool)
            near = (packed[s] & 2).astype(bool)
            gis, cis = np.nonzero(within)
            m[gis, rows[cis]] = True
            gis, cis = np.nonzero(near)
            if len(gis):
                rws = rows[cis]
                exact = (((col[rws] - vecs[gis]) ** 2).sum(1) <= r2[gis])
                m[gis, rws] = exact
        return m

    def _vr_masks(self, attr: str, grp: List[Q.Query],
                  stats: EngineStats, tile_route: bool
                  ) -> Tuple[np.ndarray, int]:
        """(g, n) exact radius masks for one V.R group.

        tile_route=True (device path): the triangle bound keeps only
        plausible tiles, distances are evaluated on the gathered
        survivors, boundary rows re-checked exactly on the host; falls
        back to the dense column pass when the bound leaves most of the
        table standing. On a sharded engine both the bound and the
        union GEMM run per shard (``_vr_plan_sharded`` /
        ``_vr_union_sharded``); the dense fallback stays replicated —
        it is the unselective case where a full-column pass beats any
        gather, sharded or not. tile_route=False (oracle path): always
        the dense full-column pass, masked by the leaf-survival matrix
        — the original engine behavior.

        Dense-vs-tile DECISION (cost-model contract): when
        ``self.cost_model`` is reliably calibrated for BOTH "vr:dense"
        and "vr:tile" (see ``repro.core.cost``), the route is whichever
        predicts cheaper on this group's features; otherwise the
        static ``_VR_DENSE_CUTOFF`` row-fraction threshold decides —
        the uncalibrated fallback. Both routes return identical masks,
        so the decision only moves time. Whichever route runs, its
        (kind, features, seconds) lands in ``stats.stage_samples`` for
        QBS cost recording / online recalibration."""
        t_vr0 = time.time()
        vecs = np.stack([b.vec() for b in grp])
        r = np.asarray([b.radius for b in grp], np.float32)
        r2 = r.astype(np.float32) ** 2
        qs = jnp.asarray(vecs, jnp.float32)
        sharded = tile_route and self.mesh is not None \
            and attr in self.sharded_vr
        cols = st = None
        if sharded:
            leaf_ok, cols, st = self._vr_plan_sharded(attr, qs, r)
        else:
            leaf_ok = np.asarray(_vr_leaf_plan(
                qs, jnp.asarray(r), self.geom[attr].centroid,
                self.geom[attr].radius))
        touched = int(leaf_ok.sum())
        g = len(grp)
        stats.vr_tiles_pruned += g * self.n_tiles - touched
        union = np.nonzero(leaf_ok.any(axis=0))[0]
        dim = vecs.shape[1]
        feats_dense = costm.vr_features("vr:dense", g, len(union),
                                        self.cap, dim, self.n)
        feats_tile = costm.vr_features("vr:tile", g, len(union),
                                       self.cap, dim, self.n)
        use_dense = len(union) * self.cap > _VR_DENSE_CUTOFF \
            * max(1, self.n)
        cm = self.cost_model
        if tile_route and cm is not None \
                and cm.reliable("vr:dense", "vr:tile"):
            pd = cm.predict("vr:dense", feats_dense)
            pt = cm.predict("vr:tile", feats_tile)
            if pd is not None and pt is not None:
                use_dense = pd <= pt
        if not tile_route or use_dense:
            if tile_route:
                stats.vr_dense_fallbacks += 1
            m, near = _vr_dense_masks(qs, jnp.asarray(r),
                                      jnp.asarray(leaf_ok),
                                      self.vec[attr], self.row_leaf)
            m, near = np.asarray(m), np.asarray(near)
            gis, ris = np.nonzero(near)
            if len(gis):
                m = np.array(m)  # writable copy for boundary patching
                col = self.vec_np[attr]
                exact = (((col[ris] - vecs[gis]) ** 2).sum(1) <= r2[gis])
                m[gis, ris] = exact
            stats.stage_samples.append(
                ("vr:dense", feats_dense, time.time() - t_vr0))
            return m, touched
        stats.vr_tiles_scanned += touched
        if sharded:
            m = self._vr_union_sharded(attr, st, cols, qs, r2, vecs)
            stats.stage_samples.append(
                ("vr:tile", feats_tile, time.time() - t_vr0))
            return m, touched
        # pad the union to a power of two so compiled shapes stay
        # bounded across batches; pad columns have no members
        u = len(union)
        up = _next_pow2(u)
        sel_u = np.zeros(up, np.int32)
        sel_u[:u] = union
        member = np.zeros((g, up), bool)
        member[:, :u] = leaf_ok[:, union]
        packed = np.asarray(_vr_union_eval(
            qs, jnp.asarray(r2), jnp.asarray(sel_u), jnp.asarray(member),
            self.vec_tiles[attr], self.vec_tile_pp[attr],
            self.bucket_rows))
        within, near = (packed & 1).astype(bool), (packed & 2).astype(bool)
        rows = self.bucket_rows_np[sel_u].reshape(-1)     # host-side map
        m = np.zeros((g, self.n), bool)
        gis, cis = np.nonzero(within)
        m[gis, rows[cis]] = True
        gis, cis = np.nonzero(near)
        if len(gis):
            col = self.vec_np[attr]
            rws = rows[cis]
            exact = (((col[rws] - vecs[gis]) ** 2).sum(1) <= r2[gis])
            m[gis, rws] = exact
        stats.stage_samples.append(
            ("vr:tile", feats_tile, time.time() - t_vr0))
        return m, touched

    # --------------------------------------------------------------- stage 3
    def _walk(self, q, ambient, pred_masks, jobs, job_rows, ctr):
        """Mirror of the scalar ``MQRLD._exec`` over device masks. Planning
        pass (job_rows None): registers every V.K as (node, candidate mask)
        and returns None for VK-containing subtrees. Finishing pass:
        substitutes batched KNN results. Traversal order is identical in
        both passes, so ``ctr`` indexes the same job list."""
        if isinstance(q, (Q.NE, Q.NR, Q.VR)):
            m = pred_masks[q]
            return m if ambient is None else (m & ambient)
        if isinstance(q, Q.VK):
            i = ctr[0]
            ctr[0] += 1
            if job_rows is None:
                jobs.append((q, ambient))
                return None
            rows = np.asarray(job_rows[i])
            m = np.zeros(self.n, bool)
            m[rows[rows >= 0]] = True
            return m
        if isinstance(q, Q.And):
            mask = ambient
            vks = []
            for p in q.parts:
                if isinstance(p, Q.VK):
                    vks.append(p)
                    continue
                pm = self._walk(p, mask, pred_masks, jobs, job_rows, ctr)
                mask = pm if mask is None else (mask & pm)
            if not vks:
                return mask if mask is not None \
                    else np.ones(self.n, bool)
            res = None
            for p in vks:
                vm = self._walk(p, mask, pred_masks, jobs, job_rows, ctr)
                if vm is not None:
                    res = vm if res is None else (res & vm)
            return res
        if isinstance(q, Q.Or):
            out = np.zeros(self.n, bool)
            any_unknown = False
            for p in q.parts:
                pm = self._walk(p, ambient, pred_masks, jobs, job_rows, ctr)
                if pm is None:
                    any_unknown = True
                else:
                    out = out | pm
            return None if any_unknown else out
        raise TypeError(q)

    def _group_jobs(self, jobs, device_loop: bool) -> List[KnnGroupSpec]:
        """Derive the KNN grouping for one batch of live jobs (policy:
        ``group_job_specs``, shared with the planner's cached path)."""
        specs = tuple((vk.attr, vk.k, m is not None) for vk, m in jobs)
        shards = (self.shards or 0) if device_loop else 0
        return list(group_job_specs(specs, device_loop, shards))

    def _run_jobs(self, jobs, stats: EngineStats, device_loop: bool,
                  groups: Optional[Sequence[KnnGroupSpec]] = None,
                  seeds: Optional[Dict[str, int]] = None
                  ) -> List[np.ndarray]:
        """Run every V.K job as one beam-loop masked KNN per group
        through the fused kernel (grouping policy: ``_group_jobs``;
        ``groups`` hands in a planner-cached grouping instead).

        ``seeds`` maps group archetypes to QBS-recorded convergence
        widths (the p90 of per-query converged widths from past runs of
        the archetype). Application differs per loop, matching each
        loop's cost model:

        On BOTH loops the recorded signal is each query's width BEYOND
        the first round it actually ran (zero when round one finished
        it): widths observed below the current first-round width are
        unobservable, so recording absolute widths under an applied
        seed would floor at the seed and ratchet forever. Tail-relative
        recording lets a seed decay: once seeded runs stop producing
        tails, zeros fill the QBS ring and the p90 falls back toward
        the default.

          * device loop — the seed sizes the STRAGGLER round width
            ``ws`` (which also shrinks the static round budget
            ceil(remaining/ws)); the fused first round keeps its narrow
            default, because widening it charges the whole batch for
            the tail's worst case.
          * host loop — default first beam + seed tail becomes the
            initial doubling beam: most queries then retire in one
            synced round instead of two.

        Seeds are quantized to powers of two before use (round widths
        are static jit args; raw p90s drift by a few tiles between
        batches and would re-trace per drift) and clamped to at least
        the engine default. Seeding shifts work between rounds but
        never affects results — both loops stop on the same exact
        bound. Every group's recorded tail width is appended to
        ``stats.knn_group_widths`` so the caller can close the QBS
        feedback loop."""
        return self._dispatch_jobs(jobs, stats, device_loop,
                                   groups=groups, seeds=seeds,
                                   eager=True).finish()

    def _dispatch_jobs(self, jobs, stats: EngineStats, device_loop: bool,
                       groups: Optional[Sequence[KnnGroupSpec]] = None,
                       seeds: Optional[Dict[str, int]] = None,
                       eager: bool = True, record_cost: bool = True
                       ) -> _PendingJobs:
        """Dispatch half of ``_run_jobs``. Per group, the device-loop
        path enqueues the fused first round (``batched_knn_device_async``)
        and defers the fence + straggler loop + stats recording into a
        finisher the returned ``_PendingJobs.finish()`` runs in group
        order; the sharded and host-loop paths have no async
        implementation and execute eagerly at dispatch (zero overlap,
        same results). With ``eager=True`` each group's finisher runs
        inline right after its dispatch — exactly the pre-split
        ``_run_jobs`` sequencing. ``record_cost=False`` skips the
        wall-time ``stage_samples`` (overlapped timing would poison the
        cost model's online refit); value-based convergence widths are
        always recorded."""
        sharded = device_loop and self.mesh is not None
        pend = _PendingJobs(len(jobs))
        if groups is None:
            groups = self._group_jobs(jobs, device_loop)
        # delta-aware QBS keying: while un-folded delta tiles are
        # unioned in, scans converge wider (delta balls overlap base
        # regions); recording those widths under the base archetype
        # would keep inflating its p90 long after fold() removes the
        # delta. A ":delta" suffix keys them separately — post-fold
        # batches immediately read the clean base seed again.
        suffix = ":delta" if self.delta_tiles else ""
        for grp in groups:
            t_g0 = time.time()
            idxs = list(grp.jobs)
            attr, kmax, n_masked = grp.attr, grp.kmax, grp.n_masked
            arch = grp.archetype + suffix
            seed = seeds.get(arch) if seeds else None
            conv: list = []
            if sharded:
                st = self.sharded_dev[attr]
                qs_np = np.stack([jobs[i][0].vec() for i in idxs])
                masks_np = np.stack([jobs[i][1]
                                     for i in idxs[:n_masked]]) \
                    if n_masked else None
                ws = max(1, _next_pow2(seed)) if seed else None
                _, rows = batched_knn_sharded(
                    st, qs_np, kmax, masks_np=masks_np, beam=self.beam,
                    interpret=self.interpret, ws=ws, stats=stats,
                    conv_out=conv, precision=self.precision)
                knn_pend = _ReadyKnn(rows)
                s = st.shards
                w_base = max(1, min(
                    -(-max(1, self.beam // 2) // s), st.t_total))
                feat_shards, feat_tiles = s, st.t_total
                feat_cap, feat_dim = st.cap, qs_np.shape[1]
            else:
                qs = jnp.asarray(np.stack([jobs[i][0].vec()
                                           for i in idxs]))
                masks = None
                if n_masked:
                    masks = jnp.asarray(np.stack(
                        [jobs[i][1] for i in idxs[:n_masked]]))
                    if n_masked < len(idxs):
                        masks = jnp.concatenate(
                            [masks,
                             jnp.ones((len(idxs) - n_masked, self.n),
                                      bool)])
                geom = self.geom_dev[attr] if device_loop \
                    else self.geom[attr]
                tiles = self.vec_tiles_dev[attr] if device_loop \
                    else self.vec_tiles[attr]
                planes = None
                if self.precision != "fp32":
                    planes = (self.vec_planes_dev if device_loop
                              else self.vec_planes)[attr]
                l = geom.n_leaves
                if device_loop:
                    ws = max(self.beam, _next_pow2(seed)) if seed \
                        else None
                    knn_pend = batched_knn_device_async(
                        geom, tiles, qs, kmax, masks=masks,
                        beam=self.beam, interpret=self.interpret,
                        planes=planes, precision=self.precision,
                        ws=ws, stats=stats, conv_out=conv)
                    w_base = max(1, min(max(1, self.beam // 2), l))
                else:
                    beam_eff = max(self.beam,
                                   _next_pow2(self.beam + seed)) \
                        if seed else self.beam
                    _, rows = batched_knn(
                        geom, tiles, qs, kmax, masks=masks,
                        beam=beam_eff, interpret=self.interpret,
                        planes=planes, precision=self.precision,
                        stats=stats, conv_out=conv)
                    knn_pend = _ReadyKnn(rows)
                    w_base = max(1, min(beam_eff, l))
                feat_shards, feat_tiles = 0, l
                feat_cap, feat_dim = geom.cap, qs.shape[1]
            # calibrated-cost feedback: the group's observed seconds
            # against the same analytic features the planner predicts
            # from (ONE builder, ``cost.knn_plan_features`` — record
            # and predict can never drift)
            kind = costm.knn_kind(device_loop, feat_shards)
            feats = costm.knn_plan_features(
                device_loop=device_loop, shards=feat_shards,
                g=len(idxs), k=kmax, beam=self.beam,
                tiles=feat_tiles, cap=feat_cap, dim=feat_dim,
                precision=self.precision, seed=seed)

            def _fin(out, knn_pend=knn_pend, conv=conv, w_base=w_base,
                     idxs=idxs, arch=arch, kind=kind, feats=feats,
                     t_g0=t_g0):
                _, rows = knn_pend.finish()
                signal = np.maximum(conv[0] - w_base, 0)
                width = int(np.ceil(np.quantile(signal, 0.9))) \
                    if len(signal) else 0
                stats.knn_group_widths.append((arch, width))
                if record_cost:
                    stats.stage_samples.append(
                        (kind, feats, time.time() - t_g0))
                for pos, i in enumerate(idxs):
                    out[i] = rows[pos, :jobs[i][0].k]

            if eager:
                pend.run_now(_fin)
            else:
                pend.add(_fin)
        return pend

    # -------------------------------------------------------------- explain
    def vr_tile_estimate(self, vr: Q.VR) -> Tuple[int, int]:
        """(surviving, total) tile counts under the V.R triangle bound —
        the planner's pruned-tile estimate for ``explain()``; the same
        bound ``_vr_masks`` executes, evaluated for one query."""
        g = self.geom[vr.attr]
        ok = np.asarray(_vr_leaf_plan(
            jnp.asarray(vr.vec()[None, :], jnp.float32),
            jnp.asarray([vr.radius], jnp.float32), g.centroid, g.radius))
        return int(ok.sum()), self.n_tiles

    # -------------------------------------------------------------- execute
    def execute_batch(self, queries: Sequence[Q.Query], *,
                      device_loop: Optional[bool] = None,
                      plan: Optional[EnginePlan] = None
                      ) -> Tuple[List[np.ndarray], EngineStats]:
        """Execute a batch of plannable query trees. Returns one row array
        per query (see module docstring for the ordering contract).
        ``device_loop`` overrides the engine default per call (None =
        use the constructor flag) without rebuilding device state.

        ``plan`` (built by ``repro.core.planner`` and cached per batch
        archetype) supplies the pre-derived job layout, KNN grouping, and
        QBS beam seeds: plannability checks and grouping are skipped, and
        the job layout is cross-checked against this batch's walk."""
        device_loop = self._resolve_loop(device_loop, plan)
        t0 = time.time()
        stats = EngineStats(queries=len(queries),
                            shards=(self.shards or 0) if device_loop
                            else 0)
        pred_masks = self._stage_batch(queries, stats, device_loop, plan)
        jobs, groups, seeds = self._plan_jobs(queries, pred_masks, plan)
        job_rows = self._run_jobs(jobs, stats, device_loop,
                                  groups=groups, seeds=seeds)
        out = self._finish_walk(queries, pred_masks, jobs, job_rows)
        stats.time_s = time.time() - t0
        return out, stats

    def execute_batch_async(self, queries: Sequence[Q.Query], *,
                            device_loop: Optional[bool] = None,
                            plan: Optional[EnginePlan] = None,
                            record_cost: bool = False) -> PendingBatch:
        """Dispatch half of ``execute_batch``: predicate masks and every
        KNN group's fused first round are ENQUEUED on the device and
        this returns without waiting for results — per-round state
        (heaps, bounds, active masks) stays device-resident. The
        returned ``PendingBatch.materialize()`` runs the deferred
        epilogue — one explicit fence per KNN group (the (G,)
        active-mask read whose D2H copy was started at dispatch), the
        compacted straggler loop, the finishing walk — and yields
        exactly ``execute_batch``'s (rows, stats).

        Other batches may be dispatched between the two halves: the
        serving pipeline overlaps chunk i's epilogue and chunk i+2's
        staging with chunk i+1's device compute. ``record_cost=False``
        (the default here, unlike the synchronous path) skips the
        per-stage wall-time cost samples — under overlap a stage's
        observed seconds include waiting on unrelated enqueued work,
        which would poison the cost model's online refit. Value-based
        feedback (convergence widths) is still recorded at
        materialize time."""
        device_loop = self._resolve_loop(device_loop, plan)
        t0 = time.time()
        stats = EngineStats(queries=len(queries),
                            shards=(self.shards or 0) if device_loop
                            else 0)
        pred_masks = self._stage_batch(queries, stats, device_loop, plan)
        jobs, groups, seeds = self._plan_jobs(queries, pred_masks, plan)
        pending = self._dispatch_jobs(jobs, stats, device_loop,
                                      groups=groups, seeds=seeds,
                                      eager=False,
                                      record_cost=record_cost)
        t_disp = time.time() - t0

        def _materialize():
            t1 = time.time()
            job_rows = pending.finish()
            out = self._finish_walk(queries, pred_masks, jobs, job_rows)
            # host-side work only: dispatch + epilogue (the overlap
            # window between the halves is deliberately not counted)
            stats.time_s = t_disp + (time.time() - t1)
            return out, stats

        return PendingBatch(_materialize)

    def _resolve_loop(self, device_loop: Optional[bool],
                      plan: Optional[EnginePlan]) -> bool:
        """Effective loop flag + cached-plan validation (shared by the
        sync and async batch entry points)."""
        if plan is not None:
            # only the device loop executes sharded; host-loop (oracle)
            # plans always carry shards=0 and are valid on any engine
            want = (self.shards or 0) if plan.device_loop else 0
            if plan.shards != want:
                raise ValueError(
                    f"EnginePlan was grouped for shards={plan.shards} "
                    f"but this engine runs shards={want} "
                    f"(stale or mis-keyed plan cache)")
            if plan.precision != self.precision:
                raise ValueError(
                    f"EnginePlan was keyed for precision="
                    f"{plan.precision!r} but this engine runs "
                    f"precision={self.precision!r} "
                    f"(stale or mis-keyed plan cache)")
            return plan.device_loop
        if device_loop is None:
            return self.device_loop
        return device_loop

    def _stage_batch(self, queries: Sequence[Q.Query], stats: EngineStats,
                     device_loop: bool, plan: Optional[EnginePlan]
                     ) -> Dict[Q.Query, np.ndarray]:
        """Plannability checks (planless batches only) + predicate-mask
        stage — the shared front half of both batch entry points."""
        if plan is None:
            for q in queries:
                if not plannable(q):
                    raise ValueError(
                        f"query not plannable for the batched engine "
                        f"(use MQRLD.execute_batch for scalar fallback): "
                        f"{q!r}")
        return self._predicate_masks(queries, stats,
                                     tile_route=device_loop)

    def _plan_jobs(self, queries: Sequence[Q.Query],
                   pred_masks: Dict[Q.Query, np.ndarray],
                   plan: Optional[EnginePlan]):
        """Walk the batch into V.K jobs and cross-check a cached plan's
        job layout against them. Returns (jobs, groups, seeds)."""
        jobs: List[Tuple[Q.VK, Optional[jax.Array]]] = []
        ctr = [0]
        for q in queries:
            self._walk(q, None, pred_masks, jobs, None, ctr)
        groups = seeds = None
        if plan is not None:
            got = tuple((vk.attr, vk.k, m is not None) for vk, m in jobs)
            if got != plan.job_specs:
                raise ValueError(
                    f"EnginePlan job layout does not match this batch "
                    f"(stale or mis-keyed plan cache): plan expects "
                    f"{plan.job_specs}, walk produced {got}")
            groups, seeds = plan.groups, plan.seeds
        return jobs, groups, seeds

    def _finish_walk(self, queries: Sequence[Q.Query],
                     pred_masks: Dict[Q.Query, np.ndarray], jobs,
                     job_rows: List[np.ndarray]) -> List[np.ndarray]:
        """Finishing pass: substitute job rows into each query's mask
        walk (host numpy) — the shared back half of both entry points."""
        out: List[np.ndarray] = []
        ctr = [0]
        for q in queries:
            if isinstance(q, Q.VK):
                ctr[0] += 1  # consume this query's own job slot
                rows = np.asarray(job_rows[ctr[0] - 1])
                out.append(rows[rows >= 0].astype(np.int64))
                continue
            m = self._walk(q, None, pred_masks, jobs, job_rows, ctr)
            out.append(np.nonzero(m)[0].astype(np.int64))
        return out
