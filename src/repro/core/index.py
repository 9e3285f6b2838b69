"""High-dimensional learned index (paper §6).

Build = divisive hierarchical clustering (Algorithm 2): DPC splits with an
optional per-split LPGF pass, a *training-based evaluation* stop rule (a
linear-regression CDF over distance-to-centroid keys must predict in-bucket
positions with hit ratio >= delta = 0.951), and a cluster tree whose nodes
store {centroid C, radius R, ordered child list L | last-mile model M}.

Storage adaptation (Scala/JVM pointers -> TPU): the tree is struct-of-arrays;
leaf buckets are contiguous row ranges of the permuted MMO table, sorted by
key within each bucket, so the last-mile prediction indexes directly into
the physical layout. Queries run in two executors that return identical
results (tested):
  * host executor — paper-faithful traversal in sibling order with C/R
    pruning; counts node scans + bucket touches (CBR, Algorithm 3 input)
  * batched executor — vectorized lower-bound ranking over all leaves +
    padded bucket gathers, jit/vmap-able (the TPU serving path), with
    host-driven beam doubling until the exactness bound is met.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.dpc import dpc
from repro.core.lpgf import lpgf
from repro.kernels import ops


# ---------------------------------------------------------------------------
# Tree storage
# ---------------------------------------------------------------------------
@dataclass
class ClusterTree:
    centroid: np.ndarray      # (M, d)
    radius: np.ndarray        # (M,)
    parent: np.ndarray        # (M,)
    children: List[List[int]]  # sibling order = search order (Algorithm 3)
    is_leaf: np.ndarray       # (M,) bool
    bucket_start: np.ndarray  # (M,) leaf row ranges (else -1)
    bucket_end: np.ndarray
    lm_a: np.ndarray          # (M,) last-mile slope (leaves)
    lm_b: np.ndarray          # (M,) last-mile intercept
    depth: np.ndarray         # (M,)
    access_count: np.ndarray = field(default=None)  # Algorithm 3 statistics

    def __post_init__(self):
        if self.access_count is None:
            self.access_count = np.zeros(len(self.radius), np.int64)

    @property
    def n_nodes(self) -> int:
        return len(self.radius)

    @property
    def leaf_ids(self) -> np.ndarray:
        return np.nonzero(self.is_leaf)[0]

    def max_depth(self) -> int:
        return int(self.depth.max(initial=0))

    def size_bytes(self) -> int:
        arrs = [self.centroid, self.radius, self.parent, self.is_leaf,
                self.bucket_start, self.bucket_end, self.lm_a, self.lm_b,
                self.depth]
        child = sum(len(c) for c in self.children) * 8
        return int(sum(a.nbytes for a in arrs) + child)


@dataclass
class QueryStats:
    nodes_scanned: int = 0
    buckets_touched: int = 0        # unique buckets per query
    rows_scanned: int = 0
    time_s: float = 0.0
    cbr: float = 0.0
    _bucket_ids: set = field(default_factory=set)

    def touch(self, bucket_id: int):
        self._bucket_ids.add(int(bucket_id))
        self.buckets_touched = len(self._bucket_ids)


@dataclass
class BuildReport:
    n_nodes: int
    n_leaves: int
    max_depth: int
    avg_bucket: float
    build_s: float
    lm_hit_ratio: float       # mean last-mile hit ratio across leaves
    index_bytes: int
    # wall seconds per build phase, filled by the platform's build
    # pipeline: transform, lpgf, index (DPC/tree), layout (permutation +
    # leaf metadata)
    phase_s: Dict[str, float] = field(default_factory=dict)


# ---------------------------------------------------------------------------
# Build (Algorithm 2)
# ---------------------------------------------------------------------------
def _fit_last_mile(keys_sorted: np.ndarray) -> Tuple[float, float]:
    """Least-squares fit F(k) = a*k + b with F(k)*m ~ position."""
    m = len(keys_sorted)
    if m <= 1:
        return 0.0, 0.5
    target = (np.arange(m) + 0.5) / m
    k = keys_sorted.astype(np.float64)
    var = k.var()
    if var < 1e-18:
        return 0.0, float(target.mean())
    a = float(np.cov(k, target, bias=True)[0, 1] / var)
    b = float(target.mean() - a * k.mean())
    return a, b


def _hit_ratio(keys_sorted: np.ndarray, a: float, b: float,
               tol: int) -> float:
    m = len(keys_sorted)
    if m == 0:
        return 1.0
    pred = np.clip(np.round((a * keys_sorted + b) * m - 0.5), 0, m - 1)
    return float(np.mean(np.abs(pred - np.arange(m)) <= tol))


def build_index(features: np.ndarray, *, delta: float = 0.951,
                hit_tol: int = 8, min_leaf: int = 32, max_leaf: int = 4096,
                max_depth: int = 12, split_lpgf: bool = False,
                dpc_max_clusters: int = 8, dpc_sample: int = 4096,
                seed: int = 0) -> Tuple[ClusterTree, np.ndarray, "BuildReport"]:
    """Build the cluster tree over features (already representation-enhanced).

    Returns (tree, perm, report): ``perm`` maps new physical row order ->
    original row index; callers re-lay the MMO table with it.
    """
    t0 = time.time()
    x = np.asarray(features, np.float32)
    n = len(x)
    idx_all = np.arange(n)

    nodes: List[dict] = []
    order_rows: List[np.ndarray] = []
    cursor = 0
    hit_ratios: List[float] = []

    def new_node(parent: int, depth: int) -> int:
        nodes.append(dict(parent=parent, depth=depth, children=[],
                          centroid=None, radius=0.0, is_leaf=False,
                          start=-1, end=-1, a=0.0, b=0.0))
        return len(nodes) - 1

    root = new_node(-1, 0)
    stack: List[Tuple[int, np.ndarray]] = [(root, idx_all)]

    rng = np.random.default_rng(seed)
    while stack:
        node_id, rows = stack.pop()
        pts = x[rows]
        c = pts.mean(axis=0)
        nodes[node_id]["centroid"] = c
        keys = np.sqrt(np.maximum(
            ((pts - c[None]) ** 2).sum(1), 0.0)).astype(np.float32)
        nodes[node_id]["radius"] = float(keys.max(initial=0.0))

        srt = np.argsort(keys, kind="stable")
        a, b = _fit_last_mile(keys[srt])
        hr = _hit_ratio(keys[srt], a, b, hit_tol)

        stop = (len(rows) <= min_leaf
                or nodes[node_id]["depth"] >= max_depth
                or (hr >= delta and len(rows) <= max_leaf))
        if not stop:
            # split via DPC (optionally LPGF-enhanced coordinates)
            sub = pts
            if split_lpgf and len(rows) > min_leaf:
                sub = lpgf(pts, iters=1)
            if len(rows) > dpc_sample:
                # sample-fit DPC centers, then assign all rows to nearest
                sel = rng.choice(len(rows), dpc_sample, replace=False)
                res = dpc(sub[sel], max_clusters=dpc_max_clusters,
                          seed=seed)
                cent = np.stack([sub[sel][res.labels == l].mean(0)
                                 for l in np.unique(res.labels)])
                d2 = ops.pairwise_sq_l2_host(sub, cent)
                labels = d2.argmin(1).astype(np.int32)
            else:
                labels = dpc(sub, max_clusters=dpc_max_clusters,
                             seed=seed).labels
            uniq = np.unique(labels)
            if len(uniq) >= 2:
                subclusters = []
                for l in uniq:
                    sel = rows[labels == l]
                    if len(sel):
                        subclusters.append(sel)
                # sibling order: child centroid distance to parent centroid
                cents = [x[s].mean(0) for s in subclusters]
                dists = [float(np.linalg.norm(cc - c)) for cc in cents]
                order = np.argsort(dists, kind="stable")
                for oi in order:
                    child = new_node(node_id, nodes[node_id]["depth"] + 1)
                    nodes[node_id]["children"].append(child)
                    stack.append((child, subclusters[oi]))
                continue
            # DPC failed to split -> fall through to leaf

        # leaf: physical layout = rows sorted by key
        nodes[node_id]["is_leaf"] = True
        nodes[node_id]["a"], nodes[node_id]["b"] = a, b
        hit_ratios.append(hr)
        nodes[node_id]["start"] = cursor
        nodes[node_id]["end"] = cursor + len(rows)
        order_rows.append(rows[srt])
        cursor += len(rows)

    perm = np.concatenate(order_rows) if order_rows else np.array([], np.int64)
    m = len(nodes)
    tree = ClusterTree(
        centroid=np.stack([nd["centroid"] for nd in nodes]),
        radius=np.array([nd["radius"] for nd in nodes], np.float32),
        parent=np.array([nd["parent"] for nd in nodes], np.int32),
        children=[list(nd["children"]) for nd in nodes],
        is_leaf=np.array([nd["is_leaf"] for nd in nodes], bool),
        bucket_start=np.array([nd["start"] for nd in nodes], np.int64),
        bucket_end=np.array([nd["end"] for nd in nodes], np.int64),
        lm_a=np.array([nd["a"] for nd in nodes], np.float32),
        lm_b=np.array([nd["b"] for nd in nodes], np.float32),
        depth=np.array([nd["depth"] for nd in nodes], np.int32),
    )
    # remap bucket ranges to the permuted physical order (they already are:
    # order_rows appended in leaf-creation order == cursor order)
    leaves = tree.leaf_ids
    report = BuildReport(
        n_nodes=m, n_leaves=len(leaves), max_depth=tree.max_depth(),
        avg_bucket=float(np.mean(tree.bucket_end[leaves]
                                 - tree.bucket_start[leaves])),
        build_s=time.time() - t0,
        lm_hit_ratio=float(np.mean(hit_ratios)) if hit_ratios else 1.0,
        index_bytes=tree.size_bytes())
    return tree, perm, report


# ---------------------------------------------------------------------------
# Incremental fold (async-ingest merge path)
# ---------------------------------------------------------------------------
def fold_into_tree(tree: ClusterTree, enhanced: np.ndarray,
                   delta_enh: np.ndarray
                   ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Merge delta rows into an existing tree's leaf buckets in place.

    The cheap half of the offline build: instead of re-running transform
    init + DPC clustering over base+delta (a cold ``prepare``), each
    delta row is assigned to the nearest leaf centroid in the enhanced
    space, spliced into that leaf's bucket (re-sorted by
    distance-to-centroid key so the last-mile CDF model stays valid,
    and refit), and leaf + ancestor radii are widened so the tree stays
    a correct bounding hierarchy. Exactness of every query path never
    depends on the assignment — only layout quality does (per-leaf meta
    and engine tiles are rebuilt exactly from the merged table).

    ``enhanced`` is the PERMUTED base feature matrix (tree bucket ranges
    index it), ``delta_enh`` the delta rows in the same space. Mutates
    ``tree`` (bucket ranges, radii, last-mile fits) and returns
    ``(perm, bucket_id, bucket_starts)`` over the combined
    [base-physical; delta] row order, ready for
    ``MMOTable.apply_permutation``.
    """
    nb, m = len(enhanced), len(delta_enh)
    leaves = tree.leaf_ids
    cen = tree.centroid[leaves].astype(np.float32)
    d2 = ops.pairwise_sq_l2_host(delta_enh, cen)
    assign = d2.argmin(axis=1)                      # leaf position per row
    # widen ancestor balls so C/R pruning stays conservative
    for j in range(m):
        node = int(leaves[assign[j]])
        x = delta_enh[j]
        while node >= 0:
            dist = float(np.linalg.norm(x - tree.centroid[node]))
            if dist > tree.radius[node]:
                tree.radius[node] = dist
            node = int(tree.parent[node])
    comb = np.concatenate([np.asarray(enhanced, np.float32),
                           np.asarray(delta_enh, np.float32)])
    # splice per leaf, walking leaves in their current physical order
    order = np.argsort(tree.bucket_start[leaves], kind="stable")
    segs: List[np.ndarray] = []
    cursor = 0
    for pos in order:
        lid = int(leaves[pos])
        s, e = int(tree.bucket_start[lid]), int(tree.bucket_end[lid])
        extra = np.nonzero(assign == pos)[0]
        rows = np.concatenate([np.arange(s, e, dtype=np.int64),
                               nb + extra.astype(np.int64)])
        if len(extra) and len(rows):
            keys = np.sqrt(np.maximum(
                ((comb[rows] - tree.centroid[lid][None]) ** 2).sum(1),
                0.0)).astype(np.float32)
            srt = np.argsort(keys, kind="stable")
            rows = rows[srt]
            a, b = _fit_last_mile(keys[srt])
            tree.lm_a[lid], tree.lm_b[lid] = a, b
        tree.bucket_start[lid] = cursor
        tree.bucket_end[lid] = cursor + len(rows)
        segs.append(rows)
        cursor += len(rows)
    perm = np.concatenate(segs) if segs else np.array([], np.int64)
    bucket_id = np.zeros(len(perm), np.int32)
    for b, lid in enumerate(leaves):
        s, e = int(tree.bucket_start[lid]), int(tree.bucket_end[lid])
        bucket_id[s:e] = b
    bucket_starts = np.concatenate(
        [tree.bucket_start[leaves], [len(perm)]]).astype(np.int32)
    return perm, bucket_id, bucket_starts


# ---------------------------------------------------------------------------
# Host executor (paper-faithful traversal)
# ---------------------------------------------------------------------------
class HostExecutor:
    """Sibling-order traversal with C/R pruning + last-mile bucket scans.

    ``data`` must be the PERMUTED feature matrix (tree bucket ranges index
    it directly); ``keys[i]`` = distance of row i to its leaf centroid.
    """

    def __init__(self, tree: ClusterTree, data: np.ndarray):
        self.tree = tree
        self.data = np.asarray(data, np.float32)
        self.keys = self._row_keys()

    def _row_keys(self) -> np.ndarray:
        keys = np.zeros(len(self.data), np.float32)
        for lid in self.tree.leaf_ids:
            s, e = int(self.tree.bucket_start[lid]), int(self.tree.bucket_end[lid])
            c = self.tree.centroid[lid]
            keys[s:e] = np.sqrt(
                np.maximum(((self.data[s:e] - c) ** 2).sum(1), 0))
        return keys

    # -------------------------------------------------------------- helpers
    def _leaf_window(self, lid: int, key_lo: float, key_hi: float
                     ) -> Tuple[int, int]:
        """Last-mile search: the linear CDF model predicts the position of
        the query key; the window doubles outward until the sorted keys
        bracket [key_lo, key_hi] — O(1) model + local expansion instead of
        a full binary search (paper §6.1.1)."""
        s, e = int(self.tree.bucket_start[lid]), int(self.tree.bucket_end[lid])
        m = e - s
        if m == 0:
            return s, s
        ks = self.keys[s:e]
        a, b = float(self.tree.lm_a[lid]), float(self.tree.lm_b[lid])
        # model-seeded exponential expansion, then exact tighten
        pos_lo = int(np.clip(round((a * key_lo + b) * m - 0.5), 0, m - 1))
        pos_hi = int(np.clip(round((a * key_hi + b) * m - 0.5), 0, m - 1))
        w = 8
        lo = pos_lo
        while lo > 0 and ks[lo] >= key_lo:
            lo = max(0, lo - w)
            w *= 2
        w = 8
        hi = pos_hi + 1
        while hi < m and ks[hi - 1] <= key_hi:
            hi = min(m, hi + w)
            w *= 2
        lo_b = lo + int(np.searchsorted(ks[lo:hi], key_lo, side="left"))
        hi_b = lo + int(np.searchsorted(ks[lo:hi], key_hi, side="right"))
        return s + lo_b, s + hi_b

    # ------------------------------------------------------------------ KNN
    def knn(self, q: np.ndarray, k: int,
            row_mask: Optional[np.ndarray] = None
            ) -> Tuple[np.ndarray, QueryStats]:
        t0 = time.time()
        tree = self.tree
        stats = QueryStats()
        q = np.asarray(q, np.float32)
        best_d = np.full(k, np.inf)
        best_i = np.full(k, -1, np.int64)

        def push(cands: np.ndarray):
            nonlocal best_d, best_i
            if not len(cands):
                return
            d2 = ((self.data[cands] - q) ** 2).sum(1)
            if row_mask is not None:
                d2 = np.where(row_mask[cands], d2, np.inf)
            d = np.sqrt(np.maximum(d2, 0))
            alld = np.concatenate([best_d, d])
            alli = np.concatenate([best_i, cands])
            sel = np.argsort(alld, kind="stable")[:k]
            best_d, best_i = alld[sel], alli[sel]

        def visit(node: int):
            nonlocal stats
            stats.nodes_scanned += 1
            tree.access_count[node] += 1
            cq = float(np.linalg.norm(q - tree.centroid[node]))
            lb = max(0.0, cq - float(tree.radius[node]))
            if lb > best_d[-1]:
                return
            if tree.is_leaf[node]:
                stats.touch(node)
                dk = best_d[-1]
                if np.isfinite(dk):
                    lo, hi = self._leaf_window(node, cq - dk, cq + dk)
                else:
                    lo, hi = (int(tree.bucket_start[node]),
                              int(tree.bucket_end[node]))
                # last-mile model centers the scan; expand radially until
                # the key window covers [cq-dk, cq+dk]
                stats.rows_scanned += hi - lo
                push(np.arange(lo, hi))
                return
            for ch in tree.children[node]:  # sibling order (Algorithm 3)
                visit(ch)

        visit(0)
        stats.time_s = time.time() - t0
        n_leaves = len(tree.leaf_ids)
        stats.cbr = stats.buckets_touched / max(1, n_leaves)
        valid = best_i >= 0
        return best_i[valid], stats

    # ---------------------------------------------------------------- range
    def range_query(self, q: np.ndarray, radius: float,
                    row_mask: Optional[np.ndarray] = None
                    ) -> Tuple[np.ndarray, QueryStats]:
        t0 = time.time()
        tree = self.tree
        stats = QueryStats()
        q = np.asarray(q, np.float32)
        out: List[np.ndarray] = []

        def visit(node: int):
            stats.nodes_scanned += 1
            tree.access_count[node] += 1
            cq = float(np.linalg.norm(q - tree.centroid[node]))
            if cq - float(tree.radius[node]) > radius:
                return
            if tree.is_leaf[node]:
                stats.touch(node)
                lo, hi = self._leaf_window(node, cq - radius, cq + radius)
                stats.rows_scanned += hi - lo
                cands = np.arange(lo, hi)
                d2 = ((self.data[cands] - q) ** 2).sum(1)
                m = d2 <= radius * radius
                if row_mask is not None:
                    m &= row_mask[cands]
                out.append(cands[m])
                return
            for ch in tree.children[node]:
                visit(ch)

        visit(0)
        stats.time_s = time.time() - t0
        stats.cbr = stats.buckets_touched / max(1, len(tree.leaf_ids))
        rows = np.concatenate(out) if out else np.array([], np.int64)
        return rows, stats


# ---------------------------------------------------------------------------
# Batched executor (TPU-native serving path)
# ---------------------------------------------------------------------------
class BatchedExecutor:
    """Vectorized leaf-ranked KNN: lower bounds over all leaves, padded
    bucket gathers, exactness via beam doubling against the bound.

    Since the engine refactor this is a thin veneer over
    ``repro.core.engine.batched_knn``: the leaf scan runs through the
    Pallas ``fused_topk`` row-mask kernel (interpret mode on CPU) instead
    of a host-side per-query loop. Kept as the single-space KNN API; rich
    hybrid batches go through ``repro.core.engine.HybridEngine``.
    """

    def __init__(self, tree: ClusterTree, data: np.ndarray,
                 *, interpret: bool = True, tile: int = 128):
        import jax.numpy as jnp

        from repro.core.engine import LeafGeometry, bucket_tiles, tile_data
        self.tree = tree
        self.data = np.asarray(data, np.float32)
        self.interpret = interpret
        leaves = tree.leaf_ids
        self.leaves = leaves
        starts = tree.bucket_start[leaves]
        ends = tree.bucket_end[leaves]
        rows, cap, leaf_of_tile = bucket_tiles(starts, ends, tile)
        self.bucket_cap = cap
        self.geom = LeafGeometry(
            centroid=jnp.asarray(tree.centroid[leaves][leaf_of_tile],
                                 jnp.float32),
            radius=jnp.asarray(tree.radius[leaves][leaf_of_tile],
                               jnp.float32),
            bucket_rows=jnp.asarray(rows), cap=cap)
        self._data_tiles = jnp.asarray(tile_data(self.data, rows))

    def knn(self, qs: np.ndarray, k: int, beam: int = 8
            ) -> Tuple[np.ndarray, np.ndarray, QueryStats]:
        """qs: (Q, d) -> (dists (Q,k), rows (Q,k), stats). Exact."""
        from repro.core.engine import EngineStats, batched_knn
        es = EngineStats()
        best_d, best_i = batched_knn(
            self.geom, self._data_tiles, np.asarray(qs, np.float32), k,
            beam=beam, interpret=self.interpret, stats=es)
        stats = QueryStats()
        stats.buckets_touched = es.knn_buckets
        stats.rows_scanned = es.rows_scanned
        stats.time_s = es.time_s
        # buckets_touched counts TILES, so normalize by the tile count to
        # keep the cross-bucket-rate contract (cbr <= 1)
        nq, t = len(qs), self.geom.n_leaves
        stats.cbr = stats.buckets_touched / max(1, nq * t)
        return best_d.astype(np.float32), best_i.astype(np.int64), stats
