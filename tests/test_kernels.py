"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs pure-jnp ref."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.fused_topk import topk_l2_masked_pallas, topk_l2_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.lpgf_force import lpgf_force_pallas
from repro.kernels.pairwise_l2 import pairwise_sq_l2_pallas

RNG = np.random.default_rng(42)


def _arr(shape, dtype):
    return jnp.asarray(RNG.normal(size=shape).astype(dtype))


@pytest.mark.parametrize("m,n,d", [(17, 33, 5), (64, 64, 16), (100, 257, 40),
                                   (1, 300, 128), (130, 1, 7)])
@pytest.mark.parametrize("dtype", [np.float32, np.float16])
def test_pairwise_sweep(m, n, d, dtype):
    q, p = _arr((m, d), dtype), _arr((n, d), dtype)
    got = pairwise_sq_l2_pallas(q, p, bm=32, bn=64, interpret=True)
    want = ref.pairwise_sq_l2(q, p)
    tol = 1e-4 if dtype == np.float32 else 5e-2
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)


@pytest.mark.parametrize("m,n,d,k", [(20, 100, 8, 5), (64, 64, 16, 10),
                                     (7, 500, 24, 1), (50, 33, 4, 33)])
def test_topk_sweep(m, n, d, k):
    q, p = _arr((m, d), np.float32), _arr((n, d), np.float32)
    gd, gi = topk_l2_pallas(q, p, k, bm=16, bn=64, interpret=True)
    wd, wi = ref.topk_l2(q, p, k)
    np.testing.assert_allclose(np.sort(gd, 1), np.sort(wd, 1),
                               rtol=1e-4, atol=1e-4)
    # index sets must match where distances are distinct
    for i in range(m):
        assert set(np.asarray(gi)[i].tolist()) == \
            set(np.asarray(wi)[i].tolist())


@pytest.mark.parametrize("g,c,d,k", [(5, 37, 12, 4), (8, 300, 32, 10),
                                     (3, 7, 5, 10), (1, 1, 1, 3),
                                     (16, 129, 8, 16)])
@pytest.mark.parametrize("density", [1.0, 0.5, 0.02])
def test_topk_masked_sweep(g, c, d, k, density):
    """Row-masked per-query-candidate variant (hybrid-engine leaf scan)."""
    q = _arr((g, d), np.float32)
    p = _arr((g, c, d), np.float32)
    v = jnp.asarray(RNG.random((g, c)) < density)
    gd, gi, _ = topk_l2_masked_pallas(q, p, v, k, bg=4, bc=64,
                                      interpret=True)
    wd, wi = ref.topk_l2_masked(q, p, v, k)
    gd, gi, wd, wi = map(np.asarray, (gd, gi, wd, wi))
    # identical validity pattern, same distances, same index sets
    assert (np.isfinite(gd) == np.isfinite(wd)).all()
    fin = np.isfinite(wd)
    np.testing.assert_allclose(gd[fin], wd[fin], rtol=1e-4, atol=1e-4)
    assert ((gi >= 0) == fin).all() and ((wi >= 0) == fin).all()
    for i in range(g):
        assert set(gi[i][fin[i]].tolist()) == set(wi[i][fin[i]].tolist())
        # masked-out candidates never appear
        assert all(bool(v[i, j]) for j in gi[i][fin[i]])


# ---------------------------------------------------------------------------
# topk_l2_masked edge cases (the hybrid engine's beam-round kernel)
# ---------------------------------------------------------------------------
def test_topk_masked_all_masked_tiles():
    """Queries whose whole candidate tile is masked out must come back
    as (inf, -1) everywhere; mixed rows are unaffected."""
    g, c, d, k = 4, 96, 8, 5
    q = _arr((g, d), np.float32)
    p = _arr((g, c, d), np.float32)
    v = np.ones((g, c), bool)
    v[0] = False                      # fully masked query
    v[2, 50:] = False                 # half-masked query
    gd, gi, _ = topk_l2_masked_pallas(q, p, jnp.asarray(v), k,
                                   bg=2, bc=32, interpret=True)
    gd, gi = np.asarray(gd), np.asarray(gi)
    assert (gi[0] == -1).all() and np.isinf(gd[0]).all()
    assert (gi[1] >= 0).all()
    assert all(j < 50 for j in gi[2][gi[2] >= 0])
    wd, wi = ref.topk_l2_masked(q, p, jnp.asarray(v), k)
    assert (np.isfinite(gd) == np.isfinite(np.asarray(wd))).all()


def test_topk_masked_k_exceeds_surviving_rows():
    """k larger than the surviving-row count: exactly the survivors
    come back, the rest of the k slots are (inf, -1) padding."""
    g, c, d, k = 3, 40, 6, 25
    q = _arr((g, d), np.float32)
    p = _arr((g, c, d), np.float32)
    v = np.zeros((g, c), bool)
    v[0, :7] = True
    v[1, :1] = True                   # single survivor
    v[2, :] = True                    # k < c here? no: k=25 < c=40
    gd, gi, _ = topk_l2_masked_pallas(q, p, jnp.asarray(v), k,
                                   bg=2, bc=16, interpret=True)
    gd, gi = np.asarray(gd), np.asarray(gi)
    assert (gi[0] >= 0).sum() == 7 and np.isinf(gd[0][7:]).all()
    assert (gi[1] >= 0).sum() == 1
    assert set(gi[0][gi[0] >= 0].tolist()) == set(range(7))
    assert (gi[2] >= 0).sum() == k


def test_topk_masked_k_exceeds_candidate_width():
    """k > C: the kernel pads the requested width with (inf, -1)."""
    g, c, d, k = 2, 9, 4, 16
    q = _arr((g, d), np.float32)
    p = _arr((g, c, d), np.float32)
    v = jnp.asarray(np.ones((g, c), bool))
    gd, gi, _ = topk_l2_masked_pallas(q, p, v, k, interpret=True)
    gd, gi = np.asarray(gd), np.asarray(gi)
    assert gd.shape == (g, k) and gi.shape == (g, k)
    assert (gi[:, :c] >= 0).all() and (gi[:, c:] == -1).all()


def test_topk_masked_duplicate_distances():
    """Duplicated candidate points (exactly tied distances): distances
    must match the ref merge, returned indices must be unique, valid,
    and consistent with their reported distance."""
    g, c, d, k = 3, 64, 5, 10
    q = _arr((g, d), np.float32)
    base = np.asarray(_arr((g, c // 2, d), np.float32))
    p = jnp.asarray(np.concatenate([base, base], axis=1))  # every point x2
    v = jnp.asarray(np.ones((g, c), bool))
    gd, gi, _ = topk_l2_masked_pallas(q, p, v, k, bg=2, bc=16,
                                   interpret=True)
    wd, wi = ref.topk_l2_masked(q, p, v, k)
    gd, gi, wd, wi = map(np.asarray, (gd, gi, wd, wi))
    np.testing.assert_allclose(gd, wd, rtol=1e-5, atol=1e-5)
    pn = np.asarray(p)
    for i in range(g):
        ids = gi[i][gi[i] >= 0]
        assert len(set(ids.tolist())) == len(ids)  # no duplicate slots
        d2 = ((pn[i, ids] - np.asarray(q)[i]) ** 2).sum(1)
        np.testing.assert_allclose(d2, gd[i][gi[i] >= 0],
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("c,bc", [(37, 16), (129, 32), (100, 64),
                                  (5, 64)])
def test_topk_masked_ragged_tile_counts(c, bc):
    """Candidate counts that are not a multiple of the block width:
    padding rows never leak into the result."""
    g, d, k = 5, 7, 6
    q = _arr((g, d), np.float32)
    p = _arr((g, c, d), np.float32)
    v = jnp.asarray(RNG.random((g, c)) < 0.6)
    gd, gi, _ = topk_l2_masked_pallas(q, p, v, k, bg=2, bc=bc,
                                   interpret=True)
    wd, wi = ref.topk_l2_masked(q, p, v, k)
    gd, gi, wd, wi = map(np.asarray, (gd, gi, wd, wi))
    assert (np.isfinite(gd) == np.isfinite(wd)).all()
    fin = np.isfinite(wd)
    np.testing.assert_allclose(gd[fin], wd[fin], rtol=1e-4, atol=1e-4)
    assert (gi < c).all()
    for i in range(g):
        assert set(gi[i][fin[i]].tolist()) == set(wi[i][fin[i]].tolist())


# ---------------------------------------------------------------------------
# tile early-out (lb2): a pure work-skipping hint — results must be
# bit-identical to the unhinted kernel for every legal bound
# ---------------------------------------------------------------------------
def _eo_case(g=5, c=120, d=7):
    q = _arr((g, d), np.float32)
    p = _arr((g, c, d), np.float32)
    v = jnp.asarray(RNG.random((g, c)) < 0.7)
    return q, p, v


@pytest.mark.parametrize("bound", ["zero", "exact", "half", "inf_pad"])
def test_topk_masked_early_out_identical(bound):
    """Legal lower bounds (0 = never skip, the exact distance = the
    tightest legal bound, halfway = typical ball bound) never change
    the result; +inf on masked columns composes with the skip."""
    q, p, v = _eo_case()
    k = 6
    base_d, base_i, _ = topk_l2_masked_pallas(q, p, v, k, bg=2, bc=32,
                                           interpret=True)
    dtrue = jnp.maximum(((p - q[:, None, :]) ** 2).sum(-1), 0.0)
    if bound == "zero":
        lb2 = jnp.zeros(v.shape, jnp.float32)
    elif bound == "exact":
        lb2 = dtrue
    elif bound == "half":
        lb2 = 0.5 * dtrue
    else:
        lb2 = jnp.where(v, 0.0, jnp.inf)
    gd, gi, _ = topk_l2_masked_pallas(q, p, v, k, bg=2, bc=32,
                                   interpret=True, lb2=lb2)
    assert np.array_equal(np.asarray(base_i), np.asarray(gi)), bound
    np.testing.assert_array_equal(np.asarray(base_d), np.asarray(gd))


def test_topk_masked_early_out_all_masked():
    """All-masked input with bounds: still (inf, -1) everywhere."""
    q, p, v = _eo_case()
    lb2 = jnp.zeros(v.shape, jnp.float32)
    gd, gi, _ = topk_l2_masked_pallas(q, p, jnp.zeros_like(v), 4, bg=2,
                                   bc=32, interpret=True, lb2=lb2)
    assert (np.asarray(gi) == -1).all()
    assert np.isinf(np.asarray(gd)).all()


def test_topk_masked_early_out_skippable_blocks():
    """Blocks whose every candidate is refuted by a huge bound leave
    the running buffer untouched — the first block establishes the
    heap, later refuted blocks must not disturb it."""
    q, p, v = _eo_case(c=96)
    k = 5
    # bounds: first 32 candidates honest (0), the rest +inf (refuted —
    # legal only if those rows are also masked out)
    v_np = np.asarray(v).copy()
    v_np[:, 32:] = False
    v2 = jnp.asarray(v_np)
    lb2 = jnp.concatenate([jnp.zeros((len(v_np), 32), jnp.float32),
                           jnp.full((len(v_np), 64), jnp.inf,
                                    jnp.float32)], axis=1)
    gd, gi, _ = topk_l2_masked_pallas(q, p, v2, k, bg=2, bc=32,
                                   interpret=True, lb2=lb2)
    wd, wi = ref.topk_l2_masked(q, p, v2, k)
    fin = np.isfinite(np.asarray(wd))
    np.testing.assert_allclose(np.asarray(gd)[fin], np.asarray(wd)[fin],
                               rtol=1e-4, atol=1e-4)
    assert (np.asarray(gi)[~fin] == -1).all()


# ---------------------------------------------------------------------------
# Delta-union edge sweeps: the tiles the async-ingest path feeds to the
# beam loops (and through them to topk_l2_masked) — empty delta,
# delta-only hits, duplicate distances straddling the base/delta
# boundary, and the partially-filled last delta tile
# ---------------------------------------------------------------------------
def _union_platform(seed=0, n=300, d=6):
    from repro.core.lake import MMOTable
    from repro.core.platform import MQRLD
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(4, d)).astype(np.float32) * 5
    lab = rng.integers(0, 4, n)
    vec = (centers[lab] + rng.normal(size=(n, d))).astype(np.float32)
    t = (MMOTable("union").add_vector("v", vec)
         .add_numeric("price", rng.uniform(0, 100, n).astype(np.float32)))
    p = MQRLD(t, seed=seed)
    p.prepare(min_leaf=8, max_leaf=64)
    return p, rng, centers, lab


def _union_knn(p, qs, k, masks=None):
    """Run the union KNN through BOTH loops on the engine's layouts and
    assert they agree; returns the device-loop rows."""
    from repro.core.engine import batched_knn, batched_knn_device
    eng = p.engine()
    _, rh = batched_knn(eng.geom["v"], eng.vec_tiles["v"], qs, k,
                        masks=masks, beam=4)
    _, rd = batched_knn_device(eng.geom_dev["v"], eng.vec_tiles_dev["v"],
                               qs, k, masks=masks, beam=4)
    for i in range(len(qs)):
        assert set(rh[i][rh[i] >= 0].tolist()) == \
            set(rd[i][rd[i] >= 0].tolist()), i
    return rd


def test_union_empty_delta_is_base_identity():
    """Empty delta: the union state IS the base state (no copies), and
    an explicit sync back to empty restores base arrays exactly."""
    from repro.core.engine import batched_knn
    p, rng, centers, _ = _union_platform(seed=1)
    eng = p.engine()
    nb = p.table.n_rows
    assert eng.n == nb and eng.delta_tiles == 0
    base_tiles = eng.vec_tiles["v"]
    base_n_tiles = eng.n_tiles
    qs = p.table.vector["v"][:4]
    _, want = batched_knn(eng.geom["v"], eng.vec_tiles["v"], qs, 5, beam=4)
    m = 9
    p.append(numeric={"price": rng.uniform(0, 100, m).astype(np.float32)},
             vector={"v": (centers[rng.integers(0, 4, m)]
                           + rng.normal(size=(m, 6))).astype(np.float32)},
             fold=False)
    eng = p.engine()
    assert eng.n_tiles > base_n_tiles and eng.delta_rows == m
    eng.sync_delta(None, epoch=p.delta_epoch + 1)  # force back to empty
    assert eng.vec_tiles["v"] is base_tiles  # base refs restored
    assert eng.n == nb and eng.n_tiles == base_n_tiles
    _, got = batched_knn(eng.geom["v"], eng.vec_tiles["v"], qs, 5, beam=4)
    np.testing.assert_array_equal(got, want)


def test_union_delta_only_hits_k_exceeds_base_survivors():
    """A mask admitting ONLY delta rows with k > base survivors (zero):
    every returned row is a delta row, exactly the top-k of the delta."""
    p, rng, centers, _ = _union_platform(seed=2)
    nb = p.table.n_rows
    m = 7
    dvec = (centers[rng.integers(0, 4, m)]
            + rng.normal(size=(m, 6))).astype(np.float32)
    p.append(numeric={"price": rng.uniform(0, 100, m).astype(np.float32)},
             vector={"v": dvec}, fold=False)
    eng = p.engine()
    qs = dvec[:3]
    masks = np.zeros((3, eng.n), bool)
    masks[:, nb:nb + m] = True            # delta rows only
    rows = _union_knn(p, qs, 10, masks=masks)  # k=10 > m=7 survivors
    for i in range(3):
        got = rows[i][rows[i] >= 0]
        assert len(got) == m and (got >= nb).all()
        d2 = ((dvec - qs[i]) ** 2).sum(1)
        want = {nb + j for j in np.argsort(d2, kind="stable").tolist()}
        assert set(got.tolist()) == want


def test_union_duplicate_distances_straddle_boundary():
    """Delta rows that are exact copies of base rows: tied distances
    straddling the base/delta boundary must come back with the ref
    distance multiset, unique ids, and no invalid slots."""
    from repro.kernels import ref
    p, rng, _, _ = _union_platform(seed=3)
    nb = p.table.n_rows
    src = np.asarray([5, 77, 123])
    dvec = p.table.vector["v"][src].copy()     # exact duplicates
    p.append(numeric={"price": p.table.numeric["price"][src].copy()},
             vector={"v": dvec}, fold=False)
    qs = dvec[:2] + np.float32(1e-5)
    rows = _union_knn(p, qs, 4)
    full = np.concatenate([p.table.vector["v"], dvec])
    for i in range(2):
        got = rows[i][rows[i] >= 0]
        assert len(set(got.tolist())) == len(got) == 4
        d2 = ((full[got] - qs[i]) ** 2).sum(1)
        wd, _ = ref.topk_l2(qs[i][None], jnp.asarray(full), 4)
        np.testing.assert_allclose(np.sort(d2), np.sort(np.asarray(wd)[0]),
                                   rtol=1e-4, atol=1e-5)
        # both copies of the duplicated point must appear before any
        # farther row: the query's own base+delta pair is in the top-2
        assert {int(src[i]), nb + i} <= set(got.tolist())


def test_union_partially_filled_last_delta_tile():
    """m chosen so the pow2 capacity leaves the last delta tile partly
    empty: pad slots (NaN columns, -1 row ids) never leak into KNN
    results or predicate masks."""
    p, rng, centers, _ = _union_platform(seed=4)
    nb = p.table.n_rows
    m = 5                                     # capacity pads to 8
    p.append(numeric={"price": np.full(m, 55.0, np.float32)},
             vector={"v": (centers[rng.integers(0, 4, m)]
                           + rng.normal(size=(m, 6))).astype(np.float32)},
             fold=False)
    assert p.delta.capacity == 8
    eng = p.engine()
    assert eng.n == nb + 8                    # pad rows in the id space
    qs = p.delta.live_vector("v")[:3]
    rows = _union_knn(p, qs, 6)
    assert (rows < nb + m).all(), "pad slot leaked into KNN results"
    # grouped predicate masks: pads fail every predicate
    from repro.core import query as Q
    from repro.core.engine import EngineStats
    masks = eng._predicate_masks([Q.NR("price", 0, 100)], EngineStats())
    mk = masks[Q.NR("price", 0, 100)]
    assert mk[:nb + m].sum() > 0 and not mk[nb + m:].any()
    # and the full batched path stays oracle-exact
    q = Q.And.of(Q.NE("price", 55.0, 0.5),
                 Q.VK.of("v", qs[0], 8))
    for dl in (True, False):
        (got,), _ = p.execute_batch([q], device_loop=dl)
        assert set(got.tolist()) == set(p.oracle(q).tolist()), dl


# ---------------------------------------------------------------------------
# The running top-k merge (k passes of lane-wise min extraction) against
# the reference's single stable top_k. Integer-valued points keep every
# distance exact, so ties are real and both sides must agree bit for bit:
# same distances, same indices, lower index first among equal distances.
# ---------------------------------------------------------------------------
def _int_case(g, c, d, lo=-3, hi=4, dup=1):
    q = RNG.integers(lo, hi, size=(g, d)).astype(np.float32)
    p = RNG.integers(lo, hi, size=(g, c // dup, d)).astype(np.float32)
    p = np.concatenate([p] * dup + [p[:, :c - dup * (c // dup)]], axis=1)
    return jnp.asarray(q), jnp.asarray(p)


def _assert_merge_exact(got, want):
    gd, gi = map(np.asarray, got[:2])
    wd, wi = map(np.asarray, want)
    np.testing.assert_array_equal(gd, wd)
    np.testing.assert_array_equal(gi, wi)


@pytest.mark.parametrize("k,bc", [(5, 16), (10, 32), (33, 16)])
def test_merge_ties_match_ref(k, bc):
    """Heavy ties (a 4-value grid, every point duplicated): the merge's
    buffer-first, lower-position-first law equals the reference's stable
    top_k over the whole candidate set."""
    q, p = _int_case(6, 96, 3, lo=0, hi=2, dup=2)
    v = jnp.asarray(RNG.random((6, 96)) < 0.8)
    _assert_merge_exact(
        topk_l2_masked_pallas(q, p, v, k, bg=2, bc=bc, interpret=True),
        ref.topk_l2_masked(q, p, v, k))


@pytest.mark.parametrize("survivors", [0, 1, 7])
def test_merge_k_exceeds_survivors_across_blocks(survivors):
    """Fewer valid candidates than k, spread over several blocks: exactly
    the survivors come back in order, then (inf, -1) slots."""
    g, c, d, k = 4, 80, 5, 12
    q, p = _int_case(g, c, d)
    v = np.zeros((g, c), bool)
    for i in range(g):
        v[i, RNG.choice(c, survivors, replace=False)] = True
    _assert_merge_exact(
        topk_l2_masked_pallas(q, p, jnp.asarray(v), k, bg=2, bc=16,
                              interpret=True),
        ref.topk_l2_masked(q, p, jnp.asarray(v), k))


@pytest.mark.parametrize("with_lb", [False, True])
def test_merge_all_masked_blocks_between_live_ones(with_lb):
    """Whole blocks masked out between live ones (and a fully masked
    query): skipped blocks leave the running buffer untouched."""
    g, c, d, k, bc = 4, 128, 4, 6, 16
    q, p = _int_case(g, c, d)
    v = np.ones((g, c), bool)
    for blk in (1, 2, 5, 6):
        v[:, blk * bc:(blk + 1) * bc] = False
    v[3] = False
    v = jnp.asarray(v)
    lb2 = jnp.zeros((g, c), jnp.float32) if with_lb else None
    _assert_merge_exact(
        topk_l2_masked_pallas(q, p, v, k, bg=2, bc=bc, interpret=True,
                              lb2=lb2),
        ref.topk_l2_masked(q, p, v, k))


@pytest.mark.parametrize("c,bc", [(300, 64), (1024, 128)])
def test_merge_k100(c, bc):
    """k = 100 over several blocks (the widest V.K the engine serves)."""
    q, p = _int_case(8, c, 6)
    v = jnp.asarray(RNG.random((8, c)) < 0.7)
    _assert_merge_exact(
        topk_l2_masked_pallas(q, p, v, 100, bg=4, bc=bc, interpret=True),
        ref.topk_l2_masked(q, p, v, 100))


def _stable_merge(cd, ci, d, i, k):
    """The callers' merge: carry first, one stable top_k."""
    nd, pick = jax.lax.top_k(-jnp.concatenate([cd, d], 1), k)
    return -nd, jnp.take_along_axis(jnp.concatenate([ci, i], 1), pick, 1)


# (k, d, c, bc): k and d of the two benchmark configurations; the last
# case's candidate count is not a multiple of its block width
SEEDED = [(10, 128, 256, 64), (100, 768, 512, 128), (100, 128, 460, 96)]


@pytest.mark.parametrize("with_lb", [False, True])
@pytest.mark.parametrize("k,d,c,bc", SEEDED)
def test_merge_seeded_carry_matches_ref(k, d, c, bc, with_lb):
    """A call seeded with its carry's kth, merged carry first with a
    stable top_k, equals the reference over carry and block together.
    The block repeats the carry's candidates (distances exactly at the
    seed) and each of its own twice (ties inside one block); with the
    exact distances as bounds, whole blocks are skipped on them."""
    g, h = 6, c // 2
    q, half = _int_case(g, h, d, lo=0, hi=2)
    p = jnp.concatenate([half, half[:, :h // 2], half[:, :h - h // 2]],
                        axis=1)
    v = jnp.asarray(RNG.random((g, 2 * h)) < 0.9)
    cd, ci = ref.topk_l2_masked(q, p[:, :h], v[:, :h], k)
    lb2 = (((p[:, h:] - q[:, None, :]) ** 2).sum(-1) if with_lb else None)
    sd, si, work = topk_l2_masked_pallas(q, p[:, h:], v[:, h:], k, bg=2,
                                         bc=bc, interpret=True, lb2=lb2,
                                         kth0=cd[:, -1])
    si = jnp.where(si >= 0, si + h, -1)
    _assert_merge_exact(_stable_merge(cd, ci, sd, si, k),
                        ref.topk_l2_masked(q, p, v, k))
    # nothing at or above the seed is inserted
    assert (np.asarray(sd) < np.asarray(cd[:, -1:])).sum() == \
        np.isfinite(np.asarray(sd)).sum()
    assert int(np.asarray(work)[:, 1].sum()) <= k * int(
        np.asarray(work)[:, 0].sum())


@pytest.mark.parametrize("case", ["inf", "below", "first_block"])
@pytest.mark.parametrize("k,d,c,bc", SEEDED)
def test_merge_pass_count(k, d, c, bc, case):
    """The merge runs one pass per candidate that can still enter the
    top-k: a +inf seed is the unseeded call bit for bit, a seed below
    every distance gives no pass, and a first block into an empty
    buffer gives min(k, survivors) passes, survivors the fullest row's
    valid candidates."""
    g = 4
    q, p = _int_case(g, c, d)
    v = jnp.asarray(RNG.random((g, c)) < 0.7)
    base = topk_l2_masked_pallas(q, p, v, k, bg=g, bc=bc, interpret=True)
    blocks = -(-c // bc)
    if case == "inf":
        got = topk_l2_masked_pallas(q, p, v, k, bg=g, bc=bc,
                                    interpret=True,
                                    kth0=jnp.full((g,), jnp.inf))
        for a, b in zip(got, base):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    elif case == "below":
        gd, gi, work = topk_l2_masked_pallas(
            q, p, v, k, bg=g, bc=bc, interpret=True,
            kth0=jnp.full((g,), -1.0))
        assert np.isinf(np.asarray(gd)).all() and (np.asarray(gi) == -1).all()
        assert np.asarray(work).tolist() == [[blocks, 0]]
    else:
        _, _, work = topk_l2_masked_pallas(q, p[:, :bc], v[:, :bc], k,
                                           bg=g, bc=bc, interpret=True)
        fullest = int(np.asarray(v[:, :bc]).sum(1).max())
        assert np.asarray(work).tolist() == [[1, min(k, fullest)]]
    assert int(np.asarray(base[2])[0, 1]) <= k * blocks


@pytest.mark.parametrize("k", [2, 10, 100])
def test_shared_merge_matches_ref(k):
    """The shared-point-set kernel: same merge, padding rows excluded."""
    q = jnp.asarray(RNG.integers(-2, 3, size=(20, 4)).astype(np.float32))
    p = jnp.asarray(RNG.integers(-2, 3, size=(300, 4)).astype(np.float32))
    _assert_merge_exact(topk_l2_pallas(q, p, k, bm=16, bn=64,
                                       interpret=True),
                        ref.topk_l2(q, p, k))


@pytest.mark.parametrize("n,d", [(90, 11), (200, 5), (64, 33), (33, 2)])
@pytest.mark.parametrize("r,g", [(2.5, 0.7), (10.0, 1.5)])
def test_lpgf_sweep(n, d, r, g):
    x = _arr((n, d), np.float32)
    got_f, got_w = lpgf_force_pallas(x, r, g, bm=32, bn=32, interpret=True)
    want_f, want_w = ref.lpgf_force(x, r, g)
    scale = float(jnp.abs(want_f).max()) + 1e-6
    np.testing.assert_allclose(got_f / scale, want_f / scale, atol=2e-5)
    # the Fig-13 force law is continuous at the near/far boundary, so the
    # FORCE matches tightly; the WEIGHT of a boundary pair can classify
    # either way under fp reassociation -> loose tolerance on wsum
    np.testing.assert_allclose(got_w, want_w, rtol=2e-2, atol=0.5)


@pytest.mark.parametrize("b,s,h,hd", [(1, 64, 2, 16), (2, 128, 3, 32),
                                      (1, 32, 1, 64)])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 24),
                                           (False, 0)])
@pytest.mark.parametrize("dtype", [np.float32])
def test_flash_sweep(b, s, h, hd, causal, window, dtype):
    q, k, v = (_arr((b, s, h, hd), dtype) for _ in range(3))
    got = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                 bq=32, bk=32, interpret=True)
    want = ref.flash_attention(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_flash_bf16():
    q, k, v = (jnp.asarray(RNG.normal(size=(1, 64, 2, 32))).astype(
        jnp.bfloat16) for _ in range(3))
    got = flash_attention_pallas(q, k, v, bq=32, bk=32, interpret=True)
    want = ref.flash_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=5e-2, atol=5e-2)


def test_ops_dispatch_cpu_matches_ref():
    from repro.kernels import ops
    q, p = _arr((10, 6), np.float32), _arr((20, 6), np.float32)
    np.testing.assert_allclose(ops.pairwise_sq_l2(q, p),
                               ref.pairwise_sq_l2(q, p), rtol=1e-5)
    d1, i1 = ops.topk_l2(q, p, 3)
    d2, i2 = ref.topk_l2(q, p, 3)
    np.testing.assert_allclose(d1, d2, rtol=1e-5)


# ---------------------------------------------------------------------------
# mixed-precision leaf scan (ops.topk_l2_masked_mp): reduced-precision
# bound + fp32 rescue must return ROW-IDENTICAL indices to the fp32
# reference over the same gathered candidates
# ---------------------------------------------------------------------------
def _mp_vs_ref(tiles, valid_rows, q, sel, valid, k, precision,
               interpret=False, kth0=None):
    """Run the mp op and the fp32 oracle over the identical candidate
    gather; returns (mp_d, mp_idx, rescued, ref_d, ref_idx) numpy."""
    from repro.kernels import ops
    from repro.utils.quant import plan_tiles
    planes = plan_tiles(tiles, valid_rows, precision)
    pj = tuple(jnp.asarray(np.asarray(x)) for x in planes)
    dd, ii, resc = ops.topk_l2_masked_mp(
        jnp.asarray(q), jnp.asarray(sel), jnp.asarray(valid),
        jnp.asarray(tiles), *pj, k, kth0=kth0, precision=precision,
        interpret=interpret)
    gath = tiles[np.asarray(sel)].reshape(len(q), -1, tiles.shape[-1])
    wd, wi = ref.topk_l2_masked(jnp.asarray(q), jnp.asarray(gath),
                                jnp.asarray(valid), k)
    return (np.asarray(dd), np.asarray(ii), np.asarray(resc),
            np.asarray(wd), np.asarray(wi))


def _assert_mp_identical(got, want_d, want_i):
    dd, ii = got
    assert np.array_equal(ii, want_i)
    fin = np.isfinite(want_d)
    assert (np.isfinite(dd) == fin).all()
    np.testing.assert_allclose(dd[fin], want_d[fin], rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("precision", ["int8", "bf16"])
def test_mp_all_masked_tiles(precision):
    """Fully- and partially-masked candidate sets: masked candidates are
    never rescued, fully-masked queries come back (inf, -1)."""
    g, t, cap, d, k = 3, 6, 16, 8, 5
    tiles = RNG.normal(size=(t, cap, d)).astype(np.float32) * 3
    vr = np.ones((t, cap), bool)
    sel = np.tile(np.arange(4, dtype=np.int32), (g, 1))
    valid = np.ones((g, 4 * cap), bool)
    valid[0] = False                   # whole candidate set masked
    valid[2, cap:] = False             # only tile 0 survives
    dd, ii, resc, wd, wi = _mp_vs_ref(
        tiles, vr,
        RNG.normal(size=(g, d)).astype(np.float32), sel, valid, k,
        precision)
    _assert_mp_identical((dd, ii), wd, wi)
    assert (ii[0] == -1).all() and resc[0] == 0
    assert all(j < cap for j in ii[2][ii[2] >= 0])


@pytest.mark.parametrize("precision", ["int8", "bf16"])
def test_mp_duplicate_distances_straddle_rescue_boundary(precision):
    """Exactly tied distances straddling the kth boundary (integer-
    valued points, so fp32 distances are exact): the refutation rule is
    STRICT, so tie candidates are never refuted and the stable top-k
    resolves them in candidate-index order — identical to the fp32
    reference."""
    g, t, cap, d, k = 2, 4, 8, 4, 5
    # 8 candidates per tile; tiles 0/1 identical -> every distance is
    # duplicated across the tile boundary, and with k=5 the tie group at
    # the kth distance straddles the cut
    base = RNG.integers(-8, 9, size=(cap, d)).astype(np.float32)
    tiles = np.stack([base, base,
                      RNG.integers(-8, 9, size=(cap, d)).astype(np.float32),
                      np.zeros((cap, d), np.float32)])
    vr = np.ones((t, cap), bool)
    q = RNG.integers(-8, 9, size=(g, d)).astype(np.float32)
    sel = np.tile(np.arange(t, dtype=np.int32), (g, 1))
    valid = np.ones((g, t * cap), bool)
    dd, ii, resc, wd, wi = _mp_vs_ref(tiles, vr, q, sel, valid, k,
                                      precision)
    _assert_mp_identical((dd, ii), wd, wi)


@pytest.mark.parametrize("precision", ["int8", "bf16"])
def test_mp_k_exceeds_survivors_after_refutation(precision):
    """k larger than the valid candidate count, with a tight carry kth
    (kth0) refuting most of the frontier: exactly the survivors return,
    the rest of the k slots are (inf, -1) padding."""
    g, t, cap, d, k = 2, 3, 8, 6, 20
    tiles = RNG.normal(size=(t, cap, d)).astype(np.float32)
    vr = np.ones((t, cap), bool)
    q = RNG.normal(size=(g, d)).astype(np.float32)
    sel = np.tile(np.arange(2, dtype=np.int32), (g, 1))
    valid = np.zeros((g, 2 * cap), bool)
    valid[0, :7] = True
    valid[1, :1] = True               # single survivor
    dd, ii, resc, wd, wi = _mp_vs_ref(tiles, vr, q, sel, valid, k,
                                      precision)
    _assert_mp_identical((dd, ii), wd, wi)
    assert (ii[0] >= 0).sum() == 7 and (ii[1] >= 0).sum() == 1
    # a tight kth0 carry must refute without dropping true top-k rows:
    # use the true kth of query 0 as the carry (ties never refutable)
    kth0 = jnp.asarray(np.where(np.isfinite(wd[:, -1]),
                                wd[:, -1], np.inf), jnp.float32)
    dd2, ii2, resc2, _, _ = _mp_vs_ref(tiles, vr, q, sel, valid, k,
                                       precision, kth0=kth0)
    _assert_mp_identical((dd2, ii2), wd, wi)


@pytest.mark.parametrize("precision", ["int8", "bf16"])
def test_mp_degenerate_constant_tiles(precision):
    """Degenerate tiles: all-zero rows (the int8 scale floors instead of
    dividing by zero) and constant-row tiles (every distance identical)
    must round-trip exactly."""
    g, t, cap, d, k = 2, 3, 8, 5, 6
    tiles = np.zeros((t, cap, d), np.float32)
    tiles[1] = 2.5                     # constant rows -> all ties
    tiles[2] = RNG.normal(size=(cap, d)).astype(np.float32)
    vr = np.ones((t, cap), bool)
    q = RNG.normal(size=(g, d)).astype(np.float32)
    sel = np.tile(np.arange(t, dtype=np.int32), (g, 1))
    valid = np.ones((g, t * cap), bool)
    dd, ii, resc, wd, wi = _mp_vs_ref(tiles, vr, q, sel, valid, k,
                                      precision)
    _assert_mp_identical((dd, ii), wd, wi)


@pytest.mark.parametrize("precision", ["int8", "bf16"])
def test_mp_partially_filled_delta_tile(precision):
    """A partially-filled (delta-style) last tile: pad slots are zeroed
    before quantization (they must not inflate the tile scale) and are
    masked out of the scan."""
    g, t, cap, d, k = 2, 3, 8, 6, 4
    tiles = RNG.normal(size=(t, cap, d)).astype(np.float32)
    vr = np.ones((t, cap), bool)
    vr[2, 3:] = False                  # delta tile with 3 live slots
    tiles[2, 3:] = 40.0                # junk in the pad slots: a scale
    #                                    computed over them would nuke
    #                                    the live rows' resolution
    tiles_clean = tiles.copy()
    tiles_clean[2, 3:] = 0.0           # what the engine uploads as fp32
    q = RNG.normal(size=(g, d)).astype(np.float32)
    sel = np.tile(np.arange(t, dtype=np.int32), (g, 1))
    valid = np.ones((g, t * cap), bool)
    valid[:, 2 * cap + 3:] = False
    from repro.kernels import ops
    from repro.utils.quant import plan_tiles
    planes = plan_tiles(tiles, vr, precision)
    if precision == "int8":
        # the junk pad slots did not leak into the tile scale
        assert planes.scale[2] <= np.abs(tiles[2, :3]).max() / 127 + 1e-6
    pj = tuple(jnp.asarray(np.asarray(x)) for x in planes)
    dd, ii, resc = ops.topk_l2_masked_mp(
        jnp.asarray(q), jnp.asarray(sel), jnp.asarray(valid),
        jnp.asarray(tiles_clean), *pj, k, precision=precision)
    wd, wi = ref.topk_l2_masked(
        jnp.asarray(q),
        jnp.asarray(tiles_clean[sel].reshape(g, -1, d)),
        jnp.asarray(valid), k)
    _assert_mp_identical((np.asarray(dd), np.asarray(ii)),
                         np.asarray(wd), np.asarray(wi))


@pytest.mark.parametrize("precision", ["int8", "bf16"])
@pytest.mark.parametrize("interpret", [False, True])
def test_mp_lower_bound_validity(precision, interpret):
    """The conservative-bound contract itself: for every valid candidate
    the widened bound is <= the true fp32 squared distance (both the
    pure-jnp reference and the Pallas interpret dispatch)."""
    from repro.kernels import ops
    from repro.utils.quant import plan_tiles
    g, t, cap, d = 6, 8, 16, 12
    tiles = RNG.normal(size=(t, cap, d)).astype(np.float32) * 5
    vr = np.ones((t, cap), bool)
    vr[-1, 5:] = False
    q = RNG.normal(size=(g, d)).astype(np.float32) * 5
    sel = np.tile(np.arange(t, dtype=np.int32), (g, 1))
    c = t * cap
    valid = np.ones((g, c), bool)
    valid[:, -cap + 5:] = False
    planes = plan_tiles(tiles, vr, precision)
    codes = jnp.asarray(np.asarray(planes.data)[sel].reshape(g, c, d))
    cscale = jnp.asarray(np.repeat(planes.scale[sel], cap, axis=1))
    cppq = jnp.asarray(planes.ppq[sel].reshape(g, c))
    ceps = jnp.asarray(np.repeat(planes.eps[sel], cap, axis=1))
    lb2 = np.asarray(ops.quant_lb2(
        jnp.asarray(q), codes, cscale, cppq, ceps, jnp.asarray(valid),
        precision=precision, interpret=interpret))
    gath = tiles[sel].reshape(g, c, d)
    d2 = ((gath - q[:, None, :]) ** 2).sum(-1)
    assert (lb2[valid] <= d2[valid] + 1e-5).all()
    assert np.isinf(lb2[~valid]).all()
    # and the bound is not vacuous: most candidates carry a positive lb
    assert (lb2[valid] > 0).mean() > 0.5
