"""Pallas TPU kernel: fused distance + running top-k.

V.K queries never need the full (M, N) distance matrix — this kernel streams
point tiles through VMEM and keeps a per-query top-k candidate buffer in a
VMEM scratch, so HBM traffic is O(M*D + N*D + M*k) instead of O(M*N).

Grid: (M/BM, N/BN) with the N axis INNERMOST and "arbitrary" semantics —
each (i, j) step merges tile-j candidates into query tile i's running
buffer, a sorted (BM, KP) pair of distances and indices (k padded to a
lane multiple). The merge does work in proportion to the candidates
that can still enter the top-k, not to k: a candidate survives only if
``d < min(kth0, running kth)``, and the step runs one insertion pass per
survivor of its fullest row (at most k) — extract the block's smallest
survivor, find its slot in the sorted buffer, shift the tail one lane —
with no sort and no gather, which Mosaic does not lower. Tie-break law
(that of a stable sort): buffer entries first, then lower index. A
query that has seen N candidates has seen about k ln(N/k) of them beat
its running kth, so most steps run no pass at all.

Two variants share the merge scheme:
  * ``topk_l2_pallas``        — one shared point set for all queries
  * ``topk_l2_masked_pallas`` — per-query candidate tiles + a validity
    mask, the hybrid-engine leaf scan: each query ranks only the rows its
    bucket beam gathered, and filtered KNN (And(VK, predicate)) stays
    fused by zeroing the mask instead of re-gathering.

Threshold seed (``kth0``): the masked variant optionally takes each
query's kth SQUARED distance of the carry its output is merged into.
Every caller merges the kernel's top-k into that carry with a stable
``top_k``, carry first, so a candidate at or above the carry's kth can
never displace it (a tie goes to the carry): dropping it changes the
kernel's own output but not the merged result. From its first block the
call then inserts only what can reach the merged top-k, instead of
filling an empty buffer with k entries.

Tile early-out (``lb2``): the masked variant optionally takes per-candidate
SQUARED ball lower bounds (each candidate row carries its bucket tile's
``max(0, |q - C| - R)^2``). A grid step whose every valid candidate has
``lb2 >= min(kth0, running kth)`` cannot change the merged top-k — a
lower bound at or above the threshold proves the true distance can only
tie, and ties never displace the (stable) running buffer or carry — so
the whole distance + merge body is skipped under ``@pl.when``. Beam
rounds select tiles in ascending-bound order per query, so once a query
converges, the straggler tiles other queries still need stop charging
it: blocks whose candidates are all bound-refuted (or masked/padding)
become no-ops instead of full GEMM + merge steps.

Mixed-precision scan (``quant_lb2_pallas``): computes the per-candidate
widened bounds themselves in reduced precision. The candidate tiles are
stored as int8 codes (one symmetric scale per bucket tile) or bf16, the
distance GEMM runs on the narrow operands (int8 x int8 -> int32 on the
MXU), and each result is WIDENED downward by the analytic quantization
error bound plus an fp slack. Conservative-bound contract: the widened
value is always <= the true fp32 squared distance, so refuting a
candidate against a running kth distance is exact — only the surviving
frontier is rescored in fp32 (``ops.topk_l2_masked_mp``), and the final
top-k is row-identical to the fp32 oracle. Looseness only costs rescue
work, never correctness.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.pairwise_l2 import _pad

_HI = jax.lax.Precision.HIGHEST   # fp32 contraction on the MXU: the
#                                   distances feed exact top-k cuts


def _rup(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _rowwise_dot(q, p, acc=jnp.float32, precision=_HI):
    """(B, D) x (B, C, D) -> (B, C): each query against its own candidate
    rows. The query operand is shaped (B, 1, D) so the contraction is on
    the last dim of both sides, the batched form Mosaic lowers for fp32,
    bf16 and int8 alike."""
    return jax.lax.dot_general(
        q[:, None, :], p, (((2,), (2,)), ((0,), (0,))),
        precision=precision, preferred_element_type=acc)[:, 0, :]


def _kth(bestd, k: int):
    """The running kth distance of a sorted (B, KP) buffer, (B, 1)."""
    slot = jax.lax.broadcasted_iota(jnp.int32, bestd.shape, 1)
    return jnp.max(jnp.where(slot < k, bestd, -jnp.inf), axis=1,
                   keepdims=True)


def _insert_survivors(bestd, besti, d, idx0, thr, k: int):
    """Merge a new block (B, BC) into the sorted running buffer (B, KP):
    the block's candidates with ``d < thr`` (``thr`` (B, 1) at most the
    buffer's kth, so only they can enter the top-k) are inserted in
    ascending (distance, index) order, one pass each, for as many passes
    as the fullest row has survivors, capped at k — a row with more than
    k survivors keeps only its k smallest anyway. A pass extracts the
    row's smallest survivor, places it after every buffer entry at or
    below it (the tie-break law of a stable sort: buffer entries first,
    then lower index, since earlier survivors of the block are in the
    buffer already), and shifts the tail one lane with ``pltpu.roll``.
    A row out of survivors extracts +inf, whose slot is past the buffer:
    its pass changes nothing. Slots at and past k may hold finite
    entries pushed out of the top-k; a slot whose distance is +inf
    carries an arbitrary index (callers map non-finite slots to -1).
    Block candidate ``c`` has index ``idx0 + c``. Returns the buffer and
    the pass count (a scalar)."""
    kp = bestd.shape[1]
    bc = d.shape[1]
    surv = d < thr
    rest = jnp.where(surv, d, jnp.inf)
    # survivor counts as fp32 sums (exact below 2^24); the loop runs the
    # fullest row's count, at most k
    n = jnp.minimum(jnp.max(jnp.sum(jnp.where(surv, 1.0, 0.0), axis=1)),
                    float(k)).astype(jnp.int32)
    pos = jax.lax.broadcasted_iota(jnp.int32, d.shape, 1)
    slot = jax.lax.broadcasted_iota(jnp.int32, bestd.shape, 1)

    def insert(_, carry):
        rest, bd, bi = carry
        m = jnp.min(rest, axis=1, keepdims=True)
        first = jnp.min(jnp.where(rest == m, pos, bc), axis=1,
                        keepdims=True)
        at = jnp.min(jnp.where(bd > m, slot, kp), axis=1, keepdims=True)
        bd = jnp.where(slot < at, bd,
                       jnp.where(slot == at, m, pltpu.roll(bd, 1, 1)))
        bi = jnp.where(slot < at, bi,
                       jnp.where(slot == at, idx0 + first,
                                 pltpu.roll(bi, 1, 1)))
        return jnp.where(pos == first, jnp.inf, rest), bd, bi

    _, bestd, besti = jax.lax.fori_loop(0, n, insert, (rest, bestd, besti))
    return bestd, besti, n


def _init_buffer(bestd_ref, besti_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        bestd_ref[...] = jnp.full_like(bestd_ref, jnp.inf)
        besti_ref[...] = jnp.full_like(besti_ref, -1)


def _kernel(q_ref, p_ref, bestd_ref, besti_ref, *, bn: int, k: int,
            n_real: int):
    j = pl.program_id(1)
    _init_buffer(bestd_ref, besti_ref)
    q = q_ref[...].astype(jnp.float32)          # (BM, D)
    p = p_ref[...].astype(jnp.float32)          # (BN, D)
    qq = jnp.sum(q * q, axis=1, keepdims=True)
    pp = jnp.sum(p * p, axis=1, keepdims=True)
    d = jnp.maximum(qq + pp.T - 2.0 * jax.lax.dot_general(
        q, p, (((1,), (1,)), ((), ())), precision=_HI,
        preferred_element_type=jnp.float32), 0.0)   # (BM, BN)
    idx = (j * bn + jax.lax.broadcasted_iota(jnp.int32, d.shape, 1))
    # padding points must never displace real neighbors
    d = jnp.where(idx < n_real, d, jnp.inf)
    bestd = bestd_ref[...]
    bestd_ref[...], besti_ref[...], _ = _insert_survivors(
        bestd, besti_ref[...], d, j * bn, _kth(bestd, k), k)


@functools.partial(jax.jit, static_argnames=("k", "bm", "bn", "interpret"))
def topk_l2_pallas(q, p, k: int, *, bm: int = 128, bn: int = 512,
                   interpret: bool = False):
    """q: (M, D), p: (N, D) -> (dists (M, k), indices (M, k))."""
    m, d = q.shape
    n = p.shape[0]
    kk = min(k, n)
    kp = _rup(kk, 128)
    q2 = _pad(_pad(q.astype(jnp.float32), 128, 1), bm, 0)
    p2 = _pad(_pad(p.astype(jnp.float32), 128, 1), bn, 0)
    mp, dp = q2.shape
    np_ = p2.shape[0]
    grid = (mp // bm, np_ // bn)
    bestd, besti = pl.pallas_call(
        functools.partial(_kernel, bn=bn, k=kk, n_real=n),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, dp), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, dp), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, kp), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, kp), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((mp, kp), jnp.float32),
            jax.ShapeDtypeStruct((mp, kp), jnp.int32),
        ],
        interpret=interpret,
        name="topk_l2_pallas",
    )(q2, p2)
    bestd = bestd[:m, :kk]
    besti = besti[:m, :kk]
    valid = jnp.isfinite(bestd) & (besti < n)
    bestd = jnp.where(valid, bestd, jnp.inf)
    besti = jnp.where(valid, besti, -1)
    return bestd, besti


# ---------------------------------------------------------------------------
# Row-masked, per-query-candidate variant (hybrid-engine leaf scan)
# ---------------------------------------------------------------------------
def _masked_kernel(q_ref, p_ref, v_ref, kth0_ref, *refs, bc: int, k: int,
                   with_lb: bool):
    lb_ref = refs[0] if with_lb else None
    bestd_ref, besti_ref, work_ref = refs[-3:]
    j = pl.program_id(1)
    _init_buffer(bestd_ref, besti_ref)

    @pl.when(j == 0)
    def _init_work():
        work_ref[...] = jnp.zeros_like(work_ref)

    # the merged top-k can only take a candidate below both the caller's
    # carry kth (lane 0 of the seed block) and the running buffer's kth
    thr = jnp.minimum(kth0_ref[:, 0:1], _kth(bestd_ref[...], k))
    live = v_ref[...] != 0                      # (BG, BC)
    if with_lb:
        # tile early-out: a valid candidate whose squared ball bound is
        # below the threshold is the only thing that can change the
        # merged top-k; blocks with none of those are skipped wholesale
        # (module docstring: ties never displace the stable running
        # buffer or carry, so >= is safe). With no seed, an all-inf
        # buffer (kth = +inf) never refutes a valid candidate, so the
        # first tiles always merge.
        live = live & (lb_ref[...] < thr)

    # a block with no live candidate is skipped with or without bounds,
    # so both variants run the merge under the same predicate shape
    @pl.when(jnp.any(live))
    def _merge():
        q = q_ref[...].astype(jnp.float32)          # (BG, D)
        p = p_ref[...].astype(jnp.float32)          # (BG, BC, D)
        qq = jnp.sum(q * q, axis=1, keepdims=True)  # (BG, 1)
        pp = jnp.sum(p * p, axis=2)                 # (BG, BC)
        d = jnp.maximum(qq + pp - 2.0 * _rowwise_dot(q, p), 0.0)
        # masked rows (bucket padding, filtered-out predicate rows) never
        # win
        d = jnp.where(v_ref[...] != 0, d, jnp.inf)
        bestd_ref[...], besti_ref[...], n = _insert_survivors(
            bestd_ref[...], besti_ref[...], d, j * bc, thr, k)
        # work counters, replicated down the block's rows: lane 0 the
        # grid steps that computed distances, lane 1 their merge passes
        lane = jax.lax.broadcasted_iota(jnp.int32, work_ref.shape, 1)
        work_ref[...] += jnp.where(lane == 0, 1,
                                   jnp.where(lane == 1, n, 0))


def _tpu_bc(bg: int, dp: int, itemsize: int) -> int:
    """Candidate block width on TPU: 512 rows where the (bg, bc, dp)
    candidate block stays within ~2 MiB of VMEM (it is double-buffered
    under a 16 MiB scoped limit), narrowing in 128-row steps at wide
    embeddings (d=768 -> 128)."""
    fit = (2 << 20) // (bg * dp * itemsize * 128) * 128
    return max(128, min(512, fit))


@functools.partial(jax.jit, static_argnames=("k", "bg", "bc", "interpret"))
def topk_l2_masked_pallas(q, p, valid, k: int, *, bg: int = None,
                          bc: int = None, interpret: bool = False,
                          lb2=None, kth0=None):
    """q: (G, D), p: (G, C, D), valid: (G, C) -> (dists (G, k), idx (G, k),
    work (R, 2)).

    Row g of ``p`` is query g's own candidate tile; ``valid`` entries of 0
    (bucket padding / filtered rows) are excluded. Returned squared
    distances are ascending; exhausted slots come back as (inf, -1) and
    indices point into [0, C). ``work`` holds, per group of ``bg`` rows,
    the grid steps that computed distances and the merge passes they
    ran (the ``engine.topk_blocks`` / ``engine.topk_merge_passes``
    counters).

    ``kth0`` (optional, (G,)): each query's kth SQUARED distance of the
    carry the result will be merged into, carry first, with a stable
    top-k (module docstring). Candidates at or above it are left out;
    the merged result is the same as without it. None (or +inf) gives
    the exact top-k of the candidates.

    ``lb2`` (optional, (G, C)): per-candidate SQUARED lower bounds for
    the tile early-out (module docstring) — grid steps whose valid
    candidates are all bound-refuted skip the distance + merge body.
    Purely a work-skipping hint: results are identical with and without
    it, and bounds may be conservative (0 disables the skip for that
    candidate).

    Block defaults are backend-dependent: on TPU small VMEM-safe tiles
    (bg=8, bc from ``_tpu_bc``: 512 rows at D=128, 128 at D=768); in
    interpret mode the per-grid-step overhead dominates everything
    else, so tiles grow to cover the whole problem (bounded at
    bc=16384) and the 128-lane padding of D is skipped — this is what
    makes the CPU serving path competitive. The running buffer is
    always k padded to a lane multiple.
    """
    g, _ = q.shape
    c = p.shape[1]
    kk = max(1, min(k, c))
    kp = _rup(kk, 128)
    dpad = 8 if interpret else 128
    dp = _rup(q.shape[1], dpad)
    if bg is None:
        bg = min(64, _rup(g, 8)) if interpret else 8
    if bc is None:
        bc = (min(16384, _rup(c, 128)) if interpret
              else _tpu_bc(bg, dp, 4))
    q2 = _pad(_pad(q.astype(jnp.float32), dpad, 1), bg, 0)
    p2 = _pad(_pad(_pad(p.astype(jnp.float32), dpad, 2), bc, 1), bg, 0)
    v2 = _pad(_pad(valid.astype(jnp.int32), bc, 1), bg, 0)
    # the seed, one lane-wide row per query (padding rows are all-invalid)
    seed = (jnp.full((g,), jnp.inf, jnp.float32) if kth0 is None
            else kth0.astype(jnp.float32))
    k2 = _pad(jnp.broadcast_to(seed[:, None], (g, 128)), bg, 0)
    gp = q2.shape[0]
    cp = p2.shape[1]
    grid = (gp // bg, cp // bc)
    in_specs = [
        pl.BlockSpec((bg, dp), lambda i, j: (i, 0)),
        pl.BlockSpec((bg, bc, dp), lambda i, j: (i, j, 0)),
        pl.BlockSpec((bg, bc), lambda i, j: (i, j)),
        pl.BlockSpec((bg, 128), lambda i, j: (i, 0)),
    ]
    operands = [q2, p2, v2, k2]
    if lb2 is not None:
        # pad columns carry +inf bounds (can never force a merge)
        operands.append(_pad(_pad(lb2.astype(jnp.float32), bc, 1,
                                  value=jnp.inf), bg, 0))
        in_specs.append(pl.BlockSpec((bg, bc), lambda i, j: (i, j)))
    bestd, besti, work = pl.pallas_call(
        functools.partial(_masked_kernel, bc=bc, k=kk,
                          with_lb=lb2 is not None),
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((bg, kp), lambda i, j: (i, 0)),
            pl.BlockSpec((bg, kp), lambda i, j: (i, 0)),
            pl.BlockSpec((bg, 128), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((gp, kp), jnp.float32),
            jax.ShapeDtypeStruct((gp, kp), jnp.int32),
            jax.ShapeDtypeStruct((gp, 128), jnp.int32),
        ],
        interpret=interpret,
        name="topk_l2_masked_pallas",
    )(*operands)
    bestd = bestd[:g, :kk]
    besti = jnp.where(jnp.isfinite(bestd), besti[:g, :kk], -1)
    if kk < k:  # fewer candidates than k: pad to the requested width
        bestd = jnp.pad(bestd, ((0, 0), (0, k - kk)),
                        constant_values=jnp.inf)
        besti = jnp.pad(besti, ((0, 0), (0, k - kk)), constant_values=-1)
    return bestd, besti, work.reshape(gp // bg, bg, 128)[:, 0, :2]


# ---------------------------------------------------------------------------
# Mixed-precision candidate scan: widened lower bounds from int8/bf16 tiles
# ---------------------------------------------------------------------------
def _quant_lb2_kernel(qc_ref, qm_ref, c_ref, cs_ref, cp_ref, ce_ref, v_ref,
                      o_ref, *, precision: str):
    from repro.utils.quant import SLACK_ABS, SLACK_MAG, SLACK_REL
    qm = qm_ref[...]                            # (BG, pad): sq, qqq, qeps
    sq = qm[:, 0:1]
    qqq = qm[:, 1:2]
    qeps = qm[:, 2:3]
    cp = cp_ref[...]                            # (BG, BC) exact deq norms^2
    if precision == "int8":
        # int8 x int8 -> int32 cross terms are EXACT (|sum| < 2^24), so
        # the only error sources are the quantization itself (covered by
        # qeps/ceps) and the fp32 expansion (covered by the slack)
        cross = _rowwise_dot(qc_ref[...], c_ref[...], acc=jnp.int32,
                             precision=None).astype(jnp.float32)
        d2h = qqq + cp - (2.0 * sq * cs_ref[...]) * cross
    else:
        # bf16 x bf16 products are exact in the fp32 accumulator
        cross = _rowwise_dot(qc_ref[...], c_ref[...], precision=None)
        d2h = qqq + cp - 2.0 * cross
    d2h = jnp.maximum(d2h, 0.0)
    dhat = jnp.sqrt(d2h)
    mag = jnp.maximum(qqq + cp, 0.0)
    slack = SLACK_ABS + SLACK_REL * dhat + SLACK_MAG * jnp.sqrt(mag)
    lbr = jnp.maximum(dhat - (qeps + ce_ref[...]) - slack, 0.0)
    o_ref[...] = jnp.where(v_ref[...] != 0, lbr * lbr, jnp.inf)


@functools.partial(jax.jit,
                   static_argnames=("precision", "bg", "bc", "interpret"))
def quant_lb2_pallas(q, codes, cscale, cppq, ceps, valid, *, precision: str,
                     bg: int = None, bc: int = None,
                     interpret: bool = False):
    """Widened squared lower bounds, semantics of ``ref.quant_lb2``.

    q: (G, D) fp32 raw queries (quantized here, outside the grid);
    codes: (G, C, D) int8/bf16 candidate tiles; cscale/cppq/ceps: (G, C)
    fp32 per-candidate scale / exact dequantized norm^2 / row error
    bound; valid: (G, C). Returns (G, C) fp32 — +inf where invalid.
    """
    from repro.utils.quant import quantize_query
    g, _ = q.shape
    c = codes.shape[1]
    qcast, qscale, qqq, qeps = quantize_query(q, precision)
    qmeta = jnp.stack([qscale, qqq, qeps], axis=1)      # (G, 3)

    dpad = 8 if interpret else 128
    if bg is None:
        bg = min(64, _rup(g, 8)) if interpret else 8
    if bc is None:
        bc = (min(16384, _rup(c, 128)) if interpret
              else _tpu_bc(bg, _rup(codes.shape[2], dpad),
                           codes.dtype.itemsize))
    qc2 = _pad(_pad(qcast, dpad, 1), bg, 0)
    qm2 = _pad(_pad(qmeta.astype(jnp.float32), dpad, 1), bg, 0)
    c2 = _pad(_pad(_pad(codes, dpad, 2), bc, 1), bg, 0)
    cs2 = _pad(_pad(cscale.astype(jnp.float32), bc, 1), bg, 0)
    cp2 = _pad(_pad(cppq.astype(jnp.float32), bc, 1), bg, 0)
    ce2 = _pad(_pad(ceps.astype(jnp.float32), bc, 1), bg, 0)
    v2 = _pad(_pad(valid.astype(jnp.int32), bc, 1), bg, 0)
    gp, dp = qc2.shape
    cp_ = c2.shape[1]
    grid = (gp // bg, cp_ // bc)
    out = pl.pallas_call(
        functools.partial(_quant_lb2_kernel, precision=precision),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bg, dp), lambda i, j: (i, 0)),
            pl.BlockSpec((bg, qm2.shape[1]), lambda i, j: (i, 0)),
            pl.BlockSpec((bg, bc, dp), lambda i, j: (i, j, 0)),
            pl.BlockSpec((bg, bc), lambda i, j: (i, j)),
            pl.BlockSpec((bg, bc), lambda i, j: (i, j)),
            pl.BlockSpec((bg, bc), lambda i, j: (i, j)),
            pl.BlockSpec((bg, bc), lambda i, j: (i, j)),
        ],
        out_specs=[pl.BlockSpec((bg, bc), lambda i, j: (i, j))],
        out_shape=[jax.ShapeDtypeStruct((gp, cp_), jnp.float32)],
        interpret=interpret,
        name="quant_lb2_pallas",
    )(qc2, qm2, c2, cs2, cp2, ce2, v2)[0]
    return out[:g, :c]