"""Serving engine: batched prefill + decode, plus the retrieval-serving
path (embed request texts -> MQRLD hybrid queries).

Straggler/fault posture: requests are grouped into same-length batches
(no padding; one compiled program per (length, count) shape), decode runs
a fixed-length jitted loop per batch, and the engine is stateless between
batches — a replacement worker resumes from the request queue with no
handoff.

``RetrievalServer`` is the retrieval half of a production deployment: a
dynamic micro-batching admission queue in front of the platform's
planned path (``MQRLD.session().plan(...).execute()``). Requests are
keyed by their plan *signature* (``Session.signature``) and compatible
archetypes are coalesced into one micro-batch, so a warm ``LogicalPlan``
and its compiled-shape universe are reused across requests instead of
re-traced per accidental FIFO mixture. The queue is bounded
(backpressure executes oldest work to make room), deadline-expired
requests are shed BEFORE compute with an explicit ``shed`` result, and
per-archetype service times feed back into the QBS table — the same
query-aware loop that seeds KNN beam widths, applied to admission
control.
"""
from __future__ import annotations

import math
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import query as Q
from repro.models import build_model
from repro.serve.pipeline import ChunkPipeline
from repro.utils.spans import Recorder, recording, span

# Bound on the signature memo in RetrievalServer: keys are predicate
# archetype strings (constants elided), so the live population is the
# number of distinct query SHAPES served — small in practice; the cap
# is a leak backstop, not a working-set tune. FIFO eviction suffices
# because recompute-on-miss is cheap (one normalize + signature walk).
_SIG_CACHE_MAX = 1024


@dataclass
class GenRequest:
    prompt: np.ndarray         # (S,) int32
    max_new: int = 16


@dataclass
class GenResult:
    tokens: np.ndarray
    prefill_s: float
    decode_s: float


class ServeEngine:
    """Batched greedy generation, exact under mixed prompt lengths.

    Batching contract: ``generate`` buckets requests by PROMPT LENGTH
    and runs each bucket as a padding-free batch (chunked to
    ``batch_size``), then returns results in request order. Bucketing —
    not padding — is what keeps batched generation token-identical to
    per-request generation for every model family here: ``prefill``
    returns logits for the LAST position only and every ``KVCache``
    carries one scalar ``length``, so a right-padded short prompt would
    take its first greedy token from a pad position and decode against
    pad K/V at wrong positions, and left-padding would shift RoPE
    phases. Within a same-length batch both hazards vanish. Batches are
    sized to the requests present — no phantom zero rows padded up to
    ``batch_size``.
    """

    def __init__(self, cfg: ModelConfig, params=None, *, mesh=None,
                 rules=None, max_len: int = 512, batch_size: int = 8,
                 seed: int = 0):
        self.cfg = cfg
        self.model = build_model(cfg, rules, mesh)
        self.params = params if params is not None \
            else self.model.init(jax.random.PRNGKey(seed))
        self.max_len = max_len
        self.batch_size = batch_size
        self._decode_jit = jax.jit(self.model.decode)

    def _greedy(self, logits) -> jnp.ndarray:
        # mask padded vocab columns before argmax
        v = self.cfg.vocab_size
        lg = logits[..., :v] if logits.shape[-1] > v else logits
        return jnp.argmax(lg, axis=-1).astype(jnp.int32)

    def generate(self, requests: Sequence[GenRequest]) -> List[GenResult]:
        out: List[Optional[GenResult]] = [None] * len(requests)
        by_len: Dict[int, List[int]] = {}
        for i, r in enumerate(requests):
            by_len.setdefault(len(r.prompt), []).append(i)
        for plen in sorted(by_len):
            idx = by_len[plen]
            for j in range(0, len(idx), self.batch_size):
                sel = idx[j:j + self.batch_size]
                for i, res in zip(sel, self._run_batch(
                        [requests[i] for i in sel])):
                    out[i] = res
        return out  # type: ignore[return-value]

    def _run_batch(self, reqs: Sequence[GenRequest]) -> List[GenResult]:
        plen = len(reqs[0].prompt)
        assert all(len(r.prompt) == plen for r in reqs), \
            "_run_batch requires same-length prompts (generate buckets)"
        toks = np.stack([np.asarray(r.prompt, np.int32) for r in reqs])
        max_new = max(r.max_new for r in reqs)

        t0 = time.time()
        batch = {"tokens": jnp.asarray(toks)}
        if self.cfg.is_encdec:
            batch["frames"] = jnp.zeros(
                (len(reqs), self.cfg.frontend_tokens, self.cfg.d_model),
                jnp.dtype(self.cfg.dtype))
        logits, cache = self.model.prefill(self.params, batch, self.max_len)
        # SSM/plain-transformer prefill returns a filled cache; hymba and
        # enc-dec caches are populated by replaying the prompt through the
        # (ring-buffered / cross-cached) decode path
        if getattr(cache, "length", None) is not None \
                and int(np.asarray(cache.length)) == 0:
            for t in range(plen):
                _, cache = self._decode_jit(self.params, cache,
                                            jnp.asarray(toks[:, t:t + 1]))
        prefill_s = time.time() - t0

        t1 = time.time()
        # every row's position -1 is its true last prompt token
        cur = self._greedy(logits[:, -1])[:, None]
        gen = [np.asarray(cur)]
        for _ in range(max_new - 1):
            logits, cache = self._decode_jit(self.params, cache, cur)
            cur = self._greedy(logits[:, -1])[:, None]
            gen.append(np.asarray(cur))
        decode_s = time.time() - t1
        gen_arr = np.concatenate(gen, axis=1)
        return [GenResult(tokens=gen_arr[i, :reqs[i].max_new],
                          prefill_s=prefill_s, decode_s=decode_s)
                for i in range(len(reqs))]


class EmbeddingServer:
    """Embeds token batches with any pool architecture — feeds the MQRLD
    platform's vector columns."""

    def __init__(self, cfg: ModelConfig, params=None, *, mesh=None,
                 rules=None, seed: int = 0):
        self.cfg = cfg
        self.model = build_model(cfg, rules, mesh)
        self.params = params if params is not None \
            else self.model.init(jax.random.PRNGKey(seed))
        self._embed_jit = jax.jit(self.model.embedding)

    def embed(self, tokens: np.ndarray) -> np.ndarray:
        batch = {"tokens": jnp.asarray(tokens, jnp.int32)}
        if self.cfg.is_encdec:
            batch["frames"] = jnp.zeros(
                (len(tokens), self.cfg.frontend_tokens, self.cfg.d_model),
                jnp.dtype(self.cfg.dtype))
        return np.asarray(self._embed_jit(self.params, batch))


# ---------------------------------------------------------------------------
# Retrieval serving: embedder -> hybrid engine
# ---------------------------------------------------------------------------
@dataclass
class RetrievalRequest:
    tokens: np.ndarray                   # (S,) int32 prompt tokens
    attr: str                            # vector column to search
    k: int = 10
    predicate: Optional[Q.Query] = None  # VK-free filter tree, And-ed in
    # latency budget relative to ARRIVAL (submit time). None = no
    # deadline. A request whose deadline passes — or provably cannot be
    # met even if its archetype started compute right now, per QBS
    # service-time stats — is shed before compute: its future resolves
    # to a RetrievalResult with ``shed=True`` and empty rows, never a
    # silent drop.
    deadline_ms: Optional[float] = None


@dataclass
class RetrievalResult:
    rows: np.ndarray                     # result row ids (distance order)
    query: Optional[Q.Query] = None      # the MOAPI query that was run
    #                                      (None when the request was shed
    #                                      before its embedding existed)
    shed: bool = False                   # True = deadline shed, no compute
    latency_s: float = 0.0               # end-to-end: arrival -> resolve


class RetrievalFuture:
    """Handle for one submitted retrieval request. ``result()`` blocks
    only in the sense that it flushes the server's pending batch when
    this request has not run yet — execution is synchronous batched
    compute, not threads; the future exists so callers can enqueue
    requests as they arrive and let the server pick the batch boundary.

    A future resolves exactly once: either with real rows when its
    micro-batch executes, or with a ``shed=True`` result when its
    deadline expires first. Once resolved it is immutable — a failed
    later batch can never re-set it."""

    def __init__(self, server: "RetrievalServer"):
        self._server = server
        self._result: Optional[RetrievalResult] = None
        self._done = False

    def done(self) -> bool:
        return self._done

    def result(self) -> RetrievalResult:
        if not self._done:
            self._server.flush()
        if not self._done or self._result is None:
            raise RuntimeError(
                "retrieval future did not resolve: its batch failed "
                "before results were set (the request is still pending "
                "and will be retried by the next flush)")
        return self._result

    def _set(self, res: RetrievalResult):
        if self._done:       # resolved futures are immutable (see
            return           # _run_chunk's all-or-nothing contract)
        self._result = res
        self._done = True


@dataclass
class _Pending:
    """One admitted request: queue entry + admission-time bookkeeping."""
    req: RetrievalRequest
    fut: RetrievalFuture
    sig: str                             # plan signature (coalescing key)
    t_submit: float                      # arrival time (server clock)
    deadline: Optional[float]            # absolute, server clock; None=∞


_E2E_KEEP = 2048  # recent end-to-end latencies kept per signature


class RetrievalServer:
    """Dynamic micro-batching retrieval server over a prepared ``MQRLD``
    platform, running on the MOAPI v2 planned path.

    Each executed micro-batch is two compiled stages: embedding forward
    passes (bucketed by prompt length — padding-free, so a request's
    embedding is independent of which batch it lands in), then one
    ``Session.plan(...).execute()`` for all queries. Requests are
    admitted into a bounded FIFO queue and carved into micro-batches by
    PLAN SIGNATURE (``coalesce=True``, the default): all requests of one
    micro-batch share an archetype, so the session's warm ``LogicalPlan``
    and the engine's compiled shapes are reused instead of re-traced for
    every accidental mixture of shapes — micro-batch sizes are quantized
    to powers of two (capped at ``batch_size``) to bound the compiled
    shape universe to |signatures| x log2(batch_size). ``coalesce=False``
    restores the legacy strict-FIFO ``batch_size`` chunking.

    Admission control: the queue holds at most ``max_queue`` requests
    (default ``64 * batch_size``); ``submit`` under a full queue first
    EXECUTES oldest work to make room (backpressure — the caller pays
    the flush latency, requests are never dropped by the bound).
    Requests carrying ``deadline_ms`` are shed before compute once their
    deadline passes — and predictively, when the QBS service-time stats
    for their archetype (>= 8 samples) say even an immediate start
    cannot meet the deadline. A shed future resolves to an explicit
    ``RetrievalResult(shed=True)``; shedding is never a silent drop.
    Open-arrival drive loops use ``poll()``/``next_due()`` instead of
    ``flush_one``: ``max_delay_ms`` is the batching window a partial
    micro-batch may wait for archetype-mates before running anyway
    (full groups, full queues, and imminent deadlines run immediately;
    0 = eager). With ``adaptive_window=True`` the window is derived PER
    SIGNATURE from the QBS service-time stats instead of the one static
    knob: a signature whose p50 service time is known (>= 8 samples)
    waits at most one full-batch service time (``p50 * batch_size``) —
    waiting longer than it would take to serve a full batch can only
    add latency, never amortization — capped by ``max_delay_ms`` when
    that is set (> 0). Cold signatures fall back to the static window.

    Query-aware feedback: every executed micro-batch records its
    per-request service time under its plan signature via
    ``QBSTable.record_latency`` — consumed by the predictive shed above
    and by ``ExecutablePlan.explain()``'s per-fragment latency block.
    ``stats()`` reports served/shed/batch counters, per-signature
    end-to-end p50/p99, and the spans and counters of recent chunks
    (``trace``; the server's ``recorder`` is active while a chunk runs,
    so the planner's and engine's spans land there too).

    Ordering contract: ``serve`` returns one ``RetrievalResult`` per
    request, POSITIONALLY in submission order, and a future always
    resolves to its own request's result — regardless of how coalescing
    reorders execution across micro-batches, how the planner groups or
    scalar-fallbacks queries inside a batch, or how many requests were
    shed in between. What coalescing may change is only WHEN an admitted
    request executes, never its result: embeddings are padding-free and
    the planned path is exact, so each served result is identical to
    serving the request alone. Within each result, rows are ALWAYS
    distance-ordered: the planned path returns filtered-KNN (And)
    results as ascending row ids, so the server re-ranks them by
    distance to the request embedding before returning.

    Retry contract: ``_run_chunk`` is all-or-nothing. Results for the
    whole micro-batch are embedded, executed, and ranked BEFORE any
    future is resolved or any queue entry removed; if the embedder, the
    engine, or the ranking gather raises, the exception propagates with
    every one of the chunk's requests still pending and every one of its
    futures unresolved — the next ``flush()`` retries them. A failed
    chunk therefore can never re-execute or re-resolve a request that an
    earlier chunk already resolved (resolved futures are immutable).

    ``project`` maps the embedder's output onto the searched vector
    column's space (identity by default). ``device_loop`` picks the
    engine's KNN beam-loop implementation (True = on-device, the serving
    default); ``shards`` (None = the platform's ``default_shards``)
    serves through the T-sharded multi-device path; ``precision``
    selects the mixed-precision tile scan (rows identical to fp32).
    ``clock`` injects the monotonic time source (tests and the load
    harness pass a controllable clock; deadlines, latency accounting and
    QBS service times all read it).

    ``append(...)`` ingests new rows between micro-batches
    (freshness-exact; see its docstring for the ordering and
    exception-safety contract).

    Pipelined execution (``pipeline_depth``): depth 1 (the default) is
    the serial loop above, byte-identical to the pre-pipeline server.
    Depth >= 2 runs chunks through ``repro.serve.pipeline
    .ChunkPipeline`` — embed/stage, async device dispatch, and the
    rank/record epilogue become overlapping stages with up to ``depth``
    chunks in flight, for a sustained-QPS gain at identical per-request
    rows (each chunk's results are exactly the serial loop's; only WHEN
    futures resolve shifts — a full-group auto-flush dispatches without
    retiring, and ``poll``/``flush``/``result()`` retire in FIFO
    order). Every serial contract carries over: in-order resolution per
    request, all-or-nothing chunk failure with retryable futures,
    bounded admission (backpressure retires in-flight work), and
    deadline shedding (in-flight chunks are no longer sheddable — they
    are already computing). ``drain()`` is the explicit quiescent
    barrier; ``append`` drains first, and reopt steps run only when the
    pipe is empty, so generation swaps still land between micro-batches.

    Online re-optimization: ``attach_reopt(controller)`` hands the
    server a ``repro.core.reopt.ReoptController``; ``poll()`` then
    drives one ``controller.step()`` at every idle point and after
    every executed micro-batch — the cooperative-scheduling contract
    the controller's module doc describes. Index-generation swaps
    therefore land exactly BETWEEN micro-batches, under the same
    ordering contract as ``append``: futures already resolved are
    immutable, requests still pending execute against the new
    generation at their flush epoch, and every served result stays
    oracle-exact across the swap (results are compared by logical row
    identity — ``platform.view().row_ids`` — since a new generation
    re-permutes physical layout). ``stats()`` reports the serving
    generation / build id and, when a controller is attached, its
    progress (``ReoptController.status()``).
    """

    def __init__(self, platform, embedder: EmbeddingServer, *,
                 batch_size: int = 64, pad_token: int = 0,
                 project=None, device_loop: bool = True,
                 shards: Optional[int] = None,
                 precision: Optional[str] = None,
                 coalesce: bool = True,
                 max_queue: Optional[int] = None,
                 max_delay_ms: float = 0.0,
                 adaptive_window: bool = False,
                 pipeline_depth: int = 1,
                 clock: Callable[[], float] = time.monotonic):
        self.platform = platform
        self.embedder = embedder
        self.batch_size = batch_size
        self.pad_token = pad_token   # kept for API compat; prompts are
        #                              no longer padded (length buckets)
        self.project = project
        self.device_loop = device_loop
        self.shards = shards
        # mixed-precision tile scan (None = platform default): results
        # are row-identical to fp32, only the scan cost changes
        self.precision = precision
        self.coalesce = coalesce
        # batching window for poll(): how long a lone request may wait
        # for archetype-mates before a partial micro-batch runs anyway.
        # 0 = eager (poll == flush_one); the open-arrival drive loop
        # sets ~one full-batch service time — without a window, trickle
        # arrivals execute as size-1 chunks and throughput collapses to
        # the per-chunk overhead floor
        self.max_delay_ms = float(max_delay_ms)
        # per-signature window from QBS service stats (see class doc);
        # max_delay_ms becomes the cap rather than the window itself
        self.adaptive_window = bool(adaptive_window)
        self.max_queue = max_queue if max_queue is not None \
            else 64 * batch_size
        if self.max_queue < 1:
            raise ValueError("max_queue must be >= 1")
        self._clock = clock
        self.session = platform.session(device_loop=device_loop,
                                        shards=shards,
                                        precision=precision)
        self._pending: List[_Pending] = []   # admission FIFO
        self._sig_cache: Dict[Tuple, str] = {}
        self.reopt = None                    # see attach_reopt()
        # pipelined executor (class doc): depth 1 = the serial loop
        # (no pipeline object at all — the pre-pipeline code path,
        # byte-identical); depth >= 2 overlaps chunk stages
        self.pipeline_depth = int(pipeline_depth)
        if self.pipeline_depth < 1:
            raise ValueError("pipeline_depth must be >= 1")
        self._pipe = ChunkPipeline(self, self.pipeline_depth) \
            if self.pipeline_depth > 1 else None
        self._inflight_ids: set = set()      # id(_Pending) of dispatched
        # serving counters + per-signature end-to-end latencies
        self.n_submitted = 0
        self.n_served = 0
        self.n_shed = 0
        self.n_batches = 0
        self._e2e: Dict[str, List[float]] = {}
        # spans and counters of the chunks this server ran (stats()["trace"])
        self.recorder = Recorder()

    # ------------------------------------------------------------ embedding
    def _embed_tokens(self, token_lists: Sequence[np.ndarray]) -> np.ndarray:
        """THE prompt -> vector recipe — shared by query serving and
        ``append`` so ingested embeddings always live in the same space
        queries search. Prompts are bucketed by length into padding-free
        forward passes (one per distinct length), so an embedding
        depends only on the prompt itself — never on the longest
        neighbor that happened to share its batch. That invariance is
        what makes coalesced serving exact: moving a request between
        micro-batches cannot change its embedding, hence its result."""
        lens = [len(t) for t in token_lists]
        out: List[Optional[np.ndarray]] = [None] * len(token_lists)
        for plen in sorted(set(lens)):
            idx = [i for i, n in enumerate(lens) if n == plen]
            toks = np.stack([np.asarray(token_lists[i], np.int32)
                             for i in idx])
            emb = self.embedder.embed(toks)
            if self.project is not None:
                emb = np.asarray(self.project(emb))
            for j, i in enumerate(idx):
                out[i] = np.asarray(emb[j])
        return np.stack(out)  # type: ignore[arg-type]

    def _queries(self, reqs: Sequence[RetrievalRequest],
                 emb: np.ndarray) -> List[Q.Query]:
        out = []
        for r, e in zip(reqs, emb):
            vk = Q.VK.of(r.attr, e, r.k)
            out.append(vk if r.predicate is None
                       else Q.And.of(r.predicate, vk))
        return out

    def _ranked(self, req: RetrievalRequest, emb: np.ndarray,
                rows: np.ndarray) -> np.ndarray:
        if req.predicate is None or len(rows) == 0:
            return rows  # top-level V.K is already distance-ordered
        # view(): row ids may point into the un-folded delta region
        col = self.platform.view().vector[req.attr][rows]
        d2 = ((col - emb[None, :]) ** 2).sum(1)
        return rows[np.argsort(d2, kind="stable")]

    def signature(self, request: RetrievalRequest) -> str:
        """The plan signature this request coalesces under — computed
        WITHOUT its embedding (signatures elide vector constants, so a
        placeholder vector signs identically; see
        ``Session.signature``).

        Cached per (attr, k, predicate SIGNATURE) with a FIFO bound.
        The key must be the predicate's archetype string, not the live
        predicate object: per-request predicate trees differ in their
        constants, so object keys never hit AND pin every predicate
        ever served in memory — the unbounded-leak/zero-hit bug this
        replaces. Signatures elide exactly those constants, so two
        predicates with equal signatures produce the identical
        combined-query signature — the string key loses nothing. The
        bound only evicts memoized strings; a miss recomputes."""
        pred_sig = None if request.predicate is None \
            else Q.signature(Q.normalize(request.predicate))
        key = (request.attr, int(request.k), pred_sig)
        sig = self._sig_cache.get(key)
        if sig is None:
            vk = Q.VK.of(request.attr, (), int(request.k))
            q = vk if request.predicate is None \
                else Q.And.of(request.predicate, vk)
            sig = self.session.signature(q)
            if len(self._sig_cache) >= _SIG_CACHE_MAX:
                self._sig_cache.pop(next(iter(self._sig_cache)))
            self._sig_cache[key] = sig
        return sig

    # ------------------------------------------------------------- writes
    def append(self, *, numeric=None, vectors=None, tokens=None,
               attr: Optional[str] = None,
               raw_uri: Optional[Sequence[str]] = None,
               fold: Optional[bool] = None) -> int:
        """Ingest new MMOs into the serving platform without taking
        queries offline (the platform's freshness-exact delta region).

        ``vectors`` supplies embedding columns directly; ``tokens`` (a
        list of int32 prompt arrays) is embedded through the server's
        embedder — bucketed and projected exactly like query prompts —
        into the ``attr`` vector column. Returns the number of live
        (un-folded) delta rows; ``fold`` is forwarded to
        ``MQRLD.append`` (None = the platform's auto-fold policy).

        Ordering / concurrency contract: the append is applied
        atomically BETWEEN micro-batches. Futures already resolved are
        immutable; requests still pending — including those submitted
        before this call — observe the appended rows when their
        micro-batch flushes (freshness-exact: every executed batch
        queries base+delta at its flush epoch). There is no state in
        which an in-flight batch sees a half-applied append, because
        execution is synchronous batched compute and ``MQRLD.append``
        validates the whole batch of rows before mutating the region.

        Exception safety: embedding or validation failures propagate
        WITHOUT touching the platform, the pending queue, or any
        future — the next ``flush()`` serves exactly what it would
        have served before the failed call.

        Pipelined mode first ``drain()``s every in-flight chunk, so
        the append still lands at a quiescent boundary: chunks
        dispatched before this call resolve against the pre-append
        state they were planned on, requests still queued observe the
        appended rows at their flush epoch — the serial contract,
        unchanged."""
        self.drain()
        vectors = dict(vectors or {})
        if tokens is not None:
            if attr is None:
                raise ValueError("append(tokens=...) needs attr=")
            vectors[attr] = self._embed_tokens(tokens)
        return self.platform.append(numeric=numeric, vector=vectors,
                                    raw_uri=raw_uri, fold=fold)

    # ------------------------------------------------------------- async
    @property
    def queue_depth(self) -> int:
        return len(self._pending) + len(self._inflight_ids)

    @property
    def inflight_chunks(self) -> int:
        """Chunks currently dispatched in the pipeline (0 in serial
        mode). Their requests still count in ``queue_depth`` until
        their epilogue retires them."""
        return 0 if self._pipe is None else self._pipe.inflight

    def _pickable(self) -> List[_Pending]:
        """Pending entries NOT currently in a dispatched chunk — what
        shedding, chunk picking, and due/window checks operate on.
        Dispatched entries are REMOVED from ``_pending`` by
        ``_mark_inflight`` (and re-queued on chunk failure), so this is
        always the whole queue with no per-call filtering — the
        pipelined scheduler's scans cost exactly what the serial
        loop's do."""
        return self._pending

    def _mark_inflight(self, chunk: Sequence[_Pending]) -> None:
        """Move a dispatched chunk's entries out of the pending queue
        (one O(queue) rebuild per chunk — the same cost the serial
        epilogue's dequeue pays) into the in-flight id set."""
        ids = set(map(id, chunk))
        self._inflight_ids |= ids
        self._pending = [p for p in self._pending if id(p) not in ids]

    def _unmark_inflight(self, chunk: Sequence[_Pending], *,
                         requeue: bool = False) -> None:
        """Drop a chunk from the in-flight set. ``requeue=True``
        (chunk FAILED before its mutation point) re-inserts its entries
        at the FRONT of the pending queue — they are the oldest work,
        so FIFO order is preserved and the next flush retries them."""
        self._inflight_ids.difference_update(map(id, chunk))
        if requeue:
            self._pending[:0] = chunk

    def drain(self) -> int:
        """Pipeline barrier: materialize every in-flight chunk
        (resolving its futures) WITHOUT dispatching new work. No-op in
        serial mode. After ``drain()`` no chunk state remains on the
        device, so ``append()`` and a reopt ``swap()`` happen at the
        same quiescent between-micro-batches boundary the serial loop
        guarantees. Returns requests served by the drain."""
        if self._pipe is None:
            return 0
        return self._pipe.drain()

    def submit(self, request: RetrievalRequest, *,
               now: Optional[float] = None) -> RetrievalFuture:
        """Admit one request; returns its future. ``now`` overrides the
        arrival timestamp (server clock) — trace replay uses it so
        recorded latencies measure from true arrival, not from when the
        replay loop got around to submitting.

        Auto-flush: with coalescing, a micro-batch runs as soon as some
        signature has ``batch_size`` requests queued; legacy FIFO mode
        runs once ``batch_size`` total are queued. Backpressure: when
        the queue is at ``max_queue``, oldest work is executed (not
        dropped) until the new request fits."""
        t = self._clock() if now is None else now
        self._shed_expired(t)
        while self.queue_depth >= self.max_queue:
            # backpressure: execute, never drop
            self.flush_one(reason="backpressure")
        fut = RetrievalFuture(self)
        dl = None if request.deadline_ms is None \
            else t + float(request.deadline_ms) / 1e3
        self._pending.append(_Pending(
            req=request, fut=fut, sig=self.signature(request),
            t_submit=t, deadline=dl))
        self.n_submitted += 1
        if self.coalesce:
            counts: Dict[str, int] = {}
            for p in self._pickable():
                counts[p.sig] = counts.get(p.sig, 0) + 1
            if any(c >= self.batch_size for c in counts.values()):
                self._autoflush()
        elif len(self._pickable()) >= self.batch_size:
            self._autoflush()
        return fut

    def _autoflush(self) -> None:
        """A full micro-batch exists at submit time. Serial mode runs
        it to completion (``flush_one``). Pipelined mode only
        DISPATCHES it (retiring first when the pipe is full): the
        submit path pays embed+enqueue, the device computes in the
        background, and the epilogue lands on a later
        ``poll``/``flush`` — this is where the overlap engages under
        sustained load."""
        if self._pipe is None:
            self.flush_one(reason="full")
            return
        if self._pipe.inflight >= self._pipe.depth:
            self._pipe.retire()
        self._pipe.dispatch(self._next_chunk(), "full")

    def result(self, future: RetrievalFuture) -> RetrievalResult:
        """Resolve a future (flushing pending work if needed)."""
        return future.result()

    def flush(self):
        """Run every pending request, one micro-batch at a time. A
        chunk is dequeued only after it executed (see the class retry
        contract): on a raise, the failed chunk's requests stay pending
        and the next flush retries them. Pipelined mode keeps filling
        free stage slots and retiring FIFO until both the queue and the
        pipe are empty."""
        if self._pipe is not None:
            while True:
                self._shed_expired(self._clock())
                if self._pipe.inflight >= self._pipe.depth:
                    self._pipe.retire()
                elif self._pickable():
                    self._pipe.dispatch(self._next_chunk(), "flush")
                elif self._pipe.inflight:
                    self._pipe.retire()
                else:
                    return
        while self._pending:
            self.flush_one()

    def flush_one(self, reason: str = "flush") -> int:
        """Shed expired work, then execute ONE micro-batch (the chunk
        ``_next_chunk`` picks), regardless of the batching window.
        Returns the number of requests served (0 when shedding emptied
        the queue). Pipelined mode dispatches one chunk when a stage
        slot is free, then retires the oldest in-flight chunk — one
        call still makes one chunk's worth of progress, so the
        backpressure loop in ``submit`` shrinks the queue each call.
        ``reason`` is counted as why the chunk ran (``stats()``)."""
        self._shed_expired(self._clock())
        if self._pipe is not None:
            if self._pickable() and \
                    self._pipe.inflight < self._pipe.depth:
                self._pipe.dispatch(self._next_chunk(), reason)
            return self._pipe.retire()
        if not self._pending:
            return 0
        chunk = self._next_chunk()
        self._run_chunk(chunk, reason)
        return len(chunk)

    def poll(self) -> int:
        """Window-respecting variant of ``flush_one`` for open-arrival
        drive loops: sheds expired work, then runs one micro-batch only
        if one is DUE — a signature group (or the whole queue) reached
        ``batch_size``, some admitted request has waited out its
        signature's batching window, or some deadline would expire
        within it. Returns requests served this call (0 = nothing due
        yet; see ``next_due`` for when to come back).

        When a re-optimization controller is attached, every ``poll``
        also drives one ``controller.step()`` — after the micro-batch
        when one ran (the swap-safe boundary), otherwise at the idle
        point — so background tuning, beside-builds, and generation
        swaps make progress exactly when the serving loop has slack.

        Pipelined mode: fill free stage slots with due chunks, then
        retire the oldest in-flight chunk — the host's dispatch work
        (embed/stage) for new chunks runs while the device computes the
        chunks already enqueued. Reopt steps (and shape prewarming)
        only run when the pipe is EMPTY: a generation swap must land at
        a quiescent boundary, and an in-flight chunk's epilogue would
        otherwise rank against post-swap state."""
        now = self._clock()
        self._shed_expired(now)
        if self._pipe is not None:
            return self._poll_pipelined(now)
        reason = self._due(now) if self._pending else None
        if reason is None:
            self._reopt_step()
            return 0
        chunk = self._next_chunk()
        self._run_chunk(chunk, reason)
        self._reopt_step()
        return len(chunk)

    def _poll_pipelined(self, now: float) -> int:
        """One pipelined scheduling step (see ``poll``): dispatch every
        due chunk a free stage slot can take, retire the FIFO head, and
        use genuinely idle ticks for shape prewarming / reopt."""
        pipe = self._pipe
        while pipe.inflight < pipe.depth and self._pickable():
            reason = self._due(now)
            if reason is None:
                break
            pipe.dispatch(self._next_chunk(), reason)
        if pipe.inflight:
            served = pipe.retire()
            if pipe.inflight == 0:
                self._reopt_step()
            return served
        # idle: burn the free stage slot on prewarming, else reopt
        if not pipe.prewarm_step():
            self._reopt_step()
        return 0

    def _window_s(self, sig: str) -> float:
        """Batching window (seconds) for one signature. Static mode:
        ``max_delay_ms`` for every signature. Adaptive mode: one
        full-batch service time (QBS p50 x ``batch_size``) once >= 8
        service samples exist — the longest wait that amortization can
        still pay for — capped by ``max_delay_ms`` when set; the static
        window until the signature is warm."""
        base = self.max_delay_ms / 1e3
        if not self.adaptive_window:
            return base
        lq = self.platform.qbs.latency_quantiles(sig)
        if lq is None or lq["n"] < 8:
            return base
        w = float(lq["p50"]) * self.batch_size
        return min(base, w) if base > 0 else w

    def next_due(self) -> Optional[float]:
        """Earliest clock time at which some pending entry exhausts its
        signature's batching window (or its deadline, whichever is
        sooner) — the wake-up time for a drive loop whose ``poll``
        returned 0. None when nothing is pending (entries already in
        flight through the pipeline don't count — they are served by
        the retire half of the next ``poll``, not by a window)."""
        avail = self._pickable()
        if not avail:
            return None
        win: Dict[str, float] = {}
        due = []
        for p in avail:
            if p.sig not in win:
                win[p.sig] = self._window_s(p.sig)
            t = p.t_submit + win[p.sig]
            due.append(t if p.deadline is None else min(t, p.deadline))
        return min(due)

    def _due(self, now: float) -> Optional[str]:
        """Why a micro-batch is worth running right now, or None when
        none is (queue non-empty is the caller's precondition):
        ``"full"`` when the queue or a signature group holds
        ``batch_size``, ``"window"`` when an entry's signature window is
        exhausted (or zero), ``"deadline"`` when a deadline falls inside
        that window. Only entries NOT already in flight count (serial
        mode: all of them)."""
        avail = self._pickable()
        if len(avail) >= self.batch_size:
            return "full"
        if self.coalesce:
            counts: Dict[str, int] = {}
            for p in avail:
                counts[p.sig] = counts.get(p.sig, 0) + 1
                if counts[p.sig] >= self.batch_size:
                    return "full"
        win: Dict[str, float] = {}   # one QBS lookup per sig per pass
        for p in avail:
            if p.sig not in win:
                win[p.sig] = self._window_s(p.sig)
            w = win[p.sig]
            if w <= 0 or now - p.t_submit >= w:
                return "window"
            if p.deadline is not None and p.deadline <= now + w:
                return "deadline"
        return None

    # ------------------------------------------------- re-optimization
    def attach_reopt(self, controller) -> None:
        """Attach a ``repro.core.reopt.ReoptController``; ``poll()``
        will drive one ``controller.step()`` per call (see ``poll``).
        The controller inherits this server's session when it was built
        without one, so plan prewarming lands in the cache the serving
        path actually reads."""
        if controller.session is None:
            controller.session = self.session
        self.reopt = controller

    def _reopt_step(self) -> Optional[str]:
        """One unit of cooperative background work (no-op when no
        controller is attached). Called only between micro-batches /
        at idle points, so a generation swap inside ``step()`` can
        never be observed by a half-executed batch."""
        if self.reopt is None:
            return None
        return self.reopt.step()

    # ------------------------------------------------------ admission ctrl
    def _service_estimate(self, sig: str) -> float:
        """Expected per-request service time for an archetype, from the
        QBS serving stats (0.0 until >= 8 samples exist — predictive
        shedding stays off for cold archetypes rather than guessing)."""
        lq = self.platform.qbs.latency_quantiles(sig)
        if lq is None or lq["n"] < 8:
            return 0.0
        return float(lq["p50"])

    def _shed_expired(self, now: float):
        """Resolve-with-shed every pending request whose deadline has
        passed — or whose archetype's QBS p50 service time says it
        cannot finish in the remaining budget even starting now.
        Shedding is an explicit resolution (``shed=True``), never a
        drop: counters and the future both record it. Entries already
        in flight through the pipeline are never shed — their compute
        is already enqueued on device, so serving the result costs
        less than wasting it."""
        keep: List[_Pending] = []
        est: Dict[str, float] = {}   # one QBS lookup per sig per pass
        for p in self._pending:
            # in-flight entries are not in _pending, so they are never
            # shed — their compute is already enqueued on device
            if p.deadline is None:
                keep.append(p)
                continue
            if p.sig not in est:
                est[p.sig] = self._service_estimate(p.sig)
            if p.deadline <= now + est[p.sig]:
                p.fut._set(RetrievalResult(
                    rows=np.empty(0, np.int64), query=None, shed=True,
                    latency_s=max(0.0, now - p.t_submit)))
                self.n_shed += 1
            else:
                keep.append(p)
        self._pending = keep

    def _next_chunk(self) -> List[_Pending]:
        """Pick the next micro-batch (queue is non-empty). Coalescing:
        prefer the signature group that has ``batch_size`` requests and
        the oldest head; otherwise the oldest request's group. Sizes are
        quantized to powers of two (<= ``batch_size``) so the compiled
        shape universe stays |signatures| x log2(batch_size). Legacy
        FIFO: the first ``batch_size`` entries regardless of signature.
        Entries are SELECTED here, not removed — ``_finish_chunk``
        dequeues only after the batch succeeded. Pipelined mode picks
        only entries not already in flight (``_pickable``)."""
        avail = self._pickable()
        if not self.coalesce:
            return avail[:self.batch_size]
        groups: Dict[str, List[_Pending]] = {}
        for p in avail:                   # FIFO order within each group
            groups.setdefault(p.sig, []).append(p)
        full = [g for g in groups.values() if len(g) >= self.batch_size]
        if full:
            grp = min(full, key=lambda g: g[0].t_submit)
        else:
            grp = groups[avail[0].sig]
        # full groups always run at batch_size itself; partial groups
        # round DOWN to a power of two (the leftovers stay queued for
        # the next micro-batch), so per signature the engine only ever
        # compiles sizes {1, 2, 4, ..., batch_size}
        if len(grp) >= self.batch_size:
            take = self.batch_size
        else:
            take = 2 ** int(math.log2(len(grp)))
            self.recorder.count("serve.rounded_off", len(grp) - take)
        return grp[:take]

    # ---------------------------------------------------------- execution
    def _run_chunk(self, chunk: Sequence[_Pending], reason: str):
        """Execute one single-signature (coalesced) or mixed (FIFO)
        micro-batch, all-or-nothing: every result is computed and ranked
        before ANY future resolves or queue entry leaves ``_pending``.
        Past the mutation point nothing can raise (plain list/dict
        bookkeeping), so either the whole chunk resolves and dequeues,
        or none of it does. The chunk runs under this server's
        recorder, in the ``serve.*`` spans ``stats()`` reports."""
        reqs = [p.req for p in chunk]
        t0 = self._clock()
        with recording(self.recorder), \
                span("serve.chunk", size=len(chunk), sig=chunk[0].sig):
            with span("serve.embed"):
                emb = self._embed_tokens([r.tokens for r in reqs])
            with span("serve.queries"):
                queries = self._queries(reqs, emb)
            with span("serve.plan"):
                plan = self.session.plan(queries,
                                         device_loop=self.device_loop)
            with span("serve.execute"):
                rows, stats = plan.execute()
            with span("serve.rank"):
                ranked = [self._ranked(req, e, r)
                          for req, e, r in zip(reqs, emb, rows)]
            with span("serve.finish"):
                self._finish_chunk(chunk, queries, ranked, t0, reason,
                                   stats)

    def _finish_chunk(self, chunk: Sequence[_Pending], queries,
                      ranked, t0: float, reason: str, stats) -> None:
        """The shared mutation point for a fully-computed chunk:
        resolve futures, dequeue entries, record serving stats. Both
        the serial loop (``_run_chunk``) and the pipeline's retire
        stage (``ChunkPipeline.retire``) end here, so QBS latency /
        e2e-ring writes and the recorder's per-chunk counters (why the
        chunk ran, its ``EngineStats``, each request's queue wait
        ``t0 - t_submit``) are funneled through one code path
        regardless of execution mode. Nothing here can raise (plain
        list/dict bookkeeping), preserving the all-or-nothing
        contract."""
        t1 = self._clock()
        per_req_s = (t1 - t0) / max(1, len(chunk))
        sig_counts: Dict[str, int] = {}
        for p, rk, q in zip(chunk, ranked, queries):
            p.fut._set(RetrievalResult(rows=rk, query=q,
                                       latency_s=max(0.0,
                                                     t1 - p.t_submit)))
            sig_counts[p.sig] = sig_counts.get(p.sig, 0) + 1
            e2e = self._e2e.setdefault(p.sig, [])
            e2e.append(max(0.0, t1 - p.t_submit))
            if len(e2e) > _E2E_KEEP:
                del e2e[:len(e2e) - _E2E_KEEP]
        for sig, n in sig_counts.items():
            self.platform.qbs.record_latency(sig, per_req_s, n=n)
        done = {id(p) for p in chunk}
        self._pending = [p for p in self._pending if id(p) not in done]
        self.n_served += len(chunk)
        self.n_batches += 1
        rec = self.recorder
        start_ns = time.time_ns() - int((t1 - t0) * 1e9)  # t0, host clock
        for p in chunk:
            rec.sample("serve.queue_wait",
                       max(0, int((t0 - p.t_submit) * 1e9)), start_ns)
        rec.count("serve.chunks")
        rec.count("serve.chunks." + reason)
        rec.count("engine.queries", stats.queries)
        rec.count("engine.rows_scanned", stats.rows_scanned)
        rec.count("engine.knn_rounds", stats.knn_rounds)
        rec.count("engine.topk_blocks", stats.topk_blocks)
        rec.count("engine.topk_merge_passes", stats.topk_merge_passes)
        rec.count("engine.knn_buckets", stats.knn_buckets)
        rec.count("engine.predicate_buckets", stats.predicate_buckets)

    # ------------------------------------------------------------- sync
    def serve(self, requests: Sequence[RetrievalRequest]
              ) -> List[RetrievalResult]:
        futures = [self.submit(r) for r in requests]
        self.flush()
        return [f.result() for f in futures]

    def stats(self) -> dict:
        """Serving counters plus per-signature end-to-end latency
        quantiles (seconds; service-time quantiles live in the QBS
        table, see ``QBSTable.latency_quantiles``). ``generation`` /
        ``build_id`` identify the index generation currently serving;
        ``reopt`` is the attached controller's progress (None when no
        controller is attached).

        ``trace`` is the operator's record of the most recent chunks:
        ``{"spans": {name: {"n": total, "ring": [[start_ns, dur_ns],
        ...]}}, "counters": {name: value}, "time_minus_monotonic_ns":
        offset}`` (``repro.utils.spans``; ``start_ns`` on the
        profiler's host clock, the last 16,384 entries per name; the
        offset maps it onto ``time.monotonic_ns()`` as of this call). Spans: ``serve.chunk`` from chunk pick to the
        last future resolved, its stages ``serve.embed``,
        ``serve.queries``, ``serve.plan``, ``serve.execute``,
        ``serve.rank``, ``serve.finish`` (pipelined mode:
        ``serve.dispatch`` and ``serve.retire`` around them, and
        ``serve.execute`` twice, dispatch and epilogue), the engine's
        and planner's spans inside, and ``serve.queue_wait``, one
        sample per served request (chunk start less arrival). Counters:
        ``serve.chunks`` and ``serve.chunks.<why>`` (``full``,
        ``window``, ``deadline``, ``flush``, ``backpressure``),
        ``serve.rounded_off`` (requests a partial group's power-of-two
        round-down left queued), and the chunks' summed ``EngineStats``
        (``engine.queries``, ``engine.rows_scanned``,
        ``engine.knn_rounds``, ``engine.topk_blocks``,
        ``engine.topk_merge_passes``, ``engine.knn_buckets``,
        ``engine.predicate_buckets``)."""
        by_sig = {}
        for sig, ls in self._e2e.items():
            a = np.asarray(ls, np.float64)
            by_sig[sig] = {"p50_s": float(np.quantile(a, 0.5)),
                           "p99_s": float(np.quantile(a, 0.99)),
                           "n": len(ls)}
        return {"submitted": self.n_submitted, "served": self.n_served,
                "shed": self.n_shed, "batches": self.n_batches,
                "queue_depth": self.queue_depth,
                "pipeline_depth": self.pipeline_depth,
                "inflight_chunks": self.inflight_chunks,
                "generation": self.platform.generation,
                "build_id": self.platform.build_id,
                "reopt": None if self.reopt is None
                else self.reopt.status(),
                "by_signature": by_sig,
                "trace": self.recorder.snapshot()}
