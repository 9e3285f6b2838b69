"""Chunk-level pipelined executor for ``RetrievalServer``.

A bounded three-stage software pipeline over signature-coalesced
micro-batch chunks, on ONE Python thread:

  1. **stage/embed** (host) — tokens -> embeddings -> query ASTs for
     the newest chunk (``RetrievalServer._embed_tokens`` /
     ``_queries``);
  2. **dispatch** (device) — ``Session.plan(...).execute_async()``
     enqueues the chunk's predicate masks and fused KNN first round on
     the device's XLA execution threads and returns immediately
     (``repro.core.planner.PendingExecution``);
  3. **epilogue** (host) — ``materialize()`` fences the chunk at its
     stage boundary (the (G,) active-mask read whose D2H copy started
     at dispatch), runs straggler rounds + the finishing walk, ranks
     rows, resolves futures, and records QBS latency / convergence /
     workload (all ring writes behind ``QBSTable``'s lock, funneled
     through this stage).

With ``depth`` chunks in flight, the epilogue of chunk *i* and the
staging of chunk *i+2* run on the host while the device executes chunk
*i+1*'s already-enqueued programs in the background — that overlap is
the sustained-QPS win. jax's async dispatch provides the concurrency:
a jitted call returns before the program finishes, and the single
device executes enqueued programs in dispatch order, so materializing
an older chunk never waits on a newer chunk's work.

Fence contract: after dispatch, a chunk's ONLY device syncs happen
inside its ``materialize()`` — no stage takes an eager ``np.asarray``
mid-pipeline. ``depth=1`` is not constructed at all: the server keeps
its serial ``_run_chunk`` loop byte-identical (including cost-sample
recording, which the async path skips — see
``ExecutablePlan.execute_async``).

Ordering / failure contract (mirrors the serial loop):

  * chunks retire strictly FIFO (oldest dispatched first), so each
    request's future resolves exactly once, in its own chunk's
    epilogue — in-order per request;
  * all-or-nothing per chunk: a dispatch or materialize failure leaves
    every one of THAT chunk's requests pending (entries unmarked, back
    in the pickable queue) and its futures unresolved/retryable, and
    propagates — chunks already retired are untouched (futures are
    immutable once set) and chunks still in flight retire normally on
    the next pump;
  * ``drain()`` is the quiescent barrier: it retires every in-flight
    chunk (and settles any prewarm dispatch) WITHOUT dispatching new
    work, so ``append()`` atomicity and a reopt ``swap()`` land
    between micro-batches exactly as the serial loop guarantees.

Shape prewarming: the first time a signature dispatches a FULL-batch
chunk, its pow2 partial sizes (batch_size/2 ... 1) are queued; the
server's idle polls run one queued size at a time through the free
stage slot (``prewarm_step``: dispatch on one idle tick, materialize on
the next, results discarded, ``record=False`` so QBS rings stay
clean) — window-flushed partial chunks then hit warm compiled shapes
instead of stalling the pipeline on a cold trace+compile.
"""
from __future__ import annotations

import time
from collections import deque
from typing import Deque, List, Optional, Sequence, Set, Tuple

from repro.utils.spans import recording, span


class _InflightChunk:
    """One dispatched micro-batch: its queue entries, staged inputs,
    and the deferred epilogue handle."""

    __slots__ = ("chunk", "reqs", "emb", "queries", "pending", "t0",
                 "reason", "start_ns", "p0")

    def __init__(self, chunk, reqs, emb, queries, pending, t0, reason,
                 start_ns, p0):
        self.chunk = chunk
        self.reqs = reqs
        self.emb = emb
        self.queries = queries
        self.pending = pending
        self.t0 = t0
        self.reason = reason          # why the chunk ran (server counters)
        self.start_ns = start_ns      # dispatch start, host clock
        self.p0 = p0                  # dispatch start, perf_counter_ns


class ChunkPipeline:
    """The server-side pipeline state: a FIFO of in-flight chunks
    bounded by ``depth``, plus the shape-prewarm queue. Owned by one
    ``RetrievalServer`` (depth >= 2 only; depth 1 keeps the serial
    loop) and driven from its ``poll``/``flush``/``submit`` paths —
    single-threaded by construction, like the server itself."""

    def __init__(self, server, depth: int):
        if depth < 2:
            raise ValueError("ChunkPipeline needs depth >= 2 "
                             "(depth 1 is the server's serial loop)")
        self.server = server
        self.depth = int(depth)
        self._inflight: Deque[_InflightChunk] = deque()
        # prewarm state: signatures whose full-batch shape was seen,
        # the (sig, template query, size) compile queue, and the one
        # prewarm execution currently occupying the idle stage slot
        self._warm_seen: Set[str] = set()
        self._warm_queue: Deque[Tuple[str, object, int]] = deque()
        self._warm_pending = None

    # ------------------------------------------------------------ state
    @property
    def inflight(self) -> int:
        return len(self._inflight)

    # ------------------------------------------------------------ stages
    def dispatch(self, chunk: Sequence, reason: str) -> None:
        """Stages 1+2 for one chunk: embed + build queries (host), then
        enqueue the planned execution on the device and append the
        chunk to the in-flight FIFO. On ANY raise the chunk's entries
        stay pending and unmarked (nothing was appended), so the next
        flush retries them — in-flight chunks are unaffected.
        ``reason`` is why the chunk ran, counted when it retires."""
        srv = self.server
        reqs = [p.req for p in chunk]
        t0 = srv._clock()
        start_ns, p0 = time.time_ns(), time.perf_counter_ns()
        with recording(srv.recorder), \
                span("serve.dispatch", size=len(chunk), sig=chunk[0].sig):
            with span("serve.embed"):
                emb = srv._embed_tokens([r.tokens for r in reqs])
            with span("serve.queries"):
                queries = srv._queries(reqs, emb)
            with span("serve.plan"):
                plan = srv.session.plan(queries,
                                        device_loop=srv.device_loop)
            with span("serve.execute"):
                pending = plan.execute_async()
        self._inflight.append(_InflightChunk(
            list(chunk), reqs, emb, queries, pending, t0, reason,
            start_ns, p0))
        srv._mark_inflight(chunk)
        self._note_shape(chunk, queries)

    def retire(self) -> int:
        """Stage 3 for the OLDEST in-flight chunk: materialize (the
        chunk's one fence), rank, then resolve futures / dequeue /
        record QBS through the server's shared epilogue
        (``_finish_chunk`` — the serial loop's mutation point).
        Returns requests served (0 when nothing is in flight).

        All-or-nothing: a raise before the mutation point drops the
        chunk from the pipe with its entries returned to the pickable
        queue and every future unresolved — retryable, isolated to
        this chunk."""
        if not self._inflight:
            return 0
        srv = self.server
        ent = self._inflight[0]
        with recording(srv.recorder), span("serve.retire"):
            try:
                with span("serve.execute"):
                    rows, stats = ent.pending.materialize()
                with span("serve.rank"):
                    ranked = [srv._ranked(req, e, r) for req, e, r in
                              zip(ent.reqs, ent.emb, rows)]
            except BaseException:
                self._inflight.popleft()
                srv._unmark_inflight(ent.chunk, requeue=True)
                raise
            self._inflight.popleft()
            srv._unmark_inflight(ent.chunk)
            with span("serve.finish"):
                srv._finish_chunk(ent.chunk, ent.queries, ranked, ent.t0,
                                  ent.reason, stats)
        # the chunk's whole life, dispatch to resolution: chunks overlap
        # here, so it is recorded as an interval, not entered as a span
        srv.recorder.add("serve.chunk", ent.start_ns,
                         time.perf_counter_ns() - ent.p0)
        return len(ent.chunk)

    def drain(self) -> int:
        """Quiescent barrier: retire every in-flight chunk in FIFO
        order (dispatching nothing new) and settle any in-flight
        prewarm execution, so no chunk state remains on device.
        ``RetrievalServer.append`` and the reopt swap boundary call
        this first. Returns total requests served."""
        n = 0
        while self._inflight:
            n += self.retire()
        if self._warm_pending is not None:
            pend, self._warm_pending = self._warm_pending, None
            pend.materialize()
        return n

    # ---------------------------------------------------------- prewarm
    def _note_shape(self, chunk: Sequence, queries: List) -> None:
        """First full-batch dispatch of a signature: queue its pow2
        partial sizes for idle-slot compilation (largest first — the
        sizes window flushes actually produce under load)."""
        srv = self.server
        sig = chunk[0].sig
        if len(chunk) < srv.batch_size or sig in self._warm_seen:
            return
        self._warm_seen.add(sig)
        size = srv.batch_size // 2
        while size >= 1:
            self._warm_queue.append((sig, queries[0], size))
            size //= 2

    def prewarm_step(self) -> bool:
        """One unit of idle-slot prewarming: materialize the in-flight
        prewarm execution if one exists, else dispatch the next queued
        partial shape (``record=False`` — dummy executions must not
        feed QBS convergence/workload rings or the latency stats).
        Results are discarded; only the traced/compiled shapes and the
        warmed plan skeleton persist. Returns True when it did work
        (the server then skips its reopt step for this idle tick)."""
        if self._warm_pending is not None:
            pend, self._warm_pending = self._warm_pending, None
            pend.materialize()
            return True
        if not self._warm_queue:
            return False
        srv = self.server
        _, query, size = self._warm_queue.popleft()
        plan = srv.session.plan([query] * size,
                                device_loop=srv.device_loop)
        self._warm_pending = plan.execute_async(record=False)
        return True
