"""Fused retrieval-to-generation serving: the RGL "unified system" front-end.

``RAGServeEngine`` closes the gap between the retrieval pipeline and the
decode server: a raw ``(query_emb, query_text)`` request goes through

    index -> seed retrieval -> subgraph construction -> dynamic filter
          -> tokenization -> batched prefill -> continuous-batching decode

inside one engine.  Three amortization mechanisms drive throughput:

* **Batched admission retrieval** — every admission wave runs ONE jitted
  ``RGLPipeline.retrieve_many`` call over the whole wave (padded to a fixed
  shape), instead of per-request retrieval dispatches.  This is the paper's
  core batching speedup applied at serve time.
* **Retrieval caching** — a policy-driven (lru / lfu / ttl, optional expiry)
  :class:`~repro.serving.cache.RetrievalCache` keyed on quantized query
  embeddings lets repeated / near-duplicate queries skip index + BFS + filter
  entirely.  Hit/miss counters are exposed as ``engine.cache_hits`` /
  ``engine.cache_misses``; pick the policy via the ``cache_policy`` /
  ``cache_ttl`` fields of the engine's ``ServingConfig``.
* **Async admission prefetch** (``prefetch=True``, or ``RGL_PREFETCH=1``) —
  wave *i+1*'s retrieval is *launched* (dispatched, results left as device
  arrays) while wave *i*'s decode steps run, and *collected* (forced,
  tokenized, admitted) only once decode slots free up: double-buffered
  admission via :class:`~repro.serving.prefetch.AdmissionPrefetcher`.  Sync
  mode runs the identical launch/collect code back-to-back, so the two
  schedules produce bitwise-identical outputs (see
  ``tests/test_async_serving.py``).

Two admission *granularities* sit on top of either schedule
(``admission=`` / ``RGL_ADMISSION``): classic **wave** admission retrieves
and admits whole waves, while **continuous** admission launches one
retrieval per request and — under prefetch — collects whichever request's
retrieval is ready (``AdmissionPrefetcher.ready_index``), so a single slow
retrieval row no longer delays its wave-mates and a freed decode slot never
waits for a wave boundary.  Outputs are bitwise identical across all four
combinations (greedy decode is schedule-invariant per request).

Generation itself rides the slot-based :class:`~repro.serving.engine.ServeEngine`
(one jitted decode step for all slots, masked batched prefill admission).
``spec_decode`` / ``RGL_SPEC_DECODE=1`` switches the decode arena to
self-speculative multi-token decode (prompt-lookup drafts verified in one
dispatch; bitwise-identical outputs, up to ``draft_window`` tokens committed
per dispatch) — see :mod:`repro.serving.engine`.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Optional

import numpy as np

from repro import tracing
from repro.core.pipeline import RGLPipeline
from repro.models.transformer.config import TransformerConfig
from repro.serving.cache import RetrievalCache
from repro.serving.config import ServingConfig
from repro.serving.engine import Request, ServeEngine
from repro.serving.prefetch import AdmissionPrefetcher
from repro.serving.stats import flatten_stats


@dataclasses.dataclass
class RAGRequest:
    """A raw serving request: query embedding + query text, no tokens yet.

    Terminal states (exactly one holds when the engine hands the request
    back): ``done`` (served — possibly ``stale`` or ``degraded``),
    ``failed`` (retrieval faults exhausted the whole degradation ladder, or
    the engine was aborted; ``error`` says why), or ``shed`` (refused by
    overload control or expired past its deadline before admission).
    """

    uid: int
    query_emb: np.ndarray  # (D,) float32
    query_text: str
    max_new_tokens: int = 32
    # seconds of deadline budget from submit time; the engine sheds the
    # request at any launch/collect/admit boundary past it.  None falls back
    # to the engine's default_deadline_s (None = no deadline)
    deadline_s: Optional[float] = None
    out_tokens: list = dataclasses.field(default_factory=list)
    prompt_ids: Optional[np.ndarray] = None  # filled at admission
    retrieved_nodes: Optional[np.ndarray] = None  # filtered subgraph members
    cache_hit: bool = False
    done: bool = False
    # retired early by KV exhaustion (contiguous arena full / paged pool
    # empty): out_tokens is shorter than max_new_tokens with no EOS
    truncated: bool = False
    # --- fault-tolerance terminal/degraded markers (see class docstring) ---
    stale: bool = False  # served from a TTL-expired cache entry
    degraded: bool = False  # served retrieval-free (query-only prompt)
    failed: bool = False
    shed: bool = False
    error: Optional[str] = None  # reason for failed/shed
    deadline_at: Optional[float] = None  # absolute deadline, set at submit
    # stamps on the engine's clock (``now_fn``), one read per stage: entered
    # the pending queue, retrieval dispatched (or answered from the cache),
    # prompt linearized, first token read back to the host
    submitted_at: Optional[float] = None
    launched_at: Optional[float] = None
    prompt_at: Optional[float] = None
    first_token_at: Optional[float] = None


class RAGServeEngine:
    """End-to-end RAG server: retrieval-batched admission over a decode arena.

    Usage::

        eng = RAGServeEngine(pipe, params, cfg,
                             config=ServingConfig(slots=8, cache_len=256))
        eng.submit(RAGRequest(uid=0, query_emb=emb, query_text="..."))
        finished = eng.run_to_completion()   # .out_tokens per request

    ``pipe`` must carry a tokenizer and node_text (stages 4's inputs).
    Every serving option is a field of ``config``
    (:class:`~repro.serving.config.ServingConfig`, resolved once here: field
    value > ``RGL_*`` env > default); ``self.config`` is the resolved value.

    **Fault tolerance.**  Retrieval faults are data-plane events, not
    engine crashes: ``step()`` never raises for one.  A failed miss-group
    (dispatch raise, force raise, timeout after ``retrieval_timeout_s``, or
    a corrupt result) is retried in isolation up to ``max_retries`` times
    (``retry_backoff_s`` exponential backoff); on exhaustion the request
    walks the degradation ladder:

    1. **stale** — a resident cache entry for the key, TTL-expired allowed
       (``stale_served`` counter, ``RAGRequest.stale``);
    2. **degraded** — retrieval-free decode over a query-only prompt
       (``degraded`` counter/flag; disable with ``degraded_mode=False`` /
       ``RGL_DEGRADED=0``);
    3. **failed** — that one request terminates with ``failed=True`` and an
       ``error`` reason; wave-mates are unaffected.

    **Overload control.**  ``max_pending`` bounds the pending queue
    (0 = unbounded); on overflow ``shed_policy`` picks the victim:
    ``"reject"`` refuses the new request, ``"evict-oldest"`` sheds the
    oldest pending one.  Per-request deadlines (``deadline_s``, or the
    engine-wide ``default_deadline_s``) are checked at every
    launch/collect/admit boundary — an expired request is shed, never
    dispatched.  Shed requests surface through ``step()`` like finished
    ones, with ``shed=True``.

    ``abort()`` fails all outstanding work and reconciles every layer
    (pending queue, in-flight prefetch waves + cache keys, decode slots +
    paged KV blocks, admission tickets); ``drain()`` is run_to_completion
    that aborts the stragglers instead of raising.

    **Replica embedding.**  The engine is designed to run as one replica of
    a fleet behind :class:`repro.serving.router.ReplicaRouter`: pass the
    same ``retrieval_cache=`` instance to every replica to share the
    retrieval tier (the in-flight key registry gives the fleet single-flight
    semantics — see :mod:`repro.serving.cache`), and the router reads
    :meth:`health` each step to score replicas and route around trouble.
    """

    def __init__(
        self,
        pipeline: RGLPipeline,
        params,
        cfg: TransformerConfig,
        config: Optional[ServingConfig] = None,
        *,
        retrieval_cache: Optional[RetrievalCache] = None,
        now_fn=time.monotonic,
        sleep_fn=time.sleep,
    ):
        assert pipeline.tokenizer is not None, "pipeline needs a tokenizer"
        assert pipeline.node_text is not None, "pipeline needs node_text"
        # the one resolution pass: field value > RGL_* env > default
        self.config = resolved = (config or ServingConfig()).finalize()
        if pipeline.tokenizer.max_len >= resolved.cache_len:
            raise ValueError(
                f"tokenizer.max_len={pipeline.tokenizer.max_len} must be < "
                f"cache_len={resolved.cache_len} so every prompt fits the KV "
                f"arena"
            )
        self.pipeline = pipeline
        self.slots = resolved.slots
        self.engine = ServeEngine(
            params, cfg, slots=resolved.slots, cache_len=resolved.cache_len,
            eos_id=resolved.eos_id,
            spec_decode=resolved.spec_decode,
            draft_window=resolved.draft_window,
            paged_kv=resolved.paged_kv, kv_block_size=resolved.kv_block_size,
            kv_pool_blocks=resolved.kv_pool_blocks,
            prefix_share=resolved.prefix_share, now_fn=now_fn,
        )
        self.cache = retrieval_cache if retrieval_cache is not None else \
            RetrievalCache(capacity=resolved.cache_capacity,
                           policy=resolved.cache_policy,
                           ttl=resolved.cache_ttl)
        if self.engine.prefix_share:
            # wire the engine's pin protocol to this cache: pins only attach
            # to entries still resident (a pin on an evicted entry would leak
            # pool blocks forever), and pool pressure releases cache pins
            # before the engine truncates any live request
            self.engine.kv_pin_gate = self.cache.is_resident
            self.engine.kv_pin_reclaim = (
                lambda n: self.cache.reclaim_kv(n, owner=self.engine)
            )
        self.prefetch = resolved.prefetch
        self.admission = resolved.admission
        prefetch_depth = resolved.prefetch_depth
        if prefetch_depth is None:
            # continuous admission launches size-1 waves, so the in-flight
            # window must hold one wave per slot to keep every free slot's
            # retrieval overlapping; wave admission double-buffers (depth 1)
            prefetch_depth = resolved.slots \
                if self.admission == "continuous" else 1
        # continuous launches always carry one request, so the retrieval
        # batch pads to 1 row instead of `slots` — per-row retrieval is
        # row-independent, so results stay bitwise identical while the
        # per-dispatch compute stops scaling with the unused padding
        self.degraded_mode = resolved.degraded_mode
        self.max_pending = resolved.max_pending  # 0 = unbounded
        self.shed_policy = resolved.shed_policy
        self.default_deadline_s = resolved.default_deadline_s
        self.compact_every = resolved.compact_every  # 0 = manual only
        self._now = now_fn
        # the prefetcher shares the engine's clock pair so retry backoff,
        # timeout deadlines, and readiness polling are fully clock-injectable
        # (chaos tests drive a virtual clock and never wall-sleep)
        self.prefetcher = AdmissionPrefetcher(
            pipeline, self.cache,
            wave_size=1 if self.admission == "continuous" else resolved.slots,
            depth=prefetch_depth,
            retrieval_timeout_s=resolved.retrieval_timeout_s,
            max_retries=resolved.max_retries,
            retry_backoff_s=resolved.retry_backoff_s,
            now_fn=now_fn,
            sleep_fn=sleep_fn,
        )
        self.pending: deque = deque()
        self._inflight: dict = {}  # admission ticket -> RAGRequest
        self._next_ticket = 0  # monotonic; never reused (unlike id())
        self._step_no = 0
        # requests that went terminal outside decode (shed / failed /
        # degradation-exhausted); step() hands them back exactly once
        self._terminal: list = []
        # fault-tolerance counters (every submitted request lands in exactly
        # one of: done, failed, shed — stale/degraded refine done)
        self.shed_count = 0
        self.failed_count = 0
        self.degraded_count = 0
        self.stale_served = 0
        # online-mutation counters (apply_mutations)
        self.mutation_batches = 0
        self.mutation_invalidated = 0

    # -- cache counters -------------------------------------------------------
    @property
    def cache_hits(self) -> int:
        return self.cache.hits

    @property
    def cache_misses(self) -> int:
        return self.cache.misses

    # -- amortization telemetry (delegated to the prefetcher, which runs the
    # launch/collect phases for both admission schedules) ----------------------
    @property
    def retrieval_batches(self) -> int:
        return self.prefetcher.batches

    @property
    def retrieved_queries(self) -> int:
        return self.prefetcher.queries

    @property
    def retrieval_seconds(self) -> float:
        p = self.prefetcher
        return p.launch_seconds + p.block_seconds

    # -- terminal bookkeeping -------------------------------------------------
    def _shed(self, req: RAGRequest, reason: str) -> None:
        req.shed = True
        req.error = reason
        self.shed_count += 1
        self._terminal.append(req)

    def _fail(self, req: RAGRequest, reason: str) -> None:
        req.failed = True
        req.error = reason
        self.failed_count += 1
        self._terminal.append(req)

    def _expired(self, req: RAGRequest) -> bool:
        return req.deadline_at is not None and self._now() > req.deadline_at

    # -- admission ------------------------------------------------------------
    def _validate(self, req: RAGRequest) -> None:
        """Reject malformed requests at the front door, before any queue or
        dispatch sees them — a NaN embedding must not poison a batched
        retrieval wave, and a bad field must name the offending uid."""
        q = np.asarray(req.query_emb, np.float32)
        if q.ndim != 1:
            raise ValueError(
                f"request {req.uid}: query_emb must be 1-D, got shape "
                f"{tuple(q.shape)}"
            )
        node_emb = getattr(self.pipeline, "node_emb", None)
        if node_emb is not None and q.shape[0] != node_emb.shape[1]:
            raise ValueError(
                f"request {req.uid}: query_emb dim {q.shape[0]} != node "
                f"embedding dim {node_emb.shape[1]}"
            )
        if not np.isfinite(q).all():
            raise ValueError(
                f"request {req.uid}: query_emb contains NaN/Inf"
            )
        if not str(req.query_text).strip():
            raise ValueError(f"request {req.uid}: empty query_text")
        if req.max_new_tokens < 1:
            raise ValueError(
                f"request {req.uid}: max_new_tokens must be >= 1, got "
                f"{req.max_new_tokens}"
            )
        if req.deadline_s is not None and req.deadline_s <= 0:
            raise ValueError(
                f"request {req.uid}: deadline_s must be > 0, got "
                f"{req.deadline_s}"
            )

    def submit(self, req: RAGRequest) -> bool:
        """Validate and enqueue.  Returns True if the request entered the
        pending queue, False if overload control shed it on arrival
        (``shed_policy="reject"`` with a full queue) — the shed request is
        still handed back by the next ``step()``.  Malformed requests raise
        ``ValueError`` and never enter the system."""
        self._validate(req)
        if req.submitted_at is None:  # a failover re-dispatch keeps its own
            req.submitted_at = self._now()
        if req.deadline_at is None:
            # a request arriving with deadline_at already pinned (a router
            # failover re-dispatch) keeps it: re-submitting must never
            # restart the deadline budget
            deadline = req.deadline_s if req.deadline_s is not None \
                else self.default_deadline_s
            if deadline is not None:
                req.deadline_at = self._now() + float(deadline)
        if self.max_pending and len(self.pending) >= self.max_pending:
            if self.shed_policy == "reject":
                self._shed(req, "queue full (shed_policy=reject)")
                return False
            victim = self.pending.popleft()
            self._shed(victim, "queue full (shed_policy=evict-oldest)")
        self.pending.append(req)
        return True

    def _take_wave(self, limit: Optional[int] = None) -> list:
        cap = self.slots if limit is None else limit
        out: list = []
        while self.pending and len(out) < cap:
            r = self.pending.popleft()
            if self._expired(r):
                # deadline boundary 1: never dispatch retrieval for a
                # request that is already past its deadline
                self._shed(r, "deadline expired before retrieval dispatch")
                continue
            out.append(r)
        return out

    @property
    def _launch_unit(self) -> int:
        """Requests per retrieval launch: a full wave in wave admission, a
        single request in continuous admission (so one slow retrieval row
        never blocks the admission of its would-be wave-mates)."""
        return 1 if self.admission == "continuous" else self.slots

    def _tokenize_and_admit(self, resolved: list) -> None:
        """Stage 4+5 handoff: linearize each resolved ``(request, entry,
        error)`` triple and hand the prompt to the decode engine under a
        fresh admission ticket.

        This is where the graceful-degradation ladder runs: a request whose
        retrieval failed (``entry is None``, ``error`` says why) tries a
        stale cache entry first, then a retrieval-free (query-only) prompt,
        and only then fails — each rung per request, so one dead retrieval
        row never drags down its wave-mates.  A request past its deadline is
        shed here instead of admitted (deadline boundary 3)."""
        tok = self.pipeline.tokenizer
        node_text = self.pipeline.node_text
        for r, e, err in resolved:
            if self._expired(r):
                self._shed(r, "deadline expired before admission")
                continue
            if e is None:
                stale = self.cache.peek_stale(r.query_emb)
                if stale is not None:
                    # ladder rung 1: serve the resident (possibly
                    # TTL-expired) entry rather than nothing
                    e = stale
                    r.stale = True
                    self.stale_served += 1
                elif self.degraded_mode:
                    # ladder rung 2: retrieval-free decode (query-only
                    # prompt); e stays None
                    r.degraded = True
                    self.degraded_count += 1
                else:
                    # ladder rung 3: fail just this request
                    self._fail(r, err or "retrieval failed")
                    continue
            ticket = None
            try:
                if e is not None:
                    texts = [node_text[int(v)]
                             for v, m in zip(e.nodes, e.mask) if m]
                    r.retrieved_nodes = e.nodes[e.mask].copy()
                else:
                    texts = []
                    r.retrieved_nodes = np.empty(0, np.int32)
                with tracing.span("linearize", uid=r.uid):
                    ids, mask = tok.linearize(r.query_text, texts)
                r.prompt_at = self._now()
                r.prompt_ids = ids[mask]
                inner = Request(
                    uid=r.uid, prompt_ids=r.prompt_ids,
                    max_new_tokens=r.max_new_tokens, ticket=self._next_ticket,
                )
                if self.engine.prefix_share and e is not None:
                    # consumer side when the entry already pins this pool's
                    # prefilled prompt blocks (admission re-validates the
                    # exact prompt and falls back to fresh prefill on any
                    # mismatch); donor side otherwise — a fresh admission
                    # hands its prompt blocks to the entry as a pin
                    inner.pin_to = e
                    if getattr(e, "kv_blocks", None) is not None and \
                            getattr(e, "kv_owner", None) is self.engine:
                        inner.shared_prefix = e
                ticket = inner.ticket
                self._inflight[ticket] = r
                self._next_ticket += 1
                self.engine.submit(inner)
            except Exception as exc:  # per-request containment: a bad
                # entry (e.g. out-of-range node id slipping past
                # validation) fails its own request, not the engine
                if ticket is not None:
                    self._inflight.pop(ticket, None)
                self._fail(r, f"admission: {exc}")

    def _admit_sync(self) -> None:
        """Sync schedule: launch one wave and collect it immediately (the
        collect's ``np.asarray`` blocks for the full retrieval latency).
        Continuous admission runs the same blocking launch+collect per
        *request* instead — one admission unit per free slot."""
        if self.admission == "continuous":
            while self.engine.free_slots > 0 and self.pending:
                reqs = self._take_wave(1)
                if not reqs:  # everything left was past deadline (shed)
                    continue
                self._admit_now(reqs)
            return
        reqs = self._take_wave()
        if reqs:
            self._admit_now(reqs)

    def _admit_now(self, reqs: list) -> None:
        """Launch ``reqs``' retrieval, block on it and admit them."""
        with tracing.span("admit"):
            tok = self.engine.emitted_tokens
            self.prefetcher.launch(reqs, step=self._step_no, tokens=tok)
            self._tokenize_and_admit(self.prefetcher.collect(
                step=self._step_no, tokens=tok, sync=True))

    def _launch_pending(self) -> None:
        while self.pending and self.prefetcher.can_launch():
            reqs = self._take_wave(self._launch_unit)
            if not reqs:  # everything left was past deadline (shed)
                continue
            self.prefetcher.launch(reqs, step=self._step_no,
                                   tokens=self.engine.emitted_tokens)

    def _admit_prefetch(self) -> None:
        """Prefetch schedule: collect waves as decode slots free up
        (backpressure: never tokenize/admit into a still-full arena) and
        launch the next wave(s) so their retrieval overlaps this step's
        decode.  The launch is sandwiched between a wave's collect (which
        inserts its cache entries — so the next lookup sees them) and its
        tokenize/admit, putting the admission overhead *inside* the next
        wave's overlap window too."""
        while (self.prefetcher.launched_before(self._step_no)
                and self.engine.free_slots > 0):
            # never collect a wave in the step it launched (that would
            # forfeit its whole overlap window, e.g. under trickle load
            # where wave size < free slots) — except via the idle-arena
            # fast path below, where there is nothing to overlap with
            with tracing.span("admit"):
                resolved = self.prefetcher.collect(
                    step=self._step_no, tokens=self.engine.emitted_tokens
                )
                self._launch_pending()
                self._tokenize_and_admit(resolved)
        self._launch_pending()
        if (not self.engine.live.any() and not self.engine.queue
                and self.prefetcher.in_flight):
            # idle arena: nothing to overlap with, don't stall a step
            with tracing.span("admit"):
                self._tokenize_and_admit(
                    self.prefetcher.collect(step=self._step_no,
                                            tokens=self.engine.emitted_tokens)
                )

    def _admit_continuous(self) -> None:
        """Continuous + prefetch: per-request launches, out-of-FIFO collect.
        Each free slot collects whichever in-flight single-request wave is
        *ready* (device arrays landed, deferred owners resolved) via
        ``ready_index``/``collect_at`` — so one slow retrieval row delays
        only its own request, never its would-be wave-mates.  Launches are
        sandwiched between collect and tokenize/admit exactly like the wave
        schedule, keeping the admission overhead inside the next request's
        overlap window."""
        self._launch_pending()
        while self.engine.free_slots > 0 and self.prefetcher.in_flight:
            idx = self.prefetcher.ready_index()
            if idx is None:
                break
            with tracing.span("admit"):
                resolved = self.prefetcher.collect_at(
                    idx, step=self._step_no, tokens=self.engine.emitted_tokens
                )
                self._launch_pending()
                self._tokenize_and_admit(resolved)
        if (not self.engine.live.any() and not self.engine.queue
                and self.prefetcher.in_flight):
            # idle arena with nothing ready: block on the oldest wave rather
            # than burn empty steps (oldest first keeps deferred owners
            # resolving before their dependents)
            with tracing.span("admit"):
                self._tokenize_and_admit(
                    self.prefetcher.collect(step=self._step_no,
                                            tokens=self.engine.emitted_tokens)
                )
            self._launch_pending()

    # -- stepping -------------------------------------------------------------
    def step(self) -> list:
        """One engine step: admission (sync or prefetched, wave or
        continuous) + one decode step.  Returns the RAG requests that
        finished this step."""
        with tracing.span("step", pending=len(self.pending),
                          live=int(np.count_nonzero(self.engine.live)),
                          inflight=self.prefetcher.in_flight):
            if not self.prefetch:
                self._admit_sync()
            elif self.admission == "continuous":
                self._admit_continuous()
            else:
                self._admit_prefetch()
            finished_inner = self.engine.step()
        self._step_no += 1
        out = []
        for inner in finished_inner:
            r = self._inflight.pop(inner.ticket)
            r.out_tokens = inner.out_tokens
            r.truncated = inner.truncated
            r.first_token_at = inner.first_token_at
            r.done = True
            out.append(r)
        if self._terminal:
            # shed / failed requests surface through the same channel as
            # finished ones, exactly once
            out.extend(self._terminal)
            self._terminal.clear()
        return out

    def _drained(self) -> bool:
        return (not self.pending and not self.prefetcher.in_flight
                and not self.engine.queue and not self.engine.live.any()
                and not self._terminal)

    def run_to_completion(self, max_steps: int = 10_000) -> list:
        done = []
        for _ in range(max_steps):
            done.extend(self.step())
            if self._drained():
                return done
        raise RuntimeError(
            f"run_to_completion: work still pending after {max_steps} steps "
            f"({len(self.pending)} pending, {self.prefetcher.in_flight} "
            f"in-flight waves, {len(self.engine.queue)} queued, "
            f"{int(self.engine.live.sum())} live slots)"
        )

    # -- teardown / recovery --------------------------------------------------
    def abort(self, reason: str = "aborted") -> list:
        """Terminate every outstanding request and reconcile every layer:
        the pending queue is shed, in-flight prefetch waves are dropped
        (their in-flight cache keys released, so later lookups never defer
        to a dead wave), live decode slots are retired (paged KV blocks
        returned to the pool), and stranded admission tickets are cleared.
        The engine is immediately reusable for a fresh workload.  Returns
        every request that went terminal, exactly once."""
        while self.pending:
            self._shed(self.pending.popleft(), f"shed: {reason}")
        for r in self.prefetcher.abort():
            self._fail(r, f"aborted before admission: {reason}")
        for inner in self.engine.abort(reason=reason):
            r = self._inflight.pop(inner.ticket, None)
            if r is None:
                continue
            r.out_tokens = inner.out_tokens
            r.truncated = inner.truncated
            r.first_token_at = inner.first_token_at
            self._fail(r, inner.error or reason)
        for ticket in list(self._inflight):
            # tickets whose inner request the decode engine lost track of
            # (should be impossible; reconciled defensively)
            self._fail(self._inflight.pop(ticket), f"stranded: {reason}")
        out = list(self._terminal)
        self._terminal.clear()
        return out

    def drain(self, max_steps: int = 10_000) -> list:
        """``run_to_completion`` that never raises: if work is still
        outstanding after ``max_steps``, the stragglers are aborted and
        returned (``failed``/``shed``) alongside the completed requests, and
        the engine is left reusable."""
        done = []
        for _ in range(max_steps):
            done.extend(self.step())
            if self._drained():
                return done
        done.extend(self.abort(reason=f"drain gave up after {max_steps} steps"))
        return done

    # -- online mutation ------------------------------------------------------
    def apply_mutations(self, batch) -> "object":
        """Apply a :class:`repro.core.mutation.MutationBatch` to the live
        graph/index tier between decode steps, then invalidate every cache
        entry whose region the batch touched (releasing their prefix-share KV
        pins).  Returns the store's ``MutationReport``.

        Safe to interleave with :meth:`step`: the store builds *new* device
        arrays and re-points the pipeline (functional snapshot), so a
        retrieval wave already dispatched completes against its launch-time
        snapshot; the cache's epoch put-gate then refuses to insert those
        superseded results.  Call between steps, not from another thread.
        """
        store = getattr(self.pipeline, "mutation_store", None)
        if store is None:
            raise RuntimeError(
                "apply_mutations needs a pipeline built on a "
                "MutableGraphStore (see repro.core.mutation)"
            )
        report = store.apply(batch)
        self.mutation_batches += 1
        self.mutation_invalidated += self.cache.invalidate_regions(
            report.touched, report.epoch
        )
        if self.compact_every and \
                store.stats()["mutations_since_compact"] >= self.compact_every:
            store.compact()
        return report

    def health(self) -> dict:
        """Cheap health/load snapshot for a fronting router — raw counters
        only, no derived stats (``stats()`` is the full surface).  The fault
        counters are cumulative; the router scores health on their *deltas*
        between steps (a climbing counter, not a large one, is the signal).
        """
        p = self.prefetcher
        return {
            # fault signals (cumulative)
            "retries": p.retries,
            "timeouts": p.timeouts,
            "retrieval_failures": p.failures,
            "failed": self.failed_count,
            "degraded": self.degraded_count,
            "stale_served": self.stale_served,
            "shed": self.shed_count,
            # load signals (instantaneous)
            "pending": len(self.pending),
            "inflight_waves": p.in_flight,
            "inflight_requests": p.in_flight_requests,
            "admitted": len(self._inflight),
            "live_slots": int(self.engine.live.sum()),
            "free_slots": self.engine.free_slots,
            "queued": len(self.engine.queue),
        }

    def stats_ns(self) -> dict:
        """Namespaced stats: one sub-dict per serving layer (``cache``,
        ``engine``, ``prefetch``, ``decode``, ``mutation`` — plus ``router``
        when fronted by a :class:`~repro.serving.router.ReplicaRouter`).
        :meth:`stats` is the flat compatibility view of exactly this tree
        (see :func:`repro.serving.stats.flatten_stats`)."""
        ns = {
            "cache": self.cache.stats(),
            "engine": {
                "retrieval_batches": self.retrieval_batches,
                "retrieved_queries": self.retrieved_queries,
                "retrieval_seconds": self.retrieval_seconds,
                "prefetch": self.prefetch,
                "admission": self.admission,
                "shed": self.shed_count,
                "failed": self.failed_count,
                "degraded": self.degraded_count,
                "stale_served": self.stale_served,
                "degraded_mode": self.degraded_mode,
            },
            "prefetch": self.prefetcher.stats(),
            "decode": self.engine.decode_stats(),
        }
        store = getattr(self.pipeline, "mutation_store", None)
        mut = dict(store.stats()) if store is not None else {}
        mut["batches"] = self.mutation_batches
        mut["invalidated"] = self.mutation_invalidated
        ns["mutation"] = mut
        return ns

    def stats(self) -> dict:
        return flatten_stats(self.stats_ns())
